"""Decode step: device time per run of the decode program."""
from bench.metrics._programs import DECODE, device_seconds


def read(ctx):
    runs, sec = device_seconds(ctx, DECODE)
    if not runs:
        return None
    return 1e3 * sec / runs
