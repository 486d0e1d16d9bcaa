"""Decode step: the bytes each step needs (bf16 parameters once, the live
cache positions, one new position per row) at the chip's HBM bandwidth,
over the decode program's device time. Memory bound: a step's model
FLOPs at the bf16 peak take far less than its bytes."""
from bench.metrics._programs import DECODE, device_seconds


def read(ctx):
    runs, sec = device_seconds(ctx, DECODE)
    log = ctx.window["decode_log"]
    if not runs or not log or not sec:
        return None
    per_step = sum(ctx.counts.decode_step_bytes(ctx.config, pos, rows)
                   for rows, pos in log) / len(log)
    need = runs * per_step / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * need / sec
