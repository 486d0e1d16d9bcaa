"""Prefill: device time of the whole-prompt and chunk prefill programs
per 1000 prompt positions computed (reused prefix positions excluded)."""
from bench.metrics._programs import PREFILL, device_seconds


def read(ctx):
    runs, sec = device_seconds(ctx, PREFILL)
    toks = ctx.window["prompt_tokens"]
    if not runs or not toks:
        return None
    return 1e3 * sec / (toks / 1000.0)
