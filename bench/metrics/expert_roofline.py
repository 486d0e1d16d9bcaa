"""Expert layer: the bytes the ``moe`` scope needs each decode step (the
router, shared and held experts' weights once at bf16, the rows'
activations in and out of each expert layer; the family's
``expert_step_bytes``) at the chip's HBM bandwidth, over the scope's
device time. Memory bound: a held expert sees a few tokens a step."""
from bench.metrics._scopes import per_decode_run


def read(ctx):
    got = per_decode_run(ctx, "moe")
    log = ctx.window["decode_log"]
    if got is None or not log:
        return None
    runs, sec = got
    per_step = sum(ctx.counts.expert_step_bytes(ctx.config, rows)
                   for rows, _ in log) / len(log)
    return 100.0 * runs * per_step / ctx.peaks["hbm_bytes_per_s"] / sec
