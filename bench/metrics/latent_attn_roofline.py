"""Latent attention: the bytes the ``mla`` scope needs each decode step
(every layer's attention weights once at bf16, the live latent positions
read, one new position per row written; the family's
``latent_step_bytes``) at the chip's HBM bandwidth, over the scope's
device time. Memory bound: one query a row against the whole cache."""
from bench.metrics._scopes import per_decode_run


def read(ctx):
    got = per_decode_run(ctx, "mla")
    log = ctx.window["decode_log"]
    if got is None or not log:
        return None
    runs, sec = got
    per_step = sum(ctx.counts.latent_step_bytes(ctx.config, pos, rows)
                   for rows, pos in log) / len(log)
    return 100.0 * runs * per_step / ctx.peaks["hbm_bytes_per_s"] / sec
