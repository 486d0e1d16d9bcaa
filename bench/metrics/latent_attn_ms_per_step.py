"""Latent attention: device time of the decode program's operations
under the ``mla`` scope (projections, the latent cache's row writes and
reads, the absorbed attention), per decode run. The weight casts XLA
hoists out of the layer scan are in no scope and not in it
(``_scopes``)."""
from bench.metrics._scopes import per_decode_run


def read(ctx):
    got = per_decode_run(ctx, "mla")
    if got is None:
        return None
    runs, sec = got
    return 1e3 * sec / runs
