"""Engine loop and scheduler: blocking device-to-host fetches per engine
step in the window. Each fetch the engine counts in
``stats["host_syncs"]`` runs under one ``serve.sync`` span; this counts
those that start in the traced window."""
from bench.metrics import _serve_spans


def read(ctx):
    steps = ctx.window["engine_steps"]
    n = sum(1 for name, s, _ in _serve_spans.spans(ctx)
            if name == "serve.sync" and ctx.t0 <= s <= ctx.t1)
    if not steps or not n:
        return None
    return n / steps
