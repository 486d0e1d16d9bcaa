"""Power accountant: device-idle time inside the engine's
``serve.accountant`` spans (decode and prompt accounting, retirement
booking) per engine step in the window, on the device trace's clock."""
from bench.metrics import _serve_spans


def read(ctx):
    steps = ctx.window["engine_steps"]
    found = _serve_spans.spans(ctx)
    if not ctx.monitored or not steps or not any(
            n == "serve.accountant" for n, _, _ in found):
        return None
    ns = _serve_spans.idle_within(ctx.trace, ctx.t0, ctx.t1, found,
                                  ("serve.accountant",))
    return 1e-6 * ns / steps
