"""Engine loop and scheduler: device-idle time inside the engine's
``serve.step`` spans and outside its ``serve.accountant`` spans
(admission, sampling and its sync, retirement) per engine step in the
window, on the device trace's clock."""
from bench.metrics import _serve_spans


def read(ctx):
    steps = ctx.window["engine_steps"]
    found = _serve_spans.spans(ctx)
    if not steps or not any(n == "serve.step" for n, _, _ in found):
        return None
    ns = _serve_spans.idle_within(ctx.trace, ctx.t0, ctx.t1, found,
                                  ("serve.step",), ("serve.accountant",))
    return 1e-6 * ns / steps
