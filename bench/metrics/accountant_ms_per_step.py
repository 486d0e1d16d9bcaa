"""Power accountant: host time inside the engine's accountant calls
(decode and prompt accounting, embedding lookups for it, retirement) per
engine step in the window, from the benchmark's wrappers."""


def read(ctx):
    w = ctx.window
    if not ctx.monitored or not w["engine_steps"]:
        return None
    return 1e3 * w["accountant_s"] / w["engine_steps"]
