"""Engine loop / scheduler: mean live rows per decode step in the window
(the engine's own ``occupancy_sum / decode_steps`` counts)."""


def read(ctx):
    w = ctx.window
    if not w["decode_steps"]:
        return None
    return w["occupancy_sum"] / w["decode_steps"]
