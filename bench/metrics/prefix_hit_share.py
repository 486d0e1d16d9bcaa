"""Paged cache: share of the window's admissions that reused cached
prefix pages (the paged engine's ``prefix_hit_requests``)."""


def read(ctx):
    w = ctx.window
    if ctx.engine_mode != "paged" or not w["admissions"]:
        return None
    return 100.0 * w["prefix_hit_requests"] / w["admissions"]
