"""Stable names of the programs and kernels the per-layer metrics read,
mapped to the layer each belongs to."""

#: the decode step (``lm.make_decode_step``): the engine jits it as a
#: ``functools.partial`` over the kernel-backend selector, which JAX
#: names ``_unknown``; no other program of the serving path is a jitted
#: ``partial`` today (a stable name is for the program to give)
DECODE = ("jit__unknown",)
#: prompt prefill: whole prompts (``lm.make_slot_prefill_step``) and
#: paged chunks (``lm.make_chunk_prefill_step``)
PREFILL = ("jit_slot_prefill_step", "jit_chunk_prefill_step")
#: the power-counter Pallas kernel (``kernels/power_counters``): in the
#: device trace, custom calls named after its jitted wrapper
#: ``_edge_counters`` (``vmap_jit__edge_counters__`` when batched)
COUNTER_OPS = ("_edge_counters",)


def device_seconds(ctx, programs) -> tuple[int, float]:
    """Runs and device seconds of the named programs in the window."""
    from bench.harness import trace
    runs, ns = 0, 0.0
    for name, (n, t) in trace.program_time(ctx.trace, ctx.t0, ctx.t1).items():
        if name in programs:
            runs += n
            ns += t
    return runs, ns * 1e-9
