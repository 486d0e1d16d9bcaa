"""Counter kernel: the bytes the power-counter passes of the window must
move (uint16 stream words in, int32 per-lane counters and per-cycle zero
counts out; padding excluded) at the chip's HBM bandwidth, over the
kernel's device time. Memory bound: the chip's published peaks give no
integer vector rate to bound its operations by."""
from bench.harness import trace
from bench.metrics._programs import COUNTER_OPS


def read(ctx):
    if not ctx.monitored:
        return None
    n, ns = trace.op_time(ctx.trace, ctx.t0, ctx.t1,
                          lambda name: "custom-call" in name
                          and any(k in name for k in COUNTER_OPS))
    if not n or not ns:
        return None
    cfg, w = ctx.config, ctx.window
    rows = cfg["engine"].get("max_slots") or cfg["engine"]["max_rows"]
    need = (len(w["decode_log"]) * ctx.counts.counter_bytes_decode(cfg, rows)
            + sum(ctx.counts.counter_bytes_prefill(cfg, r)
                  for r in w["acct_rows"]))
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / (ns * 1e-9)
