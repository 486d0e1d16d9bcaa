"""A cell of the benchmark cut to CPU size: the same harness, engine
paths, traffic kinds and checks, with tiny widths, short windows and
the configured limits."""
import os
import time

from bench.harness import cell, spec

TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, vocab_size=256, attn_block_k=32)


def _shrink_sessions(t):
    t.update(session_rate=2.0, block=6, warm_seconds=0.5, think_mean_s=0.2)
    t["system_prompts"].update(min=32, max=64)
    t["user"].update(min=16, max=32, median=16)
    t["output"].update(min=16, max=32, median=16)


#: cells the harness runs that ``BENCHMARK.json`` does not list (yet):
#: name -> (configuration, its file, traffic)
UNLISTED = {"granite2b.sessions.monitored":
            ("granite-3-2b", "bench/configs/granite-3-2b.json",
             "sessions_monitored")}


def _with_unlisted(bench: dict, name: str) -> dict:
    if name not in UNLISTED or any(w["name"] == name
                                   for w in bench["workloads"]):
        return bench
    config, path, traffic = UNLISTED[name]
    bench = dict(bench, workloads=bench["workloads"] + [
        {"name": name, "config": config, "traffic": traffic, "chips": 1}])
    if all(c["name"] != config for c in bench["configs"]):
        bench["configs"] = bench["configs"] + [
            {"name": config, "file": path, "reduced": []}]
    return bench


def tiny_cell(name: str) -> spec.Cell:
    return shrink(spec.resolve(_with_unlisted(spec.load_benchmark(), name),
                               name))


def shrink(c: spec.Cell) -> spec.Cell:
    """A resolved cell cut to CPU size, in place."""
    cfg = c.config
    cfg.update(TINY)
    cfg["num_key_value_heads"] = 4 if cfg["model_type"] == "qwen2" else 2
    e = cfg["engine"]
    if e["mode"] == "slot":
        e.update(max_slots=4, cache_len=256)
    else:
        e.update(max_rows=4, cache_len=256, num_pages=72, prefill_chunk=64)
    t = c.traffic
    if t["kind"] == "closed_loop":
        t.update(clients=8, cycle=8, warm_seconds=0.5)
        t["prompt"].update(min=8, max=120, median=40, quantum=8)
        t["output"].update(min=4, max=24, median=10)
    elif t["kind"] == "sessions":
        _shrink_sessions(t)
    else:
        t.update(rate=4.0, block=8, warm_seconds=0.5)
        t["output"].update(min=16, max=32, median=16)

    def load_traffic(n):
        d = spec.load_json(os.path.join(spec.BENCH_DIR, "traffic",
                                        n + ".json"))
        _shrink_sessions(d)
        return d
    c.load_traffic = load_traffic
    return c


def run(name: str, root: str, seed: int = 2 ** 31 + 77,
        seconds: float = 1.0, traced: bool = False) -> dict:
    return cell.run(tiny_cell(name), seed, seconds, traced,
                    time.perf_counter(), require_tpu=False, root=root)
