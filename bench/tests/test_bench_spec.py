"""BENCHMARK.json resolves, by name, to the files of every cell; a cell,
a mix and a metric added as data alone are picked up with no code edit."""
import json
import os
import shutil
import subprocess
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _paths  # noqa: E402,F401

import pytest  # noqa: E402

from bench.harness import spec  # noqa: E402

BENCH = spec.load_benchmark()


def test_file_is_sound():
    assert spec.validate(BENCH) == []
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert all(w["chips"] == 1 for w in BENCH["workloads"])


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(name):
    cell = spec.resolve(BENCH, name)
    assert cell.config["name"] == next(
        w["config"] for w in BENCH["workloads"] if w["name"] == name)
    kind = spec.load_kind(cell.traffic["kind"])
    assert hasattr(kind, "start") and hasattr(kind, "prefill_shapes")
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(spec.load_metric(m["name"]).read)


def test_metric_listing_a_cell_that_lacks_its_moved_metric_is_refused():
    bench = json.loads(json.dumps(BENCH))
    closed = [w["name"] for w in bench["workloads"]
              if spec.resolve(BENCH, w["name"]).traffic["kind"]
              == "closed_loop"]
    out = next(m for m in bench["end_to_end"] if m["name"] == "out_tok_s")
    assert set(out["workloads"]) == set(closed)
    # an open-loop cell, added as data, does not report out_tok_s
    bench["workloads"].append({
        "name": "qwen05b.sessions", "config": "qwen1.5-0.5b",
        "traffic": "sessions_monitored", "chips": 1, "why": "open loop"})
    assert spec.validate(bench) == []
    bench["per_layer"].append({
        "name": "steps_seen", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "engine loop and scheduler",
        "moves": "out_tok_s", "workloads": ["qwen05b.sessions"]})
    assert any("does not report out_tok_s" in e for e in spec.validate(bench))


@pytest.mark.parametrize("bad", ["a b", "a,b", "a/b", "", "-x", "é", "x" * 65])
def test_names_refused(bad):
    with pytest.raises(ValueError):
        spec.check_name(bad)


@pytest.mark.parametrize("bad", ["tokens per s", "", "µs", "x" * 17])
def test_units_refused(bad):
    with pytest.raises(ValueError):
        spec.check_unit(bad)


def test_names_and_units_in_file():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for x in BENCH[group]:
            spec.check_name(x["name"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        spec.check_unit(m["unit"])


def test_added_cell_mix_and_metric_need_no_code(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(json.dumps(BENCH))
    mix = spec.load_json(os.path.join(spec.BENCH_DIR, "traffic",
                                      "offline_plain.json"))
    mix["clients"] = 48
    (root / "bench" / "traffic" / "offline_small.json").write_text(
        json.dumps(mix))
    (root / "bench" / "metrics" / "steps_seen.py").write_text(
        "def read(ctx):\n    return float(ctx.window['engine_steps'])\n")
    bench["workloads"].append({
        "name": "qwen05b.offline.small", "config": "qwen1.5-0.5b",
        "traffic": "offline_small", "chips": 1, "why": "added as data"})
    # a closed-loop cell reports tokens per second
    next(m for m in bench["end_to_end"] if m["name"] == "out_tok_s")[
        "workloads"].append("qwen05b.offline.small")
    bench["per_layer"].append({
        "name": "steps_seen", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "engine loop and scheduler",
        "moves": "out_tok_s", "workloads": ["qwen05b.offline.small"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert spec.validate(bench, str(root)) == []
    cell = spec.resolve(bench, "qwen05b.offline.small", str(root))
    assert cell.traffic["clients"] == 48
    assert {"steps_seen", "serve_mfu", "serve_mfu.itl"} <= {
        m["name"] for m in cell.per_layer}
    reader = spec.load_metric("steps_seen", cell.bench_dir)
    ctx = types.SimpleNamespace(window={"engine_steps": 7})
    assert reader.read(ctx) == 7.0
    gen = spec.load_kind(cell.traffic["kind"], cell.bench_dir)(
        cell.traffic, 1, 1000, {"horizon": 2048})
    assert len(gen.start(1.0)) == 48


def test_run_without_a_tpu_prints_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = os.path.join(spec.BENCH_DIR, "run.py")
    p = subprocess.run(
        [sys.executable, run, "--workload", BENCH["workloads"][0]["name"],
         "--seed", str(2 ** 31 + 9), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120,
        cwd=spec.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_alone_in_a_directory_fails(tmp_path):
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH="src")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120, cwd=tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
