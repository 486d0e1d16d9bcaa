"""A configuration file names its architecture family; the program side
(``bench/families/<family>.py``) and the float32 reference
(``bench/reference/<family>.py``) are found by that name. A family added
as two new files runs through the whole harness with no edit to any
file the benchmark has, and ``validate`` refuses a configuration whose
family is missing or incomplete."""
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _paths  # noqa: E402,F401
import _tiny  # noqa: E402

import pytest  # noqa: E402

from bench.harness import cell, spec  # noqa: E402

BENCH = spec.load_benchmark()
NEW = "dense_copy"


def _checkout(tmp_path):
    """A copy of the benchmark's files with one more configuration, of a
    family that has no files yet, and one cell on it."""
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cfg = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                      "qwen1.5-0.5b.json"))
    cfg.update(name="qwen-copy", family=NEW)
    (root / "bench" / "configs" / "qwen-copy.json").write_text(
        json.dumps(cfg))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "qwen-copy", "source": cfg["source"],
        "file": "bench/configs/qwen-copy.json", "reduced": [],
        "why": "the dense family under another name"})
    bench["workloads"].append({
        "name": "copy.offline.plain", "config": "qwen-copy",
        "traffic": "offline_plain", "chips": 1, "why": "a family as data"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, bench


def _add_family(root):
    for part in ("families", "reference"):
        shutil.copy(root / "bench" / part / "dense.py",
                    root / "bench" / part / f"{NEW}.py")


def test_added_family_needs_two_files(tmp_path, monkeypatch):
    root, bench = _checkout(tmp_path)
    _add_family(root)
    assert spec.validate(bench, str(root)) == []
    c = spec.resolve(bench, "copy.offline.plain", str(root))
    used = []
    for mod, fn in ((c.family, "arch_config"), (c.family, "plain_weights"),
                    (c.reference, "token_gaps")):
        assert os.path.dirname(os.path.dirname(mod.__file__)) == str(
            root / "bench")
        assert os.path.basename(mod.__file__) == f"{NEW}.py"
        orig = getattr(mod, fn)
        monkeypatch.setattr(mod, fn, lambda *a, _f=orig, _n=fn, **kw: (
            used.append(_n), _f(*a, **kw))[1])
    r = cell.run(_tiny.shrink(c), 2 ** 31 + 91, 1.0, False,
                 time.perf_counter(), require_tpu=False, root=str(root))
    assert r["correct"], r["checks"]
    assert set(r["checks"]) == {"logit_gap", "logit_gap_mean"}
    assert all(v["value"] is not None for v in r["checks"].values())
    assert {"arch_config", "plain_weights", "token_gaps"} <= set(used)


def _drop_family_key(root):
    p = root / "bench" / "configs" / "qwen-copy.json"
    cfg = json.loads(p.read_text())
    del cfg["family"]
    p.write_text(json.dumps(cfg))


def _bad_family_name(root):
    p = root / "bench" / "configs" / "qwen-copy.json"
    cfg = json.loads(p.read_text())
    cfg["family"] = "../dense"
    p.write_text(json.dumps(cfg))


@pytest.mark.parametrize("fault,found", [
    (_drop_family_key, "qwen-copy: no family"),
    (lambda root: os.remove(root / "bench" / "reference" / f"{NEW}.py"),
     f"has no bench/reference/{NEW}.py"),
    (lambda root: os.remove(root / "bench" / "families" / f"{NEW}.py"),
     f"has no bench/families/{NEW}.py"),
    (_bad_family_name, "qwen-copy: family: bad name"),
], ids=["no_family_key", "no_reference", "no_program_side", "bad_name"])
def test_config_without_a_whole_family_is_refused(tmp_path, fault, found):
    root, bench = _checkout(tmp_path)
    _add_family(root)
    fault(root)
    errs = spec.validate(bench, str(root))
    assert any(found in e for e in errs), errs
