"""``BENCHMARK.json`` and the files it names, resolved by name.

A cell names a configuration and a traffic mix; the configuration's
file is the one ``configs`` lists for it, and its ``family`` names
``bench/families/<family>.py`` (the program side of the architecture)
and ``bench/reference/<family>.py`` (its float32 reference); the mix is
``bench/traffic/<traffic>.json`` with its generator in
``bench/traffic/kinds/<kind>.py``, and each per-layer metric is read by
``bench/metrics/<name>.py``. Nothing here knows a cell by name: adding a
cell, a mix, a metric or an architecture family is adding files and
entries.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import re
import sys
import types

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def check_name(name: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ValueError(f"bad name {name!r}")
    return name


def check_unit(unit: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise ValueError(f"bad unit {unit!r}")
    return unit


def load_benchmark(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        return json.load(f)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list[dict]
    per_layer: list[dict]
    bench_dir: str
    family: types.ModuleType
    reference: types.ModuleType

    def load_traffic(self, name: str) -> dict:
        return load_json(os.path.join(self.bench_dir, "traffic",
                                      check_name(name) + ".json"))


def _load_file(path: str, modname: str):
    """The module of ``path``, executed once per process and path."""
    mod = sys.modules.get(modname)
    if mod is not None and getattr(mod, "__file__", None) == path:
        return mod
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def load_kind(kind: str, bench_dir: str = BENCH_DIR):
    """The generator class of a traffic kind: the one class defined in
    ``bench/traffic/kinds/<kind>.py`` that has ``start``."""
    mod = _load_file(os.path.join(bench_dir, "traffic", "kinds",
                                  check_name(kind) + ".py"),
                     f"bench_kind_{kind}")
    for obj in vars(mod).values():
        if (isinstance(obj, type) and obj.__module__ == mod.__name__
                and hasattr(obj, "start")):
            return obj
    raise ValueError(f"traffic kind {kind!r} defines no generator")


def load_metric(name: str, bench_dir: str = BENCH_DIR):
    """The reader module of a per-layer metric."""
    return _load_file(os.path.join(bench_dir, "metrics",
                                   check_name(name) + ".py"),
                      f"bench_metric_{name}")


def family_files(family: str, bench_dir: str = BENCH_DIR
                 ) -> tuple[str, str]:
    """The program-side module and the reference module of a family."""
    name = check_name(family) + ".py"
    return (os.path.join(bench_dir, "families", name),
            os.path.join(bench_dir, "reference", name))


def load_family(family: str, bench_dir: str = BENCH_DIR
                ) -> tuple[types.ModuleType, types.ModuleType]:
    """``(family module, reference module)`` of an architecture family."""
    prog, ref = family_files(family, bench_dir)
    return (_load_file(prog, f"bench_family_{family}"),
            _load_file(ref, f"bench_reference_{family}"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(bench: dict, cell_name: str, root: str = ROOT) -> Cell:
    """The cell's configuration, traffic and metrics, by name."""
    bench_dir = os.path.join(root, "bench")
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"unknown workload {cell_name!r}; have {sorted(cells)}")
    w = cells[cell_name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(os.path.join(root, conf["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     check_name(w["traffic"]) + ".json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, cell_name)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, cell_name) and m["moves"] in moved]
    family, reference = load_family(config["family"], bench_dir)
    return Cell(cell_name, config, traffic, int(w["chips"]),
                e2e, per_layer, bench_dir, family, reference)


def validate(bench: dict, root: str = ROOT) -> list[str]:
    """Problems with the file and the files it names (empty when sound)."""
    errs = []

    def need(cond, msg):
        if not cond:
            errs.append(msg)

    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x.get("name") for x in bench.get(group, [])]
        need(len(names) == len(set(names)), f"duplicate names in {group}")
        for n in names:
            try:
                check_name(n)
            except ValueError as e:
                errs.append(f"{group}: {e}")
    for m in bench["end_to_end"] + bench["per_layer"]:
        try:
            check_unit(m["unit"])
        except ValueError as e:
            errs.append(f"{m['name']}: {e}")
        need(m["better"] in ("lower", "higher"), f"{m['name']}: better")
    need(any(m["name"] == "setup_s" for m in bench["end_to_end"]),
         "no setup_s")
    for c in bench["configs"]:
        path = os.path.join(root, c["file"])
        need(os.path.isfile(path), f"missing config file {c['file']}")
        family = (load_json(path).get("family")
                  if os.path.isfile(path) else None)
        need(family is not None, f"{c['name']}: no family")
        if family is not None:
            try:
                files = family_files(family, os.path.join(root, "bench"))
            except ValueError as e:
                errs.append(f"{c['name']}: family: {e}")
                files = ()
            for f in files:
                need(os.path.isfile(f), f"{c['name']}: family {family!r} "
                     f"has no {os.path.relpath(f, root)}")
        for k in c["reduced"]:
            try:
                check_name(k)
            except ValueError as e:
                errs.append(f"reduced: {e}")
    e2e_names = {m["name"] for m in bench["end_to_end"]}
    cell_names = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        for w in m.get("workloads", []):
            need(w in cell_names, f"{m['name']}: unknown workload {w!r}")
    moved_in = {w: {m["name"] for m in bench["end_to_end"]
                    if _applies(m, w)} for w in cell_names}
    for m in bench["per_layer"]:
        need(m["moves"] in e2e_names, f"{m['name']} moves unknown metric")
        for w in m.get("workloads", []):
            need(m["moves"] in moved_in.get(w, ()),
                 f"{m['name']}: {w} does not report {m['moves']}")
        need(os.path.isfile(os.path.join(root, "bench", "metrics",
                                         m["name"] + ".py")),
             f"no reader bench/metrics/{m['name']}.py")
    for w in bench["workloads"]:
        check = [w["config"], w["traffic"]]
        for n in check:
            try:
                check_name(n)
            except ValueError as e:
                errs.append(f"{w['name']}: {e}")
        need(w["config"] in {c["name"] for c in bench["configs"]},
             f"{w['name']}: unknown config")
        tpath = os.path.join(root, "bench", "traffic", w["traffic"] + ".json")
        need(os.path.isfile(tpath), f"{w['name']}: no traffic file")
        if os.path.isfile(tpath):
            kind = load_json(tpath)["kind"]
            need(os.path.isfile(os.path.join(
                root, "bench", "traffic", "kinds", kind + ".py")),
                f"{w['name']}: no generator for kind {kind!r}")
        e2e = [m for m in bench["end_to_end"] if _applies(m, w["name"])]
        need(any(m["name"] == "setup_s" for m in e2e)
             and len(e2e) >= 2, f"{w['name']}: end-to-end metrics")
        cell_pl = [m for m in bench["per_layer"] if _applies(m, w["name"])
                   and m["moves"] in {x["name"] for x in e2e}]
        need(bool(cell_pl), f"{w['name']}: no per-layer metric")
    return errs
