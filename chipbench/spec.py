"""``BENCHMARK.json``: validation, and the files each of its names leads to.

The harness is driven by data. A cell (``workloads`` entry) names a
configuration and a traffic mix; each lives in a file of its own, and so
does each per-layer metric's reader and each cell's correctness limit:

    configs/<file named by the configuration entry>
    mixes/<traffic>.json
    metrics/<per-layer metric name>.py      (defines ``read(run)``)
    limits/<workload name>.json

``Layout`` resolves those names under one root directory, so a later
change adds a cell, a mix or a metric by adding files, and a test can
point the same code at a directory of its own.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from typing import Dict, List

PKG = Path(__file__).resolve().parent
REPO = PKG.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT_RE = re.compile(r"^[^\t\n\r]{1,200}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")

TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
E2E_SOURCES = {"host_clock", "device_trace"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# Keys that name a width: never cut (``reduced`` may not list them).
WIDTH_RE = re.compile(
    r"(_dim|_rank)$|^hidden_size$|intermediate|latent|state_size|proj|"
    r"head_size|expan|per_tok")


def _name_ok(s) -> bool:
    return isinstance(s, str) and bool(NAME_RE.match(s))


def validate(b: dict) -> List[str]:
    """Every way ``b`` breaks the benchmark's rules, as messages; an
    empty list means it is sound."""
    errs: List[str] = []

    def need(cond, msg):
        if not cond:
            errs.append(msg)

    need(set(b) == TOP_KEYS, f"top-level keys {sorted(b)}")
    cmd = b.get("command", [])
    need(isinstance(cmd, list) and 1 <= len(cmd) <= 32
         and all(isinstance(w, str) and TEXT_RE.match(w) for w in cmd),
         "command: 1-32 words of 1-200 characters")
    paths = b.get("paths", [])
    need(isinstance(paths, list) and 1 <= len(paths) <= 16, "paths: 1-16")
    for p in paths if isinstance(paths, list) else []:
        need(isinstance(p, str) and PATH_RE.match(p)
             and not p.startswith("/") and ".." not in p.split("/"),
             f"path {p!r}")
    rs = b.get("run_seconds")
    need(isinstance(rs, int) and 1 <= rs <= 51, f"run_seconds {rs!r}")

    cfg_names = set()
    for c in b.get("configs", []):
        need(set(c) == CONFIG_KEYS, f"config keys {sorted(c)}")
        need(_name_ok(c.get("name")), f"config name {c.get('name')!r}")
        need(c.get("name") not in cfg_names, f"config {c.get('name')} twice")
        cfg_names.add(c.get("name"))
        for k in ("source", "why"):
            need(isinstance(c.get(k), str) and TEXT_RE.match(c[k]),
                 f"config {c.get('name')}: {k}")
        red = c.get("reduced", [])
        need(isinstance(red, list) and len(red) <= 16
             and all(_name_ok(k) for k in red),
             f"config {c.get('name')}: reduced")
        for k in red if isinstance(red, list) else []:
            need(not WIDTH_RE.search(str(k)),
                 f"config {c.get('name')}: reduced names a width {k!r}")
        f = c.get("file", "")
        need(isinstance(f, str) and any(
            f.startswith(p.rstrip("/") + "/") for p in paths),
            f"config {c.get('name')}: file {f!r} outside paths")
    need(1 <= len(cfg_names) <= 24, "configs: 1-24")
    need(len({c.get("file") for c in b.get("configs", [])})
         == len(b.get("configs", [])), "two configurations share a file")

    cells = set()
    used_cfgs = set()
    pairs = set()
    for w in b.get("workloads", []):
        need(set(w) == WORKLOAD_KEYS, f"workload keys {sorted(w)}")
        for k in ("name", "config", "traffic"):
            need(_name_ok(w.get(k)), f"workload {w.get('name')}: {k}")
        need(w.get("name") not in cells, f"workload {w.get('name')} twice")
        cells.add(w.get("name"))
        need(w.get("config") in cfg_names,
             f"workload {w.get('name')}: unknown config")
        used_cfgs.add(w.get("config"))
        pair = (w.get("config"), w.get("traffic"))
        need(pair not in pairs, f"pair {pair} twice")
        pairs.add(pair)
        need(w.get("chips") in (1, 4), f"workload {w.get('name')}: chips")
        need(isinstance(w.get("why"), str) and TEXT_RE.match(w["why"]),
             f"workload {w.get('name')}: why")
    need(1 <= len(cells) <= 24, "workloads: 1-24")
    need(used_cfgs == cfg_names, "a configuration no cell uses")

    metric_names = set()
    e2e_cells: Dict[str, set] = {}
    for m in b.get("end_to_end", []):
        need(set(m) - {"workloads"} == E2E_KEYS, f"metric keys {sorted(m)}")
        need(m.get("source") in E2E_SOURCES, f"{m.get('name')}: source")
        bound = m.get("bound")
        need(isinstance(bound, (int, float)) and 0.01 <= bound <= 0.25,
             f"{m.get('name')}: bound {bound!r}")
        e2e_cells[m.get("name")] = set(m.get("workloads", cells))
    need("setup_s" in e2e_cells and "workloads" not in next(
        (m for m in b.get("end_to_end", []) if m.get("name") == "setup_s"),
        {}), "setup_s in every cell")
    need(1 <= len(e2e_cells) <= 16, "end_to_end: 1-16")
    for m in b.get("per_layer", []):
        need(set(m) - {"workloads"} == LAYER_KEYS, f"metric keys {sorted(m)}")
        need(m.get("source") in SOURCES, f"{m.get('name')}: source")
        need(isinstance(m.get("layer"), str) and TEXT_RE.match(m["layer"]),
             f"{m.get('name')}: layer")
        need(m.get("moves") in e2e_cells, f"{m.get('name')}: moves")
        need(set(m.get("workloads", cells))
             <= e2e_cells.get(m.get("moves"), set()),
             f"{m.get('name')}: a listed cell does not report "
             f"{m.get('moves')}")
        if m.get("name", "").endswith("_roofline"):
            need(m.get("unit") == "%", f"{m.get('name')}: unit %")
    need(1 <= len(b.get("per_layer", [])) <= 128, "per_layer: 1-128")
    for m in b.get("end_to_end", []) + b.get("per_layer", []):
        need(_name_ok(m.get("name")), f"metric name {m.get('name')!r}")
        need(m.get("name") not in metric_names, f"{m.get('name')} twice")
        metric_names.add(m.get("name"))
        need(isinstance(m.get("unit"), str) and UNIT_RE.match(m["unit"]),
             f"{m.get('name')}: unit {m.get('unit')!r}")
        need(m.get("better") in ("lower", "higher"),
             f"{m.get('name')}: better")
        need(set(m.get("workloads", [])) <= cells,
             f"{m.get('name')}: unknown workload")
    for cell in cells:
        rep = [n for n, cs in e2e_cells.items() if cell in cs]
        need(len(rep) >= 2, f"{cell}: needs setup_s and one more metric")
        need(any(cell in set(m.get("workloads", cells))
                 for m in b.get("per_layer", [])),
             f"{cell}: no per-layer metric")
    return errs


class Layout:
    """Where the files named in ``BENCHMARK.json`` live. ``root`` is the
    benchmark's directory; configuration files are named relative to
    ``repo`` (the directory holding ``BENCHMARK.json``)."""

    def __init__(self, root: Path = PKG, repo: Path = REPO):
        self.root = Path(root)
        self.repo = Path(repo)

    def bench(self) -> dict:
        b = json.loads((self.repo / "BENCHMARK.json").read_text())
        errs = validate(b)
        if errs:
            raise ValueError("BENCHMARK.json: " + "; ".join(errs))
        return b

    def config(self, bench: dict, name: str) -> dict:
        entry = next(c for c in bench["configs"] if c["name"] == name)
        return json.loads((self.repo / entry["file"]).read_text())

    def mix(self, traffic: str) -> dict:
        return json.loads((self.root / "mixes" / f"{traffic}.json")
                          .read_text())

    def limits(self, workload: str) -> dict:
        return json.loads((self.root / "limits" / f"{workload}.json")
                          .read_text())

    def metric_reader(self, name: str):
        """The ``read(run)`` function of a per-layer metric's file."""
        path = self.root / "metrics" / f"{name}.py"
        mod_name = "chipbench_metric_" + re.sub(r"\W", "_", name)
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def metrics_for(bench: dict, name: str, kind: str) -> List[dict]:
    """The ``kind`` ('end_to_end' or 'per_layer') metrics cell ``name``
    reports."""
    return [m for m in bench[kind]
            if name in m.get("workloads", [name])]
