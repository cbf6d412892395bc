"""Finds what ``BENCHMARK.json`` names, by name: a cell's configuration
(``chipbench/configs/<config>.json``, as the configuration entry's ``file``
says), its traffic mix (``chipbench/traffic/<traffic>.json``, plus
``chipbench/traffic/<traffic>.py`` where the mix needs code of its own) and
each per-layer metric's reader (``chipbench/metrics/<metric>.py``, a module
with ``read(ctx) -> float | None``). A new cell, mix or metric is a new file
and a new entry; no file here changes."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = "chipbench"


def load(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(root: Path, bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(root: Path, name: str) -> dict:
    return json.loads((root / BENCH_DIR / "traffic" / f"{name}.json")
                      .read_text())


def _module(path: Path, prefix: str):
    name = prefix + path.stem.replace(".", "_").replace("-", "_")
    sp = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def traffic_driver(root: Path, name: str):
    """The ``drive`` function of ``chipbench/traffic/<name>.py``, or None
    where the mix has no code of its own and its file's ``kind`` names one
    of the harness's drivers."""
    path = root / BENCH_DIR / "traffic" / f"{name}.py"
    return _module(path, "chipbench_traffic_").drive if path.exists() \
        else None


def reader(root: Path, metric: str):
    """The ``read`` function of ``chipbench/metrics/<metric>.py``."""
    return _module(root / BENCH_DIR / "metrics" / f"{metric}.py",
                   "chipbench_metric_").read


def e2e_of(bench: dict, cell_name: str) -> list[dict]:
    """The end-to-end metrics a cell reports: those that list it under
    ``workloads``, and those without the key."""
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell_name in m["workloads"]]


def layers_of(bench: dict, cell_name: str) -> list[dict]:
    """The per-layer metrics a cell reports: those that list it under
    ``workloads``, and those without the key that move an end-to-end
    metric the cell reports."""
    e2e = {m["name"] for m in e2e_of(bench, cell_name)}
    return [m for m in bench["per_layer"]
            if cell_name in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in e2e)]


def per_layer(root: Path, bench: dict, cell_name: str, ctx: dict) -> dict:
    """Every per-layer metric of the cell whose reader finds something."""
    out = {}
    for m in layers_of(bench, cell_name):
        v = reader(root, m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
