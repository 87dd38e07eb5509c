"""Finds everything a cell names, by name: ``BENCHMARK.json`` at the
checkout's root, ``configs/<config>.json``, ``traffic/<mix>.json``,
``metrics/<metric>.py``, ``reference/<family>.py``, ``work/<family>.py``
and ``peaks.json`` beside this file. A new configuration, traffic mix or
per-layer metric is a new file here and an entry in ``BENCHMARK.json``.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def cell(name: str) -> dict:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return _json(HERE / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return _json(HERE / "traffic" / f"{name}.json")


def family(name: str) -> tuple:
    """``(reference module, work module)`` of a model family."""
    return (importlib.import_module(f"chipbench.reference.{name}"),
            importlib.import_module(f"chipbench.work.{name}"))


def metric_reader(name: str):
    """``read(record) -> float | None`` of ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    table = _json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def metrics_for(cell_name: str, trace: bool, bench: dict) -> list:
    """The metric entries a cell reports: its end-to-end metrics without
    a trace, its per-layer metrics with one. An entry without a
    ``workloads`` list covers every cell (per-layer: every cell that
    reports the end-to-end metric it moves)."""
    e2e = [m for m in bench["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell_name in m.get("workloads", [cell_name])
            and m["moves"] in moved]
