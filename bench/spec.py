"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each lives in a file of its
own (``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``), and
each per-layer metric has a reader ``bench/metrics/<name>.py`` (or, for a
metric split by cell kind such as ``idle_share.render``, the reader of its
base name, ``bench/metrics/idle_share.py``).  Nothing here knows any cell.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(name: str, root: str = ROOT) -> Dict[str, Any]:
    """Everything one cell needs: its entry, configuration, traffic mix and
    the metrics it reports (end-to-end and per-layer)."""
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    entry = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[entry["config"]]
    return {
        "workload": entry,
        "config": load_json(os.path.join(root, cfg_entry["file"])),
        "traffic": load_json(os.path.join(BENCH_DIR, "traffic",
                                          entry["traffic"] + ".json")),
        "end_to_end": metrics_for(bench["end_to_end"], name),
        "per_layer": metrics_for(bench["per_layer"], name),
        "run_seconds": bench["run_seconds"],
    }


def metrics_for(metrics: List[Dict[str, Any]], workload: str):
    return [m for m in metrics
            if "workloads" not in m or workload in m["workloads"]]


def peaks(device_kind: str) -> Dict[str, float]:
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]


def reader(metric: str):
    """The ``read(ctx)`` function of a per-layer metric."""
    for stem in (metric, metric.split(".")[0]):
        path = os.path.join(BENCH_DIR, "metrics", stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "bench_metric_" + stem.replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader bench/metrics/{metric}.py")
