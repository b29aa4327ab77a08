"""BENCHMARK.json keeps to the contract that later PRs build on: names and
units from the allowed characters, every file a cell needs present, every
per-layer metric with a reader and listed only in cells that report the
end-to-end metric it moves."""
import os
import re

import pytest
import spec

B = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_keys_and_names():
    assert set(B) == KEYS["top"]
    for kind, entries in (("config", B["configs"]),
                          ("workload", B["workloads"]),
                          ("end_to_end", B["end_to_end"]),
                          ("per_layer", B["per_layer"])):
        names = [e["name"] for e in entries]
        assert len(names) == len(set(names))
        for e in entries:
            assert set(e) - {"workloads"} == KEYS[kind], e["name"]
            assert NAME.match(e["name"]), e["name"]
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for c in B["configs"]:
        assert _line(c["why"]) and _line(c["source"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in B["workloads"]:
        assert _line(w["why"]) and w["chips"] in (1, 4)
    for m in B["per_layer"]:
        assert _line(m["layer"])
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) < 65536


def test_bounds_and_length():
    for m in B["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    assert (2 + 14 * 24) * (B["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_every_cell_finds_its_files():
    configs = {c["name"] for c in B["configs"]}
    used = set()
    for w in B["workloads"]:
        cell = spec.cell(w["name"])
        assert cell["config"]["name"] == w["config"] in configs
        used.add(w["config"])
        e2e = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell["per_layer"]
    assert used == configs
    files = [c["file"] for c in B["configs"]]
    assert len(files) == len(set(files))
    for f in files:
        assert f.startswith(B["paths"][0] + "/")


def test_per_layer_metrics_read_what_their_cells_report():
    for m in B["per_layer"]:
        assert callable(spec.reader(m["name"]))
        moved = next(e for e in B["end_to_end"] if e["name"] == m["moves"])
        for w in m["workloads"]:
            assert "workloads" not in moved or w in moved["workloads"], (
                m["name"], w)


@pytest.mark.parametrize("metric", ["nope", "idle_share.nope"])
def test_unknown_reader_is_an_error(metric):
    if metric == "nope":
        with pytest.raises(FileNotFoundError):
            spec.reader(metric)
    else:  # a split name falls back to its base name's reader
        assert callable(spec.reader(metric))


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        spec.peaks("cpu")
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
