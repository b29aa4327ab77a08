"""Self-tests of the benchmark: ``python -m pytest bench/tests`` (on the
CPU; the repository's own ``testpaths`` stay ``tests``).  They import the
benchmark's modules from ``bench/`` and the program from ``src/``."""
import copy
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402
import spec  # noqa: E402

# the published widths cut to what a CPU test holds; everything else (the
# mixes' shapes, the engine, the loop, the check) is the cell's own
SMOKE_MODEL = {
    "musicgen-medium": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                            head_dim=16, d_ff=128, vocab_size=256),
    "internvl2-2b": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                         head_dim=16, d_ff=128, vocab_size=512),
}
SMOKE_TRAFFIC = {
    "render": {"requests": 3000,
               "prompt": [{"dist": "lognormal", "median": 12, "sigma": 0.6,
                           "min": 4, "max": 40}],
               "gen": {"dist": "uniform", "min": 12, "max": 40}},
    "vqa": {"rate_per_s": 6.0, "preroll_s": 0.5,
            "prompt": [{"dist": "choice", "values": [8, 24]},
                       {"dist": "lognormal", "median": 6, "sigma": 0.5,
                        "min": 2, "max": 12}],
            "gen": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                    "min": 3, "max": 16}}
}
SMOKE_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def smoke_cell(workload: str):
    """The cell ``workload`` of BENCHMARK.json at a CPU-test size."""
    cell = copy.deepcopy(spec.cell(workload))
    conf = cell["config"]
    conf["model"].update(SMOKE_MODEL[conf["arch"]])
    conf["engine"].update(max_slots=4, kv_pool_bytes=1 << 30,
                          prefill_rows_max=2)
    # the limit at this size, set between its readings (three seeds): the
    # program 0-0.0013, the fp8 control in its place 0.026-0.15
    conf["correct"].update(sample_tokens=48, sample_requests=3,
                           max_logit_gap=0.01)
    if "calibration_batch" in conf["substrate"]:
        conf["substrate"]["calibration_batch"] = [2, 16]
    cell["traffic"].update(SMOKE_TRAFFIC[cell["workload"]["traffic"]])
    cell["peaks"] = SMOKE_PEAKS
    return cell


@pytest.fixture
def smoke():
    return smoke_cell
