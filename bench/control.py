"""Readings for a cell's correctness limit, on the chip, in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 51

For each seed: one run of the cell as the benchmark makes it (the same
traffic, engine, window and sample of served requests) with the control in
the program's place: at the served positions, teacher-forced on the served
tokens, the tokens that the reference computed in the configuration's lower
precision (``correct.control``) puts first go through the run's own check.
The run's ``correct`` is then the control's verdict; the program's reading
on its served tokens, at the same positions, comes from the same run.  One
JSON line per seed.  The program's largest reading over a dozen seeds is the
limit's lower end, the control's smallest its upper end.
"""
import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402
import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(cell, seed, args.seconds, False,
                           control=cell["config"]["correct"]["control"])
        info = res.pop("_info")
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "program_gap": info["checked"]["program_logit_gap"],
            "control_gap": res["checks"]["max_logit_gap"]["value"],
            "limit": res["checks"]["max_logit_gap"]["limit"],
            "checked": info["checked"], "metrics": res["metrics"],
            "reference_s": info["reference_s"],
            "memory_peak_bytes": res["device"]["memory_peak_bytes"],
        }), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
