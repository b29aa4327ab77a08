"""Find an open-loop cell's knee on the chip: the highest offered rate the
system sustains without a growing backlog.

    python3 bench/sweep.py --workload <cell> --rates 2,4,6,8 --seconds 20

Runs the cell once per rate in one process (the mix's ``rate_per_s``
replaced), and prints per rate the requests due in the window, the most
requests queued in each quarter of the window and at its close, and the
tails.  The cell's traffic file then fixes its rate at about four fifths of
the knee.
"""
import argparse
import copy
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    base = spec.cell(args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        cell = copy.deepcopy(base)
        cell["traffic"]["rate_per_s"] = rate
        res = run.run_cell(cell, args.seed, args.seconds, False)
        info = res["_info"]
        w = info["window"]
        print(json.dumps({
            "rate_per_s": rate, "due": info["requests_sent"],
            "backlog_at_close": info["backlog_at_close"],
            "backlog_by_quarter": info["backlog_by_quarter"],
            "finished": info["requests_finished"],
            "ttft_p50_ms": w["ttft_p50_ms"], "ttft_p90_ms": w["ttft_p90_ms"],
            "tpot_p90_ms": w["tpot_p90_ms"], "tok_s": w["tok_s"],
            "correct": res["correct"],
        }), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
