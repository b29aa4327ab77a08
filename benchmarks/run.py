"""Benchmark orchestrator (deliverable (d)): one entry per paper table/figure
plus the roofline + beyond-paper extensions.  Prints ``name,value,derived``
CSV rows (value is dB / fJ / seconds / count as per the name).

``--json PATH`` additionally writes a machine-readable report.  Suites that
expose ``bench_records()`` (currently the kernel micro-bench) contribute
structured per-shape records - wall time plus structural counters (MXU
calls, HBM bytes per operand class, noise-operand bytes before/after the
in-kernel-RNG rewrite); other suites contribute their CSV rows as dicts.
The committed ``BENCH_kernels.json`` baseline is produced with::

    PYTHONPATH=src python benchmarks/run.py --only kernel --json BENCH_kernels.json
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time

# make `python benchmarks/run.py` work from anywhere (repo root on sys.path)
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig4,fig9,fig10,fig11,fig12,fig13,"
                         "pareto,layer_snr,model_energy,kernel,serve,"
                         "serve_energy,serve_sharded,serve_prefix,roofline")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write a machine-readable JSON report")
    ap.add_argument("--workload-seed", type=int, default=None,
                    help="override the serve_slo overload-workload seed "
                         "(default: the committed baseline seed; every "
                         "serve_slo field is a deterministic draw-for-draw "
                         "function of this seed - no wall clock)")
    ap.add_argument("--mesh", default=None, metavar="RxC",
                    help="override the serve_sharded suite's device mesh "
                         "(default: the committed baseline mesh, 1x4; the "
                         "suite runs in this process and needs that many "
                         "devices - on the CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    args = ap.parse_args()
    if args.json:
        json_dir = os.path.dirname(os.path.abspath(args.json)) or "."
        if not os.path.isdir(json_dir):
            ap.error(f"--json: directory does not exist: {json_dir}")

    import jax

    from benchmarks import kernel_bench, layer_snr, model_energy, roofline, serve_bench
    from benchmarks.paper_figures import ALL as FIG_BENCHES
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()

    if args.workload_seed is not None:
        serve_bench.SLO_SEED = args.workload_seed
    if args.mesh is not None:
        serve_bench.SHARDED_MESH = args.mesh

    suites = {}
    suites.update(FIG_BENCHES)
    suites["layer_snr"] = layer_snr.run
    suites["model_energy"] = model_energy.run
    suites["kernel"] = kernel_bench.run
    suites["serve"] = serve_bench.run
    # deterministic serve-path energy accounting alone (fast; no wall-clock
    # repeats) - the committed BENCH_energy.json baseline is produced with
    #   PYTHONPATH=src python benchmarks/run.py --only serve_energy \
    #       --json BENCH_energy.json
    suites["serve_energy"] = lambda: serve_bench.energy_rows(
        serve_bench.energy_records())
    # multi-device scaling suite: needs the mesh's devices in this process
    suites["serve_sharded"] = lambda: serve_bench.sharded_rows(
        serve_bench.sharded_records())
    # prefix-sharing paged KV suite: warm (radix prefix cache) vs cold engine
    # on identical seeded shared-system-prompt traffic; deterministic
    # structural counters + billed-prefill-energy saving
    suites["serve_prefix"] = lambda: serve_bench.rows_from_records(
        serve_bench.prefix_records())
    suites["roofline"] = roofline.run
    # suites with structured records: run once, derive the CSV rows from them
    record_fns = {"kernel": (kernel_bench.bench_records,
                             kernel_bench.rows_from_records),
                  "serve": (serve_bench.bench_records,
                            serve_bench.rows_from_records),
                  "serve_energy": (serve_bench.energy_records,
                                   serve_bench.energy_rows),
                  "serve_sharded": (serve_bench.sharded_records,
                                    serve_bench.sharded_rows),
                  "serve_prefix": (serve_bench.prefix_records,
                                   serve_bench.rows_from_records)}

    only = set(args.only.split(",")) if args.only else None
    if only and "serve" in only:
        # the serve bench surface reports energy + multi-device scaling +
        # prefix sharing too: selecting the serve suite pulls in the
        # (deterministic) serve_energy rollup, the multi-device
        # serve_sharded comparison, and the serve_prefix warm-vs-cold
        # comparison, so the committed BENCH_serve.json carries all four
        only.add("serve_energy")
        only.add("serve_sharded")
        only.add("serve_prefix")
    # schema v2.6: serve-suite records name the execution substrate they
    # ran/billed (since v2.1), serve_drift records carry the full
    # detection/swap/recovery report surface (since v2.2), serve_slo
    # records carry the overload scoreboard - goodput, TTFT/ITL percentiles,
    # shed/preempt/degrade counters, engine_deaths, conservation - for the
    # committed seeded 2x-overload scenario (since v2.3), engine
    # "serve" records name their decode-attention path (kernel/gather/
    # dense) alongside the paged_attention kernel bench records (since
    # v2.4), serve_sharded records pin the tensor-parallel engine:
    # mesh_shape/devices identity, per-device KV bytes (structural-exact),
    # greedy-token match with the single-device engine, and a tok/s scaling
    # floor (since v2.5), and serve_prefix records pin the prefix-sharing
    # paged KV cache: exact hit/CoW/eviction counters, greedy-token identity
    # with a cold-cache run, and the billed-prefill-energy saving at the
    # committed QR design point (new in v2.6; all enforced by
    # check_regression.py)
    payload = {
        "schema": "repro-imc-bench/v2.6",
        "schema_version": 2.6,
        "backend": jax.default_backend(),
        # machine/XLA provenance: lets the regression gate (and humans) tell
        # a real perf change from a toolchain change, and the schema test
        # reject stale/truncated committed artifacts
        "meta": {
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "python": platform.python_version(),
            "platform": platform.platform(),
            "machine": platform.machine(),
            "xla_flags": os.environ.get("XLA_FLAGS", ""),
        },
        "suites": {},
    }
    print("name,value,derived")
    failed = []
    for name, fn in suites.items():
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        try:
            if args.json and name in record_fns:
                records_fn, rows_fn = record_fns[name]
                records = records_fn()
                rows = rows_fn(records)
            else:
                records = None
                rows = fn()
        except Exception as e:  # noqa: BLE001 - report it, run the rest
            print(f"{name}/ERROR,0,{type(e).__name__}: {e}")
            payload["suites"][name] = {"error": f"{type(e).__name__}: {e}"}
            failed.append(name)
            continue
        dt = time.perf_counter() - t0
        for rname, val, derived in rows:
            print(f'{rname},{val},"{derived}"')
        print(f'{name}/_suite_s,{dt:.2f},"suite wall time"')
        sys.stdout.flush()
        if records is None:
            records = [
                {"name": rname, "value": val, "derived": derived}
                for rname, val, derived in rows
            ]
        payload["suites"][name] = {"wall_s": round(dt, 2), "records": records}

    if args.json:
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.json}", file=sys.stderr)
    if failed:
        print(f"# suites failed: {','.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
