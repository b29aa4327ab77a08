import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS_EXTRA", "")
)
# ^ MUST precede any jax import: jax locks the device count on first init.
#   512 placeholder host devices let launch.mesh.make_mesh build the production
#   (2, 16, 16) multi-pod mesh on a single CPU for the dry-run.

"""Multi-pod dry-run (deliverable (e)).

For every (architecture x input shape x mesh) cell:
  * build the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  * build the jit'd step (train_step for train shapes, prefill/decode for
    serve shapes) with full sharding specs,
  * ``.lower()`` against ShapeDtypeStruct stand-ins (no allocation),
  * ``.compile()`` - success proves the distribution config is coherent,
  * record ``memory_analysis()`` (fits-in-HBM proof), ``cost_analysis()``
    (FLOPs/bytes for the roofline), and the per-device collective traffic
    parsed from the post-SPMD HLO text.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --out results/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-9b \
      --shape train_4k --mesh single
"""
import argparse
import json
import re
import time
import traceback
from typing import Dict, Optional

import jax

from repro import configs
from repro.configs.shapes import SHAPES, input_specs, shape_applicable
from repro.launch import hlo_analysis
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models import init_cache, init_params

# v5e-ish hardware constants for the roofline (EXPERIMENTS.md SSRoofline)
PEAK_FLOPS = 197e12  # bf16 FLOP/s per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link

_COLLECTIVE_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\][^ ]*))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per-device collective traffic from post-SPMD HLO: sums the *output*
    bytes of every collective op, per op kind (plus op counts)."""
    out: Dict[str, Dict[str, float]] = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        shape_str, kind = m.group(1), m.group(2)
        b = _shape_bytes(shape_str)
        rec = out.setdefault(kind, {"bytes": 0.0, "count": 0})
        rec["bytes"] += b
        rec["count"] += 1
    return out


def collective_wire_bytes(colls: Dict[str, Dict[str, float]]) -> float:
    """Approximate per-device wire traffic: ring all-reduce moves ~2x the
    shard bytes; all-gather/reduce-scatter ~1x the full output/input; a2a and
    permute ~1x."""
    factors = {
        "all-reduce": 2.0,
        "all-gather": 1.0,
        "reduce-scatter": 1.0,
        "all-to-all": 1.0,
        "collective-permute": 1.0,
    }
    return sum(factors[k] * v["bytes"] for k, v in colls.items())


def make_layout_mesh(layout: str):
    """'32x8' -> (data=32, model=8); '2x32x8' -> (pod, data, model).
    Total chips must be 256 (single-pod) or 512 (multi-pod)."""
    dims = tuple(int(x) for x in layout.split("x"))
    axes = ("pod", "data", "model") if len(dims) == 3 else ("data", "model")
    return make_mesh(dims, axes)


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    fsdp: bool = True,
    extra_tag: str = "",
    layout: str = "",
) -> Dict:
    """Lower + compile one (arch x shape x mesh) cell; returns the record."""
    cfg = configs.get(arch)
    shape = SHAPES[shape_name]
    rec: Dict = {
        "arch": arch,
        "shape": shape_name,
        "mesh": layout or ("2x16x16" if multi_pod else "16x16"),
        "tag": extra_tag,
        "params": cfg.param_count(),
        "params_active": cfg.param_count(active_only=True),
    }
    skip = shape_applicable(cfg, shape)
    if skip:
        rec["status"] = "skipped"
        rec["reason"] = skip
        return rec

    t0 = time.time()
    mesh = (make_layout_mesh(layout) if layout
            else make_production_mesh(multi_pod=multi_pod))
    sds = input_specs(cfg, shape)
    try:
        if shape.kind == "train":
            bundle = steps_lib.build_train_step(cfg, mesh, sds, fsdp=fsdp)
            state_sds = bundle.state_shapes
            lowered = bundle.step_fn.lower(state_sds, sds)
        elif shape.kind == "prefill":
            bundle = steps_lib.build_prefill_step(cfg, mesh, shape, sds, fsdp=fsdp)
            p_sds = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
            lowered = bundle.step_fn.lower(p_sds, sds)
        else:  # decode
            bundle = steps_lib.build_decode_step(cfg, mesh, shape, sds, fsdp=fsdp)
            p_sds = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
            c_sds = jax.eval_shape(
                lambda: init_cache(cfg, shape.global_batch, shape.seq_len)
            )
            lowered = bundle.step_fn.lower(p_sds, sds, c_sds)
        rec["lower_s"] = round(time.time() - t0, 1)

        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)

        mem = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "peak_bytes": int(
                getattr(mem, "peak_memory_in_bytes", 0)
                or getattr(mem, "temp_size_in_bytes", 0)
            ),
        }
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        # NOTE: XLA cost_analysis counts while (scan) bodies ONCE - kept for
        # reference only; the roofline uses the corrected HLO-walk numbers.
        rec["cost_xla_raw"] = {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        }
        hlo = compiled.as_text()
        ana = hlo_analysis.analyze(hlo)
        rec["cost"] = {
            "flops": ana["flops"],
            "dot_bytes": ana["dot_bytes"],
            "dot_bytes_bf16c": ana["dot_bytes_bf16c"],
        }
        rec["collectives"] = {
            k: {"bytes": ana["collective_bytes"][k],
                "count": ana["collective_counts"][k]}
            for k in ana["collective_bytes"]
        }
        rec["collective_wire_bytes"] = hlo_analysis.collective_wire_bytes(
            ana["collective_bytes"]
        ) * ana["collective_bf16c_scale"]
        rec["hlo_lines"] = hlo.count("\n")
        rec["status"] = "ok"

        n_chips = mesh.size
        tokens = shape.global_batch * (
            shape.seq_len if shape.kind in ("train", "prefill") else 1
        )
        mult = 6.0 if shape.kind == "train" else 2.0
        model_flops_per_chip = mult * rec["params_active"] * tokens / n_chips
        rec["model_flops_per_chip"] = model_flops_per_chip
        rec["useful_flops_ratio"] = (
            model_flops_per_chip / ana["flops"] if ana["flops"] else 0.0
        )
        # roofline terms (seconds, per device; HLO quantities are per-device
        # in post-SPMD modules)
        rec["roofline"] = {
            "t_compute_s": ana["flops"] / PEAK_FLOPS,
            "t_memory_s": ana["dot_bytes_bf16c"] / HBM_BW,
            "t_collective_s": rec["collective_wire_bytes"] / ICI_BW,
            "n_chips": n_chips,
        }
    except Exception as e:  # noqa: BLE001
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--layout", default="",
                    help="override mesh, e.g. 32x8 or 2x32x8 (SSPerf)")
    args = ap.parse_args()

    archs = list(configs.ARCH_NAMES) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                mesh_tag = "multi" if multi else "single"
                fname = os.path.join(
                    args.out,
                    f"{arch}__{shape}__{mesh_tag}"
                    + (f"__{args.layout}" if args.layout else "")
                    + (f"__{args.tag}" if args.tag else "")
                    + ".json",
                )
                if os.path.exists(fname):
                    with open(fname) as f:
                        old = json.load(f)
                    if old.get("status") in ("ok", "skipped"):
                        print(f"[cached] {fname}")
                        continue
                rec = run_cell(arch, shape, multi, fsdp=not args.no_fsdp,
                               extra_tag=args.tag, layout=args.layout)
                with open(fname, "w") as f:
                    json.dump(rec, f, indent=1)
                status = rec["status"]
                extra = (
                    f"temp={rec['memory']['temp_bytes']/2**30:.2f}GiB "
                    f"flops={rec['cost']['flops']:.3g} "
                    f"useful={rec['useful_flops_ratio']:.2f} "
                    f"coll={rec['collective_wire_bytes']/2**20:.1f}MiB "
                    f"compile={rec.get('compile_s', 0)}s"
                    if status == "ok"
                    else rec.get("reason", rec.get("error", ""))[:200]
                )
                print(f"[{status}] {arch} {shape} {mesh_tag}: {extra}", flush=True)


if __name__ == "__main__":
    main()
