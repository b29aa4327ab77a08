"""Kernel micro-bench: per-shape wall time and structural counters for the
IMC matmul kernels (Pallas runs compiled on a TPU and in interpret mode
elsewhere, where wall time is indicative only; the structural counters are
the quantities that transfer to TPU - MXU matmul count, HBM bytes per
operand class, arithmetic intensity).

Benches both the CURRENT kernel (packed weight planes, one stacked MXU call
per tile, in-kernel noise) and a frozen copy of the SEED kernel (per-plane
floor/mod extraction in every grid step, per-plane noise streamed from an
HBM-materialized ``(n_banks, Bw*Bx, B, M)`` tensor), so every run reports the
before/after trajectory this PR's rewrite established - in particular the
noise-operand HBM bytes, the structural quantity the rewrite eliminates.

Also benches the paged-attention decode step (``bench: paged_attention``):
the gather path materializes every resident slot's KV out of the block pool
(``pool[bt]``) before attending - O(slots * blocks) HBM traffic per decoded
token - while the fused kernel streams one physical block at a time through
the online-softmax accumulator, so the materialized copy is a single
block-sized working set, O(1) in sequence length.  The structural counter
``gathered_kv_bytes_per_step`` records exactly that quantity; the summary's
``gathered_kv_reduction`` is the deterministic before/after ratio the
regression gate pins.

``bench_records()`` returns machine-readable dicts (consumed by
``benchmarks/run.py --json``); ``run()`` formats them as the usual CSV rows.
"""
from __future__ import annotations

import functools
import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import imc_mvm, ref
from repro.kernels.paged_attention import paged_attention_decode
from repro.kernels.ref import (
    BitSerialSpec,
    paged_attention_ref,
    quantize_codes,
)

Row = Tuple[str, float, str]

SHAPES = [
    # (B, K, M, bx, bw)
    (64, 512, 128, 6, 6),
    (128, 1024, 256, 7, 7),
    (32, 2048, 128, 4, 4),
]

PAGED_SHAPES = [
    # (slots, blocks per slot, block_size, kv heads, q groups, head_dim)
    (4, 8, 8, 2, 2, 64),
    (8, 16, 8, 4, 2, 64),
]


# ---------------------------------------------------------------------------
# frozen seed-kernel baseline (pre-rewrite design, kept ONLY as the perf
# reference: per-grid-step plane extraction + HBM noise operand)
# ---------------------------------------------------------------------------


def _seed_bitserial_kernel(x_ref, w_ref, n_ref, o_ref, *, spec, has_noise):
    bank = pl.program_id(2)

    @pl.when(bank == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    ww, xw = spec.plane_weights()
    x = x_ref[...]
    w = w_ref[...]
    w_u = w + 2.0 ** (spec.bw - 1)
    x_u = x + 2.0 ** (spec.bx - 1) if spec.x_signed else x

    acc = jnp.zeros_like(o_ref)
    for i in range(spec.bw):
        wplane = jnp.mod(jnp.floor(w_u / (2.0**i)), 2.0)
        if i == spec.bw - 1:
            wplane = 1.0 - wplane
        for j in range(spec.bx):
            xplane = jnp.mod(jnp.floor(x_u / (2.0**j)), 2.0)
            if spec.x_signed and j == spec.bx - 1:
                xplane = 1.0 - xplane
            dp = jnp.dot(xplane, wplane, preferred_element_type=jnp.float32)
            dp = jnp.minimum(dp, spec.k_h)
            if has_noise:
                dp = dp + n_ref[0, i * spec.bx + j]
                dp = jnp.maximum(dp, 0.0)
            if spec.apply_adc:
                delta = spec.v_c / (2.0**spec.b_adc)
                code = jnp.clip(
                    jnp.round(dp / delta - 0.5), 0.0, 2.0**spec.b_adc - 1
                )
                dp = (code + 0.5) * delta
            acc = acc + (ww[i] * xw[j]) * dp
    o_ref[...] += acc


def _seed_bitserial_matmul(x_codes, w_codes, noise, spec,
                           tile_b=128, tile_m=128):
    b_sz, k = x_codes.shape
    _, m = w_codes.shape
    n_banks = -(-k // spec.rows)
    bp = -(-b_sz // tile_b) * tile_b
    mp = -(-m // tile_m) * tile_m
    kp = n_banks * spec.rows
    x_p = jnp.pad(x_codes.astype(jnp.float32), ((0, bp - b_sz), (0, kp - k)))
    w_p = jnp.pad(w_codes.astype(jnp.float32), ((0, kp - k), (0, mp - m)))
    has_noise = noise is not None
    operands = [x_p, w_p]
    in_specs = [
        pl.BlockSpec((tile_b, spec.rows), lambda b, mm, kk: (b, kk)),
        pl.BlockSpec((spec.rows, tile_m), lambda b, mm, kk: (kk, mm)),
    ]
    if has_noise:
        n_p = jnp.pad(
            noise.astype(jnp.float32),
            ((0, 0), (0, 0), (0, bp - b_sz), (0, mp - m)),
        )
        operands.append(n_p)
        in_specs.append(
            pl.BlockSpec(
                (1, spec.bw * spec.bx, tile_b, tile_m),
                lambda b, mm, kk: (kk, 0, b, mm),
            )
        )
    else:
        operands.append(jnp.zeros((1, 1, 1, 1), jnp.float32))
        in_specs.append(
            pl.BlockSpec((1, 1, 1, 1), lambda b, mm, kk: (0, 0, 0, 0))
        )
    out = pl.pallas_call(
        functools.partial(
            _seed_bitserial_kernel, spec=spec, has_noise=has_noise
        ),
        grid=(bp // tile_b, mp // tile_m, n_banks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile_b, tile_m), lambda b, mm, kk: (b, mm)),
        out_shape=jax.ShapeDtypeStruct((bp, mp), jnp.float32),
        interpret=jax.default_backend() != "tpu",
    )(*operands)
    return out[:b_sz, :m]


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------


def _bench(fn, iters=3):
    fn()  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / iters * 1e6


def _structure(b, k, m, bx, bw, rows, design: str, noisy: bool):
    """Structural counters: what each configuration moves through HBM and
    issues on the MXU, per call (f32 operands; B/M padded to 128 tiles)."""
    n_banks = -(-k // rows)
    bt, mt = -(-b // 128), -(-m // 128)
    bp, mp = bt * 128, mt * 128
    kp = n_banks * rows
    counters = {
        "n_banks": n_banks,
        "x_bytes": bp * kp * 4,
        "plane_flops_mf": round(2 * b * k * m * bx * bw / 1e6),
    }
    if design == "seed":
        counters["mxu_calls"] = bx * bw * n_banks * bt * mt
        counters["w_bytes"] = kp * mp * 4
        counters["noise_bytes"] = n_banks * bw * bx * bp * mp * 4 if noisy else 0
    else:
        counters["mxu_calls"] = n_banks * bt * mt
        counters["w_bytes"] = kp * bw * mp * 4  # packed (K, Bw, M) planes
        counters["noise_bytes"] = 4 if noisy else 0  # scalar int32 seed
    return counters


def _paged_structure(slots, blocks, bs, hkv, hd, design: str):
    """KV bytes materialized OUTSIDE the block pool per decode step (f32
    K + V).  The gather path copies every resident slot's whole table
    (``pool[bt]``); the fused kernel's only materialized KV is the single
    block streamed through VMEM at each grid step - O(1) in both slot count
    and sequence length."""
    kv_elem = 4 * 2  # f32, K and V
    if design == "gather":
        gathered = slots * blocks * bs * hkv * hd * kv_elem
    else:
        gathered = bs * hkv * hd * kv_elem
    return {"gathered_kv_bytes_per_step": gathered}


def paged_attention_records(iters: int = 3) -> List[dict]:
    """Decode-step records: gather path vs fused streaming kernel (the
    pure-JAX block-walk the serve engine runs on CPU; on TPU the same walk
    is the Pallas grid)."""
    records: List[dict] = []
    key = jax.random.PRNGKey(1)
    for (slots, blocks, bs, hkv, g, hd) in PAGED_SHAPES:
        ks = jax.random.split(jax.random.fold_in(key, slots * blocks), 5)
        n_pool = slots * blocks + 1  # + reserved garbage block 0
        q = jax.random.normal(ks[0], (slots, hkv, g, hd))
        kn = jax.random.normal(ks[1], (slots, hkv, hd))
        vn = jax.random.normal(ks[2], (slots, hkv, hd))
        pk = jax.random.normal(ks[3], (n_pool, bs, hkv, hd))
        pv = jax.random.normal(ks[4], (n_pool, bs, hkv, hd))
        bt = 1 + jnp.arange(slots * blocks, dtype=jnp.int32).reshape(
            slots, blocks)
        # mid-block tail positions, staggered so the causal mask varies
        pos_b = (blocks // 2) * bs + 3 + jnp.arange(slots, dtype=jnp.int32)
        scale = hd ** -0.5

        shape_meta = {"slots": slots, "blocks": blocks, "block_size": bs,
                      "heads": hkv * g, "kv_heads": hkv, "head_dim": hd}
        configs = {
            "gather": (
                jax.jit(lambda q_, kn_, vn_: paged_attention_ref(
                    q_, kn_, vn_, pk, pv, bt, pos_b, scale=scale)),
                "gather",
            ),
            "kernel": (
                jax.jit(lambda q_, kn_, vn_: paged_attention_decode(
                    q_, kn_, vn_, pk, pv, bt, pos_b, scale=scale)),
                "kernel",
            ),
        }
        for cname, (fn, design) in configs.items():
            # block inside the callable: these ops are microsecond-scale, so
            # an async (unblocked) warmup would bleed compile time into the
            # first timed iteration and swamp the measurement
            call = (lambda fn=fn: jax.block_until_ready(fn(q, kn, vn)))
            rec = {"bench": "paged_attention", "config": cname, **shape_meta,
                   "wall_us": round(_bench(call, iters=iters), 1),
                   **_paged_structure(slots, blocks, bs, hkv, hd, design)}
            records.append(rec)
        by_cfg = {r["config"]: r for r in records
                  if r.get("bench") == "paged_attention"
                  and (r["slots"], r["blocks"]) == (slots, blocks)}
        records.append({
            "bench": "paged_attention_summary", **shape_meta,
            "speedup_vs_gather": round(
                by_cfg["gather"]["wall_us"] / by_cfg["kernel"]["wall_us"], 2),
            "gathered_kv_bytes_before":
                by_cfg["gather"]["gathered_kv_bytes_per_step"],
            "gathered_kv_bytes_after":
                by_cfg["kernel"]["gathered_kv_bytes_per_step"],
            "gathered_kv_reduction": round(
                by_cfg["gather"]["gathered_kv_bytes_per_step"]
                / by_cfg["kernel"]["gathered_kv_bytes_per_step"], 1),
        })
    return records


def bench_records(iters: int = 3) -> List[dict]:
    """Machine-readable per-(shape, config) records for run.py --json."""
    records: List[dict] = []
    key = jax.random.PRNGKey(0)
    for (b, k, m, bx, bw) in SHAPES:
        k1, k2, k3 = jax.random.split(jax.random.fold_in(key, k + m), 3)
        x = jax.random.normal(k1, (b, k))
        w = jax.random.normal(k2, (k, m))
        xc, _ = quantize_codes(x, bx, True, jnp.max(jnp.abs(x)))
        wc, _ = quantize_codes(w, bw, True, jnp.max(jnp.abs(w)))
        rows_bank = min(512, k)
        n_banks = -(-k // rows_bank)
        sigma = 0.3
        spec = BitSerialSpec(bx=bx, bw=bw, b_adc=8, rows=rows_bank, k_h=60.0,
                             v_c=55.0, x_signed=True)
        spec_noisy = BitSerialSpec(bx=bx, bw=bw, b_adc=8, rows=rows_bank,
                                   k_h=60.0, v_c=55.0, x_signed=True,
                                   sigma_noise=sigma)
        # pre-drawn HBM noise tensor: the operand class the rewrite removed
        noise = sigma * jax.random.normal(
            k3, (n_banks, bw * bx, b, m), dtype=jnp.float32
        )

        shape_meta = {"B": b, "K": k, "M": m, "bx": bx, "bw": bw,
                      "rows": rows_bank}
        configs = {
            "seed_baseline": (
                lambda: _seed_bitserial_matmul(xc, wc, None, spec),
                "seed", False,
            ),
            "seed_baseline_noise": (
                lambda: _seed_bitserial_matmul(xc, wc, noise, spec_noisy),
                "seed", True,
            ),
            "kernel": (
                lambda: imc_mvm.imc_bitserial_matmul(xc, wc, None, spec),
                "new", False,
            ),
            "kernel_noise": (
                lambda: imc_mvm.imc_bitserial_matmul(
                    xc, wc, None, spec_noisy, seed=17
                ),
                "new", True,
            ),
            "oracle": (
                lambda: ref.imc_bitserial_ref(xc, wc, None, spec),
                None, False,
            ),
        }
        for cname, (fn, design, noisy) in configs.items():
            rec = {"bench": "bitserial", "config": cname, **shape_meta,
                   "wall_us": round(_bench(fn, iters=iters), 1)}
            if design is not None:
                rec.update(_structure(b, k, m, bx, bw, rows_bank, design,
                                      noisy))
            records.append(rec)

        by_cfg = {r["config"]: r for r in records
                  if r.get("bench") == "bitserial"
                  and (r["B"], r["K"], r["M"]) == (b, k, m)}
        records.append({
            "bench": "bitserial_summary", **shape_meta,
            "speedup_vs_seed": round(
                by_cfg["seed_baseline"]["wall_us"] / by_cfg["kernel"]["wall_us"],
                2),
            "speedup_vs_seed_noise": round(
                by_cfg["seed_baseline_noise"]["wall_us"]
                / by_cfg["kernel_noise"]["wall_us"], 2),
            "noise_bytes_before": by_cfg["seed_baseline_noise"]["noise_bytes"],
            "noise_bytes_after": by_cfg["kernel_noise"]["noise_bytes"],
            "noise_bytes_reduction": round(
                by_cfg["seed_baseline_noise"]["noise_bytes"]
                / max(by_cfg["kernel_noise"]["noise_bytes"], 1), 1),
            "mxu_calls_before": by_cfg["seed_baseline"]["mxu_calls"],
            "mxu_calls_after": by_cfg["kernel"]["mxu_calls"],
        })
    records.extend(paged_attention_records(iters=iters))
    return records


def rows_from_records(records: List[dict]) -> List[Row]:
    rows: List[Row] = []
    for r in records:
        if r["bench"].startswith("paged_attention"):
            tag = (f"S{r['slots']}_N{r['blocks']}x{r['block_size']}"
                   f"_H{r['heads']}_D{r['head_dim']}")
            if r["bench"] == "paged_attention_summary":
                rows.append((
                    f"kernel/paged_summary_{tag}",
                    r["speedup_vs_gather"],
                    f"gathered_kv_B {r['gathered_kv_bytes_before']}->"
                    f"{r['gathered_kv_bytes_after']} "
                    f"({r['gathered_kv_reduction']}x)",
                ))
            else:
                rows.append((
                    f"kernel/paged_{r['config']}_{tag}", r["wall_us"],
                    f"gathered_kv_B={r['gathered_kv_bytes_per_step']}",
                ))
            continue
        tag = f"B{r['B']}_K{r['K']}_M{r['M']}_b{r['bx']}x{r['bw']}"
        if r["bench"] == "bitserial_summary":
            rows.append((
                f"kernel/summary_{tag}",
                r["speedup_vs_seed"],
                f"speedup_noise={r['speedup_vs_seed_noise']} "
                f"noise_bytes {r['noise_bytes_before']}->"
                f"{r['noise_bytes_after']} "
                f"mxu {r['mxu_calls_before']}->{r['mxu_calls_after']}",
            ))
        else:
            derived = (
                f"MXU_tiles={r['mxu_calls']} noise_B={r['noise_bytes']} "
                f"w_B={r['w_bytes']} plane_flops={r['plane_flops_mf']}MF"
                if "mxu_calls" in r else "pure-jnp oracle"
            )
            rows.append((
                f"kernel/{r['config']}_{tag}", r["wall_us"], derived
            ))
    return rows


def run() -> List[Row]:
    return rows_from_records(bench_records())
