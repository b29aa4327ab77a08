"""Pallas TPU kernels for IMC-simulated matrix multiplies.

Two kernels:

  imc_bitserial_matmul - bit-exact QS-Arch simulation (paper SSIV-B2): all
      (weight-bit x input-bit) plane binary matmuls fused into ONE stacked MXU
      call per (B, M, bank) tile, per-plane headroom clipping, additive analog
      noise generated in-kernel, per-plane ADC transfer, and signed
      power-of-two digital recombination, fused over SRAM banks.

  imc_analytic_matmul - the fast path: quantized-code matmul with the *folded*
      Gaussian analog-noise model (variance from repro.core.archs analytics)
      and an MPC-clipped output ADC; one MXU matmul per (K-tile) plus VPU
      epilogue with in-kernel output-noise generation.

TPU mapping notes (hardware adaptation, DESIGN.md SS3):
  * K is tiled at the SRAM bank height (rows=512, a multiple of the 128-wide
    MXU); M/B tiles default to 128.
  * weight bit planes are extracted ONCE per call on the host side of the
    pallas_call (weights are static across the batch and across B/M tiles) and
    handed to the kernel packed as a (K, Bw, M) operand with the two's
    complement sign-plane flip and the per-cell current gain (eq. 18) already
    folded in.  The kernel never runs floor/mod on weights, and the gain
    multiply happens once per weight plane instead of Bx times.
  * input bit planes are extracted in-register (VPU) once per grid step -
    Bx extractions, hoisted out of the weight-plane loop - and stacked into a
    (Bx*B_t, rows) operand so that ALL Bw*Bx plane dot products issue as a
    single (Bx*B_t, rows) @ (rows, Bw*M_t) MXU matmul.  This cuts MXU call
    count per tile from Bw*Bx to 1 and amortizes per-op overhead (the
    dominant cost in interpret mode, and scheduling overhead on TPU).
  * the per-plane nonlinearities (clip, noise add, ADC) are VPU elementwise
    ops applied one weight-plane slab (Bx*B_t, M_t) of the stacked
    accumulator at a time, so the live noise draws fit the chip's scoped
    VMEM; recombination walks the Bw*Bx sub-tiles in the oracle's
    i-outer/j-inner order within each bank (cross-bank f32 accumulation
    order differs: per-bank local sum, then in-place add - single-bank
    shapes match ref.py's rounding exactly, multi-bank shapes to allclose
    tolerance).
  * analog noise never touches HBM: the kernel draws it in-register, either
    from the TPU hardware PRNG (pltpu.prng_seed / prng_random_bits, seeded
    per (b, m, bank) grid step) or - in interpret/CPU mode - from the
    deterministic counter-based hash in repro.kernels.prng, whose draws are a
    pure function of global (bank, plane, b, m) indices and therefore
    bit-reproducible by the ref.py oracles.  The seed crosses the pallas_call
    boundary as a single (1, 1) int32 operand: O(1) bytes where the seed
    design streamed an O(n_banks*Bw*Bx*B*M) noise tensor (36x the output
    size per bank at the paper's 6x6-bit design point).
  * grid = (B_tiles, M_tiles, n_banks) with the bank dimension innermost:
    output tiles are revisited consecutively and accumulated in place (digital
    cross-bank reduction), and the packed weight-plane operand for a bank is
    reused across all B tiles before moving on.

Validated in interpret mode against repro.kernels.ref oracles (bit-exact on
the noiseless integer path; draw-for-draw on the fallback-PRNG noise path,
up to rare last-ulp ADC knife edges; statistical SNR-level equivalence on
the TPU hardware-PRNG path).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import prng
from repro.kernels.ref import (
    AnalyticSpec,
    BitSerialSpec,
    adc_transfer,
    mpc_adc,
    unpack_plane,
)

DEFAULT_TILE_B = 128
DEFAULT_TILE_M = 128


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _hw_prng_available(interpret: bool) -> bool:
    """Use the TPU hardware PRNG only when actually compiling for TPU."""
    return (not interpret) and jax.default_backend() == "tpu"


def _tpu_normal(shape):  # pragma: no cover - requires real TPU
    """Standard-normal draws from the TPU hardware PRNG (post prng_seed)."""
    b1 = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    b2 = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    return prng.normal_from_bits(b1, b2)


def _fold_seed(seed, *ids):
    """Mix grid ids into the base seed for per-tile hardware-PRNG seeding.

    Uses the full splitmix avalanche from repro.kernels.prng: a plain XOR of
    per-position constants is degenerate (constants that are power-of-two
    multiples of each other collide across grid steps, handing different
    tiles bit-identical hardware-PRNG streams).
    """
    # a same-width integer convert: Mosaic bitcasts vectors only
    return prng.hash_u32(seed, *ids).astype(jnp.int32)


# ---------------------------------------------------------------------------
# bit-serial kernel
# ---------------------------------------------------------------------------


def pack_weight_planes(
    w_codes: jax.Array,  # (K, M) f32 integer codes
    w_gain: Optional[jax.Array],  # (K, M) per-cell gain (1 + eps) or None
    bw: int,
) -> jax.Array:
    """Extract the Bw two's-complement weight bit planes once per call.

    Returns a (K, Bw, M) f32 operand with the sign-plane flip and the spatial
    per-cell current gain (paper eq. 18; correlated across planes because
    mismatch is fixed per physical cell) already folded in, so the kernel's
    weight-plane work is a pure block load.
    """
    w = w_codes.astype(jnp.float32)
    wp = jnp.stack(
        [unpack_plane(w, i, bw, signed=True) for i in range(bw)], axis=1
    )  # (K, Bw, M)
    if w_gain is not None:
        wp = wp * w_gain.astype(jnp.float32)[:, None, :]
    return wp


def _bitserial_kernel(
    seed_ref,  # (1, 1) i32 base noise seed (dummy when not has_noise)
    x_ref,  # (B_t, rows) f32 integer codes
    wp_ref,  # (rows, Bw, M_t) f32 packed weight planes (gain folded in)
    o_ref,  # (B_t, M_t) f32 accumulator (code units)
    *,
    spec: BitSerialSpec,
    has_noise: bool,
    hw_prng: bool,
    tile_b: int,
    tile_m: int,
):
    bank = pl.program_id(2)

    @pl.when(bank == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bx, bw = spec.bx, spec.bw
    ww, xw = spec.plane_weights()

    # input planes: Bx in-register extractions, hoisted out of the w loop
    x = x_ref[...]
    xs = jnp.concatenate(
        [unpack_plane(x, j, bx, signed=spec.x_signed) for j in range(bx)],
        axis=0,
    )  # (Bx*B_t, rows)

    wp = wp_ref[...].reshape(spec.rows, bw * tile_m)  # (rows, Bw*M_t)

    # ONE MXU call for all Bw*Bx plane dot products (counts)
    dp = jnp.dot(xs, wp, preferred_element_type=jnp.float32)

    # VPU epilogue, one weight-plane slab (Bx*B_t, M_t) at a time: headroom
    # clip -> noise -> ADC -> signed power-of-two recombination, walking
    # sub-tiles in the oracle's i-outer/j-inner order (within a bank; the
    # cross-bank accumulation order differs - per-bank local sum, then
    # in-place add to o_ref).  Slab-wise noise keeps the live VMEM set to one
    # plane's draws instead of all Bw*Bx of them.
    dp = jnp.minimum(dp, spec.k_h)
    if has_noise and hw_prng:
        pltpu.prng_seed(
            _fold_seed(seed_ref[0, 0], pl.program_id(0), pl.program_id(1),
                       bank)
        )
    acc = jnp.zeros((tile_b, tile_m), jnp.float32)
    for i in range(bw):
        slab = dp[:, i * tile_m:(i + 1) * tile_m]
        if has_noise:
            if hw_prng:
                z = _tpu_normal(slab.shape)
            else:
                # deterministic counter PRNG over GLOBAL (bank, plane, b, m)
                # indices: tile-layout independent, bit-exact vs ref.py
                row = jax.lax.broadcasted_iota(jnp.int32, slab.shape, 0)
                col = jax.lax.broadcasted_iota(jnp.int32, slab.shape, 1)
                b_g = pl.program_id(0) * tile_b + row % tile_b
                m_g = pl.program_id(1) * tile_m + col
                plane = i * bx + row // tile_b  # p = i*Bx + j
                z = prng.counter_normal(
                    seed_ref[0, 0], prng.TAG_BITSERIAL, bank, plane, b_g, m_g
                )
            slab = jnp.maximum(slab + spec.sigma_noise * z, 0.0)
        if spec.apply_adc:
            slab = adc_transfer(slab, spec.b_adc, spec.v_c)
        for j in range(bx):
            acc = acc + (ww[i] * xw[j]) * slab[j * tile_b:(j + 1) * tile_b]
    o_ref[...] += acc


def imc_bitserial_matmul(
    x_codes: jax.Array,  # (B, K) f32 integer codes
    w_codes: jax.Array,  # (K, M) f32 integer codes
    w_gain: Optional[jax.Array],  # (K, M) per-cell gain (1+eps) or None
    spec: BitSerialSpec,
    seed: Optional[jax.Array] = None,  # scalar int32 noise seed, or None
    tile_b: int = DEFAULT_TILE_B,
    tile_m: int = DEFAULT_TILE_M,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused bit-serial IMC matmul; returns (B, M) in code units.

    Per-plane temporal analog noise (std ``spec.sigma_noise`` counts) is
    generated *inside* the kernel when ``seed`` is given - no noise tensor is
    ever materialized.  ``seed=None`` (or ``sigma_noise == 0``) disables it.

    B, M, K are padded to tile multiples internally; K pads with zero codes
    (inactive rows - physically, unused bank rows).
    """
    if interpret is None:
        interpret = _interpret_default()
    b_sz, k = x_codes.shape
    _, m = w_codes.shape
    n_banks = -(-k // spec.rows)
    bp = -(-b_sz // tile_b) * tile_b
    mp = -(-m // tile_m) * tile_m
    kp = n_banks * spec.rows
    x_p = jnp.pad(x_codes.astype(jnp.float32), ((0, bp - b_sz), (0, kp - k)))
    w_p = jnp.pad(w_codes.astype(jnp.float32), ((0, kp - k), (0, mp - m)))
    g_p = None
    if w_gain is not None:
        g_p = jnp.pad(
            w_gain.astype(jnp.float32),
            ((0, kp - k), (0, mp - m)),
            constant_values=1.0,
        )
    # hoisted plane extraction: once per call, not once per grid step
    wp = pack_weight_planes(w_p, g_p, spec.bw)  # (Kp, Bw, Mp)

    has_noise = seed is not None and spec.sigma_noise > 0.0
    if has_noise:
        seed_arr = jnp.asarray(seed, jnp.int32).reshape(1, 1)
    else:
        seed_arr = jnp.zeros((1, 1), jnp.int32)

    grid = (bp // tile_b, mp // tile_m, n_banks)
    out = pl.pallas_call(
        functools.partial(
            _bitserial_kernel,
            spec=spec,
            has_noise=has_noise,
            hw_prng=_hw_prng_available(interpret),
            tile_b=tile_b,
            tile_m=tile_m,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, mm, kk: (0, 0)),
            pl.BlockSpec((tile_b, spec.rows), lambda b, mm, kk: (b, kk)),
            pl.BlockSpec(
                (spec.rows, spec.bw, tile_m), lambda b, mm, kk: (kk, 0, mm)
            ),
        ],
        out_specs=pl.BlockSpec((tile_b, tile_m), lambda b, mm, kk: (b, mm)),
        out_shape=jax.ShapeDtypeStruct((bp, mp), jnp.float32),
        interpret=interpret,
    )(seed_arr, x_p, wp)
    return out[:b_sz, :m]


# ---------------------------------------------------------------------------
# analytic-mode kernel
# ---------------------------------------------------------------------------


def _analytic_kernel(
    seed_ref,  # (1, 1) i32 noise seed (dummy when not has_noise)
    x_ref,  # (B_t, K_t)
    w_ref,  # (K_t, M_t)
    o_ref,  # (B_t, M_t)
    *,
    spec: AnalyticSpec,
    n_k: int,
    has_noise: bool,
    hw_prng: bool,
    tile_b: int,
    tile_m: int,
):
    kk = pl.program_id(2)
    # grid ids are read outside the pl.when closure: interpret mode lowers
    # program_id only at the top level of the kernel trace
    pid_b, pid_m = pl.program_id(0), pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(kk == n_k - 1)
    def _epilogue():
        y = o_ref[...]
        if has_noise:
            if hw_prng:  # pragma: no cover - requires real TPU
                pltpu.prng_seed(_fold_seed(seed_ref[0, 0], pid_b, pid_m))
                z = _tpu_normal(y.shape)
            else:
                row = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
                col = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
                b_g = pid_b * tile_b + row
                m_g = pid_m * tile_m + col
                z = prng.counter_normal(
                    seed_ref[0, 0], prng.TAG_ANALYTIC, b_g, m_g
                )
            y = y + spec.sigma_out * z
        if spec.apply_adc:
            y = mpc_adc(y, spec.b_adc, spec.y_clip)
        o_ref[...] = y


def imc_analytic_matmul(
    x_codes: jax.Array,  # (B, K)
    w_codes: jax.Array,  # (K, M)
    spec: AnalyticSpec,
    seed: Optional[jax.Array] = None,  # scalar int32 noise seed, or None
    tile_b: int = DEFAULT_TILE_B,
    tile_m: int = DEFAULT_TILE_M,
    tile_k: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Analytic-mode IMC matmul with in-kernel folded output noise.

    ``seed=None`` (or ``spec.sigma_out == 0``) disables the noise; the (B, M)
    normal draw of the seed design no longer exists as an operand.
    """
    if interpret is None:
        interpret = _interpret_default()
    b_sz, k = x_codes.shape
    _, m = w_codes.shape
    bp = -(-b_sz // tile_b) * tile_b
    mp = -(-m // tile_m) * tile_m
    kp = -(-k // tile_k) * tile_k
    x_p = jnp.pad(x_codes.astype(jnp.float32), ((0, bp - b_sz), (0, kp - k)))
    w_p = jnp.pad(w_codes.astype(jnp.float32), ((0, kp - k), (0, mp - m)))
    has_noise = seed is not None and spec.sigma_out > 0.0
    if has_noise:
        seed_arr = jnp.asarray(seed, jnp.int32).reshape(1, 1)
    else:
        seed_arr = jnp.zeros((1, 1), jnp.int32)
    n_k = kp // tile_k
    out = pl.pallas_call(
        functools.partial(
            _analytic_kernel,
            spec=spec,
            n_k=n_k,
            has_noise=has_noise,
            hw_prng=_hw_prng_available(interpret),
            tile_b=tile_b,
            tile_m=tile_m,
        ),
        grid=(bp // tile_b, mp // tile_m, n_k),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, mm, kk: (0, 0)),
            pl.BlockSpec((tile_b, tile_k), lambda b, mm, kk: (b, kk)),
            pl.BlockSpec((tile_k, tile_m), lambda b, mm, kk: (kk, mm)),
        ],
        out_specs=pl.BlockSpec((tile_b, tile_m), lambda b, mm, kk: (b, mm)),
        out_shape=jax.ShapeDtypeStruct((bp, mp), jnp.float32),
        interpret=interpret,
    )(seed_arr, x_p, w_p)
    return out[:b_sz, :m]
