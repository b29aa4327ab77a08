"""IMCLinear: the paper's technique as an executable layer.

Every matmul in the model zoo routes through :func:`linear`, which executes in
one of four modes (ExecMode):

  digital        plain matmul (the baseline; used for training + dry-run).
  fakequant      B_x/B_w input quantization only (digital FX arithmetic, STE
                 gradients) - isolates SQNR_qiy (paper eq. 8).
  imc_analytic   folded-noise IMC model: fakequant matmul + Gaussian analog
                 noise at the analytic SNR_a (repro.core.archs) + MPC-clipped
                 B_ADC output quantization (paper eqs. 10-15). Differentiable
                 (STE) => usable for noise-aware training; cheap => usable at
                 dry-run scale; pure-jnp => shards under pjit.
  imc_bitserial  bit-exact QS-Arch simulation via the Pallas kernel
                 (repro.kernels) - for silicon-fidelity studies at layer scale.
                 Per-plane analog noise is generated inside the kernel from a
                 scalar seed derived from the layer key: no noise tensor is
                 materialized at any point in this path.

The mode and design knobs live in IMCConfig, threaded through model configs.
Per-layer RNG is derived with jax.random.fold_in over a static layer id.

First-class substrates (repro.core.substrate) wrap an IMCConfig with a
calibration policy (dynamic per-batch stats vs frozen calibrated ranges) and
per-site overrides; :func:`linear` accepts either and resolves the effective
IMCConfig per compute site.  A bare IMCConfig is exactly the dynamic-policy
substrate - bit-for-bit the historical behaviour.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.archs import QSArch
from repro.core.quant import QuantSpec


@dataclasses.dataclass(frozen=True)
class IMCConfig:
    """Static IMC execution configuration (hashable; safe as a jit static arg)."""

    mode: str = "digital"  # digital|fakequant|imc_analytic|imc_bitserial
    bx: int = 6
    bw: int = 6
    b_adc: Optional[int] = None  # None -> MPC assignment from SNR_A
    rows: int = 512  # SRAM bank height (DP dim per bank)
    x_signed: bool = True
    # analog design point (QS-Arch knobs; used to derive SNR_a when
    # snr_a_db is None)
    v_wl: float = 0.7
    snr_a_db: Optional[float] = None
    y_clip_sigmas: float = 4.0
    use_kernel: bool = False  # Pallas path for bitserial (layer-scale studies)
    # assumed operand PARs (max/sigma) for static ADC assignment on the
    # bit-serial path; 4.0 ~ Gaussian tensors clipped at 4 sigma
    par_x: float = 4.0
    par_w: float = 4.0
    adc_margin_db: float = 9.0  # SQNR_qy >= SNR_A + margin (paper SSIII-B)

    def bank_rows(self, n: Optional[int] = None) -> int:
        """Auto-banking (paper SSVI bullet 4): the DP dimension per bank is
        limited to N_max of the design point - choose the largest power-of-two
        bank height within 1 dB of the peak analytic SNR_A."""
        return _bank_rows_cached(
            min(n or self.rows, self.rows), self.bx, self.bw, self.v_wl
        )

    def resolved_snr_a_db(self, n: Optional[int] = None) -> float:
        if self.snr_a_db is not None:
            return self.snr_a_db
        arch = self.qs_arch(n)
        return float(arch.snr_a_db())

    def qs_arch(self, n: Optional[int] = None) -> QSArch:
        return QSArch(n=self.bank_rows(n), bx=self.bx, bw=self.bw,
                      v_wl=self.v_wl)

    def resolved_b_adc(self, n: Optional[int] = None) -> int:
        """MPC assignment (paper eq. 15) - used for *final-output* ADCs
        (imc_analytic mode, CM/QR-style architectures)."""
        if self.b_adc is not None:
            return self.b_adc
        from repro.core.precision import by_mpc_lower_bound

        return by_mpc_lower_bound(self.resolved_snr_a_db(n))

    def resolved_b_adc_bitserial(self, n: int) -> int:
        """Per-plane ADC precision for the bit-serial QS-Arch path.

        The paper's eq. (15) targets a single final-output ADC.  In QS-Arch the
        ADC digitizes each (i, j) binary plane DP, and plane errors recombine
        with 4^(i+j) weights, so the requirement must be placed on the
        *recombined* ADC noise:

          n_banks * S_x * S_w * Delta^2/12 <= sigma_yo,code^2 * 10^-(SNR_A+m)/10

        with S_b = (4^B - 1)/3 the sum of squared plane weights and
        sigma_yo,code estimated from the assumed operand PARs.  For the paper's
        low-PAR uniform operands this reduces to ~eq. (15); for high-PAR
        Gaussian LM tensors it assigns 2-4 more bits (DESIGN.md SS7).
        """
        if self.b_adc is not None:
            return self.b_adc
        import math

        arch = self.qs_arch(n)
        nb = arch.n
        n_banks = max(1, -(-n // nb))
        sx = 2.0 ** (self.bx - 1) / self.par_x if self.x_signed else (
            2.0**self.bx * 0.5 / self.par_x
        )
        sw = 2.0 ** (self.bw - 1) / self.par_w
        sigma_yo_sq = n * sx**2 * sw**2
        budget = sigma_yo_sq * 10.0 ** (
            -(arch.snr_A_db() + self.adc_margin_db) / 10.0
        )
        s_x = (4.0**self.bx - 1) / 3.0
        s_w = (4.0**self.bw - 1) / 3.0
        delta = math.sqrt(12.0 * budget / (n_banks * s_x * s_w))
        v_c = arch.v_c_counts()
        b = int(math.ceil(math.log2(max(v_c / max(delta, 1e-6), 2.0))))
        return max(2, min(b, 14))


DIGITAL = IMCConfig(mode="digital")


import functools as _functools


@_functools.lru_cache(maxsize=1024)
def _bank_rows_cached(size: int, bx: int, bw: int, v_wl: float) -> int:
    cands = []
    c = size
    while c >= 32:
        cands.append(c)
        c //= 2
    if not cands:
        return max(size, 1)
    snrs = [QSArch(n=nb, bx=bx, bw=bw, v_wl=v_wl).snr_A_db() for nb in cands]
    peak = max(snrs)
    for nb, s in zip(cands, snrs):  # cands sorted large -> small
        if s >= peak - 1.0:
            return nb
    return cands[-1]


# ---------------------------------------------------------------------------
# quantizer helpers (dynamic per-tensor scales, STE gradients)
# ---------------------------------------------------------------------------


def _fq_ste(v, bits: int, signed: bool, max_val):
    """fake-quant with straight-through gradient."""
    if signed:
        delta = max_val * 2.0 ** (1 - bits)
        lo, hi = -(2.0 ** (bits - 1)), 2.0 ** (bits - 1) - 1
    else:
        delta = max_val * 2.0 ** (-bits)
        lo, hi = 0.0, 2.0**bits - 1
    q = jnp.clip(jnp.round(v / delta), lo, hi) * delta
    return v + jax.lax.stop_gradient(q - v)


def _dynamic_max(v):
    return jax.lax.stop_gradient(jnp.max(jnp.abs(v)) + 1e-9)


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------


def linear(
    w: jax.Array,  # (d_in, d_out)
    x: jax.Array,  # (..., d_in)
    cfg=DIGITAL,  # IMCConfig | core.substrate.Substrate
    rng: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    dot_general=None,
    site: Optional[str] = None,
) -> jax.Array:
    """y = x @ w (+ bias) on the configured execution substrate.

    ``cfg`` is the substrate the matmul executes on: either a first-class
    :class:`repro.core.substrate.Substrate` (``DigitalSubstrate`` /
    ``AnalyticIMC`` / ``BitSerialIMC``) or, for backward compatibility, a
    bare :class:`IMCConfig` - which behaves exactly like the equivalent
    dynamic-policy substrate (bit-for-bit: same ops, same per-batch
    quantizer statistics).

    ``site`` names the compute site this call implements, using the site
    vocabulary of the ONE shared shapes walk
    (``core.mapping.per_token_matmul_shapes``: ``"attn.wq"``, ``"mlp.wi"``,
    ``"lm_head"``, ...).  It selects any per-site substrate override (e.g. a
    higher B_ADC on the output head) and, under a ``frozen`` calibration
    policy, the frozen quantizer ranges - which replace the per-batch
    ``max|x|`` / ``std(y)`` statistics and make the call
    batch-composition-invariant.  ``site=None`` uses the substrate's base
    config and the calibration's ``"*"`` fallback entry.

    The call runs under a named scope of its ``site``; on every substrate
    but ``digital``, the substrate's emulation around the matmul (operand
    statistics and quantization, the noise and ADC epilogue) runs in an
    inner ``imc`` scope, while the matmul itself stays in the site's scope.
    A profile's op names carry both.
    """
    with jax.named_scope(site) if site else contextlib.nullcontext():
        return _linear(w, x, cfg, rng, bias, dot_general, site)


def _linear(w, x, cfg, rng, bias, dot_general, site):
    from repro.core import substrate as substrate_lib

    sub = substrate_lib.as_substrate(cfg)
    cfg = sub.site_config(site)
    if cfg.mode == "digital":
        if dot_general is not None:
            y = dot_general(x, w)
        else:
            y = jnp.einsum("...k,km->...m", x, w)
        return y if bias is None else y + bias

    rec = substrate_lib.active_recorder()
    if rec is not None:
        # calibration pass (eager): record this site's operand ranges, then
        # execute the cheap noiseless fakequant proxy - same ranges as the
        # real substrate without paying for noise draws / bit-serial planes
        with jax.named_scope("imc"):
            x_max = _dynamic_max(x)
            w_max = _dynamic_max(w)
            xq = _fq_ste(x, cfg.bx, cfg.x_signed, x_max)
            wq = _fq_ste(w, cfg.bw, True, w_max)
        y = jnp.einsum("...k,km->...m", xq, wq)
        rec.observe(site or substrate_lib.DEFAULT_SITE, x, w, y=y)
        return y if bias is None else y + bias

    # passive shadow observation (online drift monitoring): the sampled
    # forward executes its real substrate path below UNCHANGED - the shadow
    # recorder only taps operand/output stats through debug callbacks.  The
    # output fed to the recorder is the closest available pre-ADC proxy:
    # the fakequant product for fakequant/analytic, the kernel output for
    # bit-serial (post-ADC, a conservative sigma_yo proxy - drift detection
    # is driven by the one-sided x_max/w_max tests either way).
    shadow = substrate_lib.active_shadow_recorder()

    def _shadow_note(y_obs):
        if shadow is not None:
            shadow.observe(site or substrate_lib.DEFAULT_SITE, x, w, y=y_obs)

    stats = sub.site_stats(site)  # None => dynamic per-batch statistics
    if stats is None:
        with jax.named_scope("imc"):
            x_max = _dynamic_max(x)
            w_max = _dynamic_max(w)
    else:
        x_max = stats.x_max
        w_max = stats.w_max

    if cfg.mode == "fakequant":
        with jax.named_scope("imc"):
            xq = _fq_ste(x, cfg.bx, cfg.x_signed, x_max)
            wq = _fq_ste(w, cfg.bw, True, w_max)
        y = jnp.einsum("...k,km->...m", xq, wq)
        _shadow_note(y)
        return y if bias is None else y + bias

    if cfg.mode == "imc_analytic":
        n = x.shape[-1]
        with jax.named_scope("imc"):
            xq = _fq_ste(x, cfg.bx, cfg.x_signed, x_max)
            wq = _fq_ste(w, cfg.bw, True, w_max)
        y = jnp.einsum("...k,km->...m", xq, wq)
        _shadow_note(y)
        with jax.named_scope("imc"):
            if stats is None:
                sigma_yo = jax.lax.stop_gradient(jnp.std(y) + 1e-9)
            else:
                sigma_yo = stats.sigma_yo
            snr_a_db = cfg.resolved_snr_a_db(n)
            sigma_a = sigma_yo * 10.0 ** (-snr_a_db / 20.0)
            if rng is not None:
                y = y + sigma_a * jax.random.normal(rng, y.shape,
                                                    dtype=y.dtype)
            # MPC output ADC: clip at zeta*sigma, quantize with B_ADC bits
            # (STE)
            b_adc = cfg.resolved_b_adc(n)
            y_c = cfg.y_clip_sigmas * sigma_yo
            y = _fq_ste(jnp.clip(y, -y_c, y_c), b_adc, True, y_c)
        return y if bias is None else y + bias

    if cfg.mode == "imc_bitserial":
        from repro.kernels import ops as kops

        # the kernel is this substrate's matmul: its plane products and
        # per-plane ADC stay in the site's scope
        n = x.shape[-1]
        mcfg = kops.matmul_config_from_imc(cfg, n)
        lead = x.shape[:-1]
        x2 = x.reshape((-1, x.shape[-1]))
        y = kops.imc_matmul(x2, w, mcfg, key=rng, x_max=x_max, w_max=w_max)
        y = y.reshape(lead + (w.shape[-1],)).astype(x.dtype)
        _shadow_note(y)
        return y if bias is None else y + bias

    raise ValueError(f"unknown IMC mode {cfg.mode!r}")


def layer_rng(base: Optional[jax.Array], layer_id: int) -> Optional[jax.Array]:
    """Derive a per-layer noise key (None passes through)."""
    if base is None:
        return None
    return jax.random.fold_in(base, layer_id)
