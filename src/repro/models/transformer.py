"""Block assembly: pattern-cycled decoder layers with scan-over-layers.

Layers are grouped by the config's block ``pattern`` (e.g. gemma2 =
("local","attn"), recurrentgemma = ("rglru","rglru","local"), mamba2 =
("ssm",)).  Parameters for each pattern position are stacked across the
``n_full_cycles`` repetitions and applied under jax.lax.scan (small HLO,
fast multi-pod compiles); the remainder layers (n_layers % len(pattern)) are
applied as an explicit tail.

Three execution paths share the same block code:
  train/forward  - full sequence, no caches (optionally remat per cycle)
  prefill        - full sequence, additionally returns per-layer decode caches
  decode         - one token against caches
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.imc_linear import layer_rng
from repro.launch.sharding import ws
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import rglru as rg_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import apply_mlp, apply_norm, init_mlp, init_norm


# ---------------------------------------------------------------------------
# per-kind init
# ---------------------------------------------------------------------------


def attn_dims(cfg: ArchConfig, kind: str) -> attn_lib.AttnDims:
    hd = cfg.resolved_head_dim
    return attn_lib.AttnDims(
        n_heads=cfg.n_heads,
        n_kv=cfg.n_kv_heads,
        head_dim=hd,
        scale=cfg.attn_logit_scale or hd**-0.5,
        softcap_val=cfg.attn_softcap,
        window=cfg.window if kind == "local" else None,
        q_block=cfg.flash_q_block,
        kv_block=cfg.flash_kv_block,
        rope_theta=cfg.rope_theta,
        use_rope=cfg.pos_kind == "rope",
        paged_kernel=cfg.decode_attn != "gather",
    )


def init_block(key, cfg: ArchConfig, kind: str, dtype):
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    p: Dict[str, Any] = {"norm1": init_norm(cfg.norm_kind, d, dtype)}
    if cfg.post_norm:
        p["norm1_post"] = init_norm(cfg.norm_kind, d, dtype)
    if kind in ("attn", "local"):
        p["mixer"] = attn_lib.init_attention(
            ks[0], d, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim, dtype
        )
    elif kind == "ssm":
        p["mixer"] = ssm_lib.init_ssm(
            ks[0], d, cfg.ssm_expand, cfg.ssm_head_dim, cfg.ssm_groups,
            cfg.ssm_state, cfg.conv_width, dtype,
        )
        return p  # mamba2 blocks have no separate MLP
    elif kind == "rglru":
        p["mixer"] = rg_lib.init_rglru(ks[0], d, cfg.rnn_width,
                                       cfg.rnn_conv_width, dtype)
    else:
        raise ValueError(kind)
    p["norm2"] = init_norm(cfg.norm_kind, d, dtype)
    if cfg.post_norm:
        p["norm2_post"] = init_norm(cfg.norm_kind, d, dtype)
    if cfg.n_experts > 0:
        p["moe"] = moe_lib.init_moe(ks[1], d, cfg.d_ff, cfg.n_experts,
                                    cfg.mlp_kind, dtype)
    else:
        p["mlp"] = init_mlp(ks[1], d, cfg.d_ff, cfg.mlp_kind, dtype)
    return p


def init_block_cache(cfg: ArchConfig, kind: str, batch: int, cache_len: int, dtype):
    if kind in ("attn", "local"):
        span = min(cfg.window, cache_len) if kind == "local" else cache_len
        return attn_lib.init_kv_cache(
            batch, span, cfg.n_kv_heads, cfg.resolved_head_dim, dtype
        )
    if kind == "ssm":
        return ssm_lib.init_ssm_cache(batch, cfg, dtype)
    if kind == "rglru":
        return rg_lib.init_rglru_cache(batch, cfg.rnn_width, cfg.rnn_conv_width, dtype)
    raise ValueError(kind)


def init_block_cache_paged(cfg: ArchConfig, kind: str, batch: int,
                           cache_len: int, dtype, num_blocks: int,
                           block_size: int):
    """Paged variant: global-attention KV caches become block pools + block
    tables; sliding-window rings (already bounded at the window span) and
    recurrent states (fixed-size per slot) keep their contiguous layout."""
    if kind == "attn":
        max_blocks = -(-cache_len // block_size)
        return attn_lib.init_paged_kv_cache(
            batch, num_blocks, block_size, max_blocks, cfg.n_kv_heads,
            cfg.resolved_head_dim, dtype,
        )
    return init_block_cache(cfg, kind, batch, cache_len, dtype)


# ---------------------------------------------------------------------------
# per-kind apply
# ---------------------------------------------------------------------------


def _mlp_half(p, x, cfg: ArchConfig, rng):
    h = apply_norm(p["norm2"], x, cfg.norm_kind)
    with jax.named_scope("mlp"):
        if cfg.n_experts > 0:
            out, aux = moe_lib.apply_moe(
                p["moe"], h, cfg.n_experts, cfg.top_k, cfg.capacity_factor,
                cfg.moe_group_size, cfg.mlp_kind, cfg.imc, rng,
            )
        else:
            out, aux = apply_mlp(p["mlp"], h, cfg.mlp_kind, cfg.imc, rng), 0.0
    if cfg.post_norm:
        out = apply_norm(p["norm2_post"], out, cfg.norm_kind)
    return ws(x + out, "act_btd"), aux


@jax.named_scope("block")
def apply_block_full(
    p,
    x,  # (B, S, d)
    cfg: ArchConfig,
    kind: str,
    positions,  # (B, S)
    rng,
    want_cache: bool,
    cache_len: int,
    true_len=None,  # optional (B,) true prompt lengths (bucketed prefill)
):
    """Full-sequence block. Returns (x, cache_or_None, moe_aux)."""
    h = apply_norm(p["norm1"], x, cfg.norm_kind)
    cache = None
    if kind in ("attn", "local"):
        dims = attn_dims(cfg, kind)
        with jax.named_scope("attn"):
            q, k, v = attn_lib._project_qkv(p["mixer"], h, dims, positions,
                                            cfg.imc, rng, site_prefix=kind)
            if dims.window is not None and dims.window < h.shape[1]:
                ctx = attn_lib.banded_attention(q, k, v, dims)
            else:
                ctx = attn_lib.flash_attention(q, k, v, dims)
            b, s = h.shape[:2]
            ctx = ctx.reshape(b, s, dims.n_heads * dims.head_dim)
            out = attn_lib.linear(p["mixer"]["wo"], ctx, cfg.imc, rng,
                                  site=f"{kind}.wo")
        if want_cache:
            cache = _pack_kv_cache(k, v, cache_len, dims.window, x.dtype,
                                   true_len)
    elif kind == "ssm":
        with jax.named_scope("ssm"):
            out, state = ssm_lib.ssm_forward(p["mixer"], h, cfg, cfg.imc,
                                             rng)
        if want_cache:
            if true_len is not None:
                # recurrent state integrates pad garbage; serve engines must
                # use exact-length prefill for recurrent patterns
                raise ValueError("bucketed (padded) prefill is not supported "
                                 "for ssm blocks")
            cache = _pack_ssm_cache(p, h, state, cfg, x.dtype)
        x = x + (apply_norm(p["norm1_post"], out, cfg.norm_kind)
                 if cfg.post_norm else out)
        return ws(x, "act_btd"), cache, 0.0  # mamba2: no MLP half
    elif kind == "rglru":
        with jax.named_scope("rglru"):
            out, h_last = rg_lib.rglru_forward(p["mixer"], h, cfg, cfg.imc,
                                               rng)
        if want_cache:
            if true_len is not None:
                raise ValueError("bucketed (padded) prefill is not supported "
                                 "for rglru blocks")
            cache = _pack_rglru_cache(p, h, h_last, cfg, x.dtype)
    else:
        raise ValueError(kind)
    if cfg.post_norm:
        out = apply_norm(p["norm1_post"], out, cfg.norm_kind)
    x = x + out
    x = ws(x, "act_btd")
    x, aux = _mlp_half(p, x, cfg, rng)
    return x, cache, aux


@jax.named_scope("block")
def apply_block_decode(p, x, cfg: ArchConfig, kind: str, cache, pos, rng,
                       active=None):
    """One-token block. Returns (x, new_cache)."""
    h = apply_norm(p["norm1"], x, cfg.norm_kind)
    if kind in ("attn", "local"):
        dims = attn_dims(cfg, kind)
        with jax.named_scope("attn"):
            out, new_cache = attn_lib.attention_decode(
                p["mixer"], h, cache, pos, dims, cfg.imc, rng, active=active,
                site_prefix=kind,
            )
    elif kind == "ssm":
        with jax.named_scope("ssm"):
            out, new_cache = ssm_lib.ssm_decode(p["mixer"], h, cache, cfg,
                                                cfg.imc, rng)
        x = x + (apply_norm(p["norm1_post"], out, cfg.norm_kind)
                 if cfg.post_norm else out)
        return x, new_cache
    elif kind == "rglru":
        with jax.named_scope("rglru"):
            out, new_cache = rg_lib.rglru_decode(p["mixer"], h, cache, cfg,
                                                 cfg.imc, rng)
    else:
        raise ValueError(kind)
    if cfg.post_norm:
        out = apply_norm(p["norm1_post"], out, cfg.norm_kind)
    x = x + out
    x, _ = _mlp_half(p, x, cfg, rng)
    return x, new_cache


# ---------------------------------------------------------------------------
# prefill cache packing
# ---------------------------------------------------------------------------


def _pack_kv_cache(k, v, cache_len: int, window: Optional[int], dtype,
                   true_len=None):
    """Arrange prefill K/V into the decode cache layout.

    With ``true_len`` (bucketed prefill: per-row true lengths, S is the padded
    bucket), the linear (global) layout needs no special casing - rows beyond
    ``true_len`` hold pad garbage that decode masks and then overwrites.  The
    sliding-window ring layout does: each row's ring must be packed from ITS
    true tail ``[true_len - w, true_len)`` at ring phase ``true_len % w``, or
    the pad tail would alias (and clobber) live in-window positions.
    """
    b, s = k.shape[:2]
    if window is None:
        pad = cache_len - s
        assert pad >= 0, (cache_len, s)
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(dtype)
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(dtype)
        return {"k": kc, "v": vc}
    w = min(window, cache_len)
    if s < w:
        # slot j = position j for every row, padded or not
        kc = jnp.pad(k, ((0, 0), (0, w - s), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, w - s), (0, 0), (0, 0)))
        return {"k": kc.astype(dtype), "v": vc.astype(dtype)}
    if true_len is None:
        k_last, v_last = k[:, s - w :], v[:, s - w :]
        shift = s % w
        kc = jnp.roll(k_last, shift, axis=1)
        vc = jnp.roll(v_last, shift, axis=1)
        return {"k": kc.astype(dtype), "v": vc.astype(dtype)}

    tl = jnp.asarray(true_len, jnp.int32)

    def ring_row(k_row, v_row, tl_row):  # (S, H, hd), (S, H, hd), ()
        start = jnp.clip(tl_row - w, 0, s - w)
        ks = jax.lax.dynamic_slice_in_dim(k_row, start, w, axis=0)
        vs = jax.lax.dynamic_slice_in_dim(v_row, start, w, axis=0)
        # element j holds position start+j; ring slot of position p is p % w,
        # so roll by start % w (0 when the prompt hasn't filled the window:
        # start = 0 and slot j = position j already)
        shift = start % w
        return jnp.roll(ks, shift, axis=0), jnp.roll(vs, shift, axis=0)

    kc, vc = jax.vmap(ring_row)(k, v, tl)
    return {"k": kc.astype(dtype), "v": vc.astype(dtype)}


def _pack_ssm_cache(p, h_in, state, cfg: ArchConfig, dtype):
    """SSD decode cache from prefill: final state + last conv-window inputs."""
    from repro.core.imc_linear import linear as _linear

    proj = _linear(p["mixer"]["in_proj"], h_in[:, -(cfg.conv_width - 1):],
                   cfg.imc, site="ssm.in_proj")
    d_inner, n_heads, conv_ch = ssm_lib.ssm_dims(
        cfg.d_model, cfg.ssm_expand, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
    )
    gn = cfg.ssm_groups * cfg.ssm_state
    xbc = proj[..., d_inner : 2 * d_inner + 2 * gn]
    return {"conv": xbc.astype(dtype), "state": state}


def _pack_rglru_cache(p, h_in, h_last, cfg: ArchConfig, dtype):
    from repro.core.imc_linear import linear as _linear

    xb = _linear(p["mixer"]["rg_x"], h_in[:, -(cfg.rnn_conv_width - 1):],
                 cfg.imc, site="rg.x")
    return {"conv": xb.astype(dtype), "h": h_last}
