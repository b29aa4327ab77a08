"""Top-level LM: init, forward/loss (train), prefill and decode (serve).

Scan-over-layers: parameters for each pattern position are stacked along a
leading ``n_full_cycles`` axis under ``params["blocks"]``; remainder layers
live unstacked under ``params["tail"]``.  Caches mirror this layout.

Modality stubs (DESIGN.md SS4): ``vlm`` consumes precomputed patch embeddings
(batch, prefix_len, d_model) scattered over the first positions; ``audio``
consumes EnCodec token ids directly (they are ordinary vocab tokens).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.imc_linear import layer_rng, linear
from repro.launch.sharding import ws
from repro.models import transformer as tf
from repro.models.layers import (
    apply_norm,
    dtype_of,
    embed_init,
    init_norm,
    sinusoidal_positions,
    softcap,
)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(key, cfg: ArchConfig) -> Dict[str, Any]:
    dtype = dtype_of(cfg.dtype)
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": embed_init(keys[0], cfg.padded_vocab, cfg.d_model, dtype),
        "final_norm": init_norm(cfg.norm_kind, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(keys[1], (cfg.d_model, cfg.padded_vocab)) * 0.02
        ).astype(dtype)
    if cfg.pos_kind == "learned":
        params["pos_table"] = (
            jax.random.normal(keys[2], (cfg.max_seq, cfg.d_model)) * 0.02
        ).astype(dtype)

    n_full = cfg.n_full_cycles
    blocks = {}
    for pi, kind in enumerate(cfg.pattern):
        ks = jax.random.split(jax.random.fold_in(keys[3], pi), n_full)
        stacked = [tf.init_block(k, cfg, kind, dtype) for k in ks]
        blocks[f"p{pi}"] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *stacked
        )
    params["blocks"] = blocks
    tail = {}
    for ti, kind in enumerate(cfg.tail_kinds):
        tail[f"t{ti}"] = tf.init_block(
            jax.random.fold_in(keys[4], ti), cfg, kind, dtype
        )
    if tail:
        params["tail"] = tail
    return params


def init_cache(cfg: ArchConfig, batch: int, cache_len: int):
    dtype = dtype_of(cfg.dtype)
    n_full = cfg.n_full_cycles
    cache: Dict[str, Any] = {"blocks": {}, "pos": jnp.zeros((), jnp.int32)}
    for pi, kind in enumerate(cfg.pattern):
        one = tf.init_block_cache(cfg, kind, batch, cache_len, dtype)
        cache["blocks"][f"p{pi}"] = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (n_full,) + x.shape).copy(), one
        )
    for ti, kind in enumerate(cfg.tail_kinds):
        cache.setdefault("tail", {})[f"t{ti}"] = tf.init_block_cache(
            cfg, kind, batch, cache_len, dtype
        )
    return cache


def init_paged_cache(cfg: ArchConfig, batch: int, cache_len: int,
                     num_blocks: int, block_size: int):
    """Like :func:`init_cache` but global-attention layers get a paged block
    pool ({"pk","pv","bt"}) instead of a per-slot contiguous slice.  Every
    paged layer shares the same block-table CONTENTS (allocation is identical
    across layers); each carries its own copy so the cache tree stays
    self-contained under scan-over-layers."""
    dtype = dtype_of(cfg.dtype)
    n_full = cfg.n_full_cycles
    cache: Dict[str, Any] = {"blocks": {}, "pos": jnp.zeros((), jnp.int32)}
    for pi, kind in enumerate(cfg.pattern):
        one = tf.init_block_cache_paged(cfg, kind, batch, cache_len, dtype,
                                        num_blocks, block_size)
        cache["blocks"][f"p{pi}"] = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (n_full,) + x.shape).copy(), one
        )
    for ti, kind in enumerate(cfg.tail_kinds):
        cache.setdefault("tail", {})[f"t{ti}"] = tf.init_block_cache_paged(
            cfg, kind, batch, cache_len, dtype, num_blocks, block_size
        )
    return cache


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------


@jax.named_scope("embed")
def _embed_inputs(params, cfg: ArchConfig, tokens, prefix_embeds, positions):
    x = params["embed"][tokens]  # (B, S, d)
    if cfg.emb_scale:
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
    if cfg.modality == "vlm" and prefix_embeds is not None:
        p = prefix_embeds.shape[1]
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x[:, p:]], axis=1)
    if cfg.pos_kind == "learned":
        x = x + params["pos_table"][positions]
    elif cfg.pos_kind == "sinusoidal":
        x = x + sinusoidal_positions(positions, cfg.d_model).astype(x.dtype)
    return ws(x, "act_btd")


@jax.named_scope("head")
def _head(params, cfg: ArchConfig, x):
    x = apply_norm(params["final_norm"], x, cfg.norm_kind)
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", x, params["embed"])
    else:
        logits = linear(params["lm_head"], x, cfg.imc, site="lm_head")
    logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    if cfg.padded_vocab != cfg.vocab_size:
        # mask padding rows out of the softmax
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad_mask, -1e9, logits)
    return ws(logits, "act_btv")


# ---------------------------------------------------------------------------
# full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------


def _scan_full(params, cfg: ArchConfig, x, positions, rng, want_cache,
               cache_len, true_len=None):
    n_full = cfg.n_full_cycles

    def cycle(x_aux, inp):
        x, aux = x_aux
        bp, li = inp
        caches = []
        for pi, kind in enumerate(cfg.pattern):
            r = None if rng is None else jax.random.fold_in(
                jax.random.fold_in(rng, pi), li
            )
            x, c, a = tf.apply_block_full(
                bp[f"p{pi}"], x, cfg, kind, positions, r, want_cache,
                cache_len, true_len=true_len,
            )
            aux = aux + a
            caches.append(c)
        out_caches = {f"p{pi}": c for pi, c in enumerate(caches)} if want_cache else 0
        return (x, aux), out_caches

    body = cycle
    if cfg.remat and not want_cache:
        body = jax.checkpoint(cycle, prevent_cse=False)

    (x, aux), caches = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)),
        (params["blocks"], jnp.arange(n_full)),
    )

    tail_caches = {}
    for ti, kind in enumerate(cfg.tail_kinds):
        r = None if rng is None else jax.random.fold_in(rng, 10_000 + ti)
        x, c, a = tf.apply_block_full(
            params["tail"][f"t{ti}"], x, cfg, kind, positions, r,
            want_cache, cache_len, true_len=true_len,
        )
        aux = aux + a
        tail_caches[f"t{ti}"] = c
    return x, aux, caches, tail_caches


def forward(
    params,
    cfg: ArchConfig,
    tokens,  # (B, S) int32
    prefix_embeds=None,  # (B, P, d) for vlm
    rng=None,
):
    """Full-sequence logits (B, S, V)."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = _embed_inputs(params, cfg, tokens, prefix_embeds, positions)
    x, aux, _, _ = _scan_full(params, cfg, x, positions, rng, False, 0)
    return _head(params, cfg, x), aux


def loss_fn(
    params,
    cfg: ArchConfig,
    batch: Dict[str, jax.Array],  # tokens (B,S), optional prefix_embeds
    rng=None,
    aux_coef: float = 0.01,
    z_coef: float = 1e-4,
):
    """Next-token cross entropy + MoE aux + z-loss. Returns (loss, metrics)."""
    tokens = batch["tokens"]
    logits, aux = forward(params, cfg, tokens, batch.get("prefix_embeds"), rng)
    targets = tokens[:, 1:]
    lg = logits[:, :-1].astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0] - logz
    ce = -jnp.mean(ll)
    z_loss = jnp.mean(logz**2)
    loss = ce + aux_coef * aux + z_coef * z_loss
    return loss, {"ce": ce, "moe_aux": aux, "z_loss": z_loss}


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------


def prefill(
    params,
    cfg: ArchConfig,
    tokens,  # (B, S)
    cache_len: int,
    prefix_embeds=None,
    rng=None,
    true_len=None,  # optional (B,) int32: true prompt lengths (S is padding)
):
    """Process a prompt; returns (last-position logits, cache).

    ``true_len`` enables bucketed prefill: ``tokens`` may be right-padded to a
    bucket length S, with each row's real prompt occupying the first
    ``true_len[i]`` positions.  Causality keeps the padded positions from
    contaminating real ones; the returned logits are gathered at each row's
    true last position, ``cache["pos"]`` becomes the (B,) vector ``true_len``,
    and sliding-window ring caches are packed per-row from the true tail.
    Attention KV-cache rows beyond ``true_len`` hold pad garbage but are
    masked during decode until they are overwritten position-by-position.
    """
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = _embed_inputs(params, cfg, tokens, prefix_embeds, positions)
    x, _, caches, tail_caches = _scan_full(
        params, cfg, x, positions, rng, True, cache_len, true_len=true_len
    )
    if true_len is None:
        x_last = x[:, -1:]
        pos = jnp.asarray(s, jnp.int32)
    else:
        pos = jnp.asarray(true_len, jnp.int32)
        x_last = x[jnp.arange(b), pos - 1][:, None, :]
    logits = _head(params, cfg, x_last)
    cache = {"blocks": caches, "pos": pos}
    if tail_caches:
        cache["tail"] = tail_caches
    return logits, cache


def decode_step(
    params,
    cfg: ArchConfig,
    token,  # (B,) int32 - the most recent token
    cache,
    rng=None,
    active=None,  # optional (B,) bool: rows allowed to write their KV slot
):
    """One decode step. Returns (logits (B, 1, V), new_cache).

    ``cache["pos"]`` may be a scalar (all slots synchronized) or a (B,)
    vector of per-slot positions (continuous batching); either way the
    returned cache carries ``pos + 1`` with the same shape.  ``active``
    matters only for paged caches: an inactive row's stale block table may
    reference physical blocks reassigned to another request, so its K/V
    write is routed to the garbage block.
    """
    b = token.shape[0]
    pos = cache["pos"]
    positions = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))[:, None]
    x = _embed_inputs(params, cfg, token[:, None], None, positions)

    def cycle(x, inp):
        bp, bc, li = inp
        new_cs = {}
        for pi, kind in enumerate(cfg.pattern):
            r = None if rng is None else jax.random.fold_in(
                jax.random.fold_in(rng, pi), li
            )
            x, nc = tf.apply_block_decode(bp[f"p{pi}"], x, cfg, kind,
                                          bc[f"p{pi}"], pos, r, active=active)
            new_cs[f"p{pi}"] = nc
        return x, new_cs

    x, new_caches = jax.lax.scan(
        cycle, x,
        (params["blocks"], cache["blocks"], jnp.arange(cfg.n_full_cycles)),
    )
    new_cache = {"blocks": new_caches, "pos": pos + 1}
    if "tail" in cache:
        new_tail = {}
        for ti, kind in enumerate(cfg.tail_kinds):
            r = None if rng is None else jax.random.fold_in(rng, 10_000 + ti)
            x, nc = tf.apply_block_decode(
                params["tail"][f"t{ti}"], x, cfg, kind, cache["tail"][f"t{ti}"],
                pos, r, active=active,
            )
            new_tail[f"t{ti}"] = nc
        new_cache["tail"] = new_tail
    logits = _head(params, cfg, x)
    return logits, new_cache


def param_count(params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))
