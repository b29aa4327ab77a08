"""GQA/MQA/MHA attention: flash-style blocked causal attention (pure JAX,
lax.scan online-softmax), an exact banded path for sliding windows, and a
single-token decode path designed for sequence-sharded KV caches.

Memory behaviour is the point: full S x S score matrices are never
materialized, so prefill_32k compiles within HBM at the production meshes
(deliverable (e)); the decode path's softmax over the sequence axis is sharded
over the ``model`` mesh axis (logical name "kv_bshd"), which XLA GSPMD turns
into the flash-decode partial-max/partial-sum collective pattern (DESIGN SS5).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.imc_linear import DIGITAL, IMCConfig, linear
from repro.kernels.paged_attention import paged_attention_decode, write_routing
from repro.launch.sharding import attn_carry_pin, attn_expand_groups, attn_grad_spec, ws, ws_attn
from repro.models.layers import dense_init, rope, softcap

NEG_INF = -1e30


def init_attention(key, d_model: int, n_heads: int, n_kv: int, head_dim: int, dtype):
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim, dtype),
        "wk": dense_init(ks[1], d_model, n_kv * head_dim, dtype),
        "wv": dense_init(ks[2], d_model, n_kv * head_dim, dtype),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model, dtype),
    }


class AttnDims(NamedTuple):
    n_heads: int
    n_kv: int
    head_dim: int
    scale: float
    softcap_val: Optional[float]
    window: Optional[int]
    q_block: int
    kv_block: int
    rope_theta: float
    use_rope: bool
    # paged decode attention: True streams KV blocks through the fused
    # online-softmax kernel (repro.kernels.paged_attention); False takes the
    # reference gather path that materializes pool[bt] (escape hatch,
    # cfg.decode_attn="gather")
    paged_kernel: bool = True


def _project_qkv(params, x, dims: AttnDims, positions, imc, rng,
                 site_prefix: str = "attn"):
    b, s, _ = x.shape
    q = linear(params["wq"], x, imc, rng,
               site=f"{site_prefix}.wq").reshape(b, s, dims.n_heads,
                                                 dims.head_dim)
    k = linear(params["wk"], x, imc, rng,
               site=f"{site_prefix}.wk").reshape(b, s, dims.n_kv,
                                                 dims.head_dim)
    v = linear(params["wv"], x, imc, rng,
               site=f"{site_prefix}.wv").reshape(b, s, dims.n_kv,
                                                 dims.head_dim)
    if dims.use_rope:
        q = rope(q, positions, dims.rope_theta)
        k = rope(k, positions, dims.rope_theta)
    q = ws(q, "act_bthd")
    return q, k, v


def _scores(q_blk, k_blk, dims: AttnDims):
    """q: (B, QB, Hkv, G, hd), k: (B, KB, Hkv, hd) -> (B, Hkv, G, QB, KB) f32."""
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk",
        q_blk.astype(jnp.float32),
        k_blk.astype(jnp.float32),
    )
    s = s * dims.scale
    if dims.softcap_val is not None:
        s = dims.softcap_val * jnp.tanh(s / dims.softcap_val)
    return s


def _block_mask(q_pos, k_pos, s_kv, window):
    mask = q_pos[:, None] >= k_pos[None, :]
    mask = jnp.logical_and(mask, (k_pos < s_kv)[None, :])
    if window is not None:
        mask = jnp.logical_and(mask, q_pos[:, None] - k_pos[None, :] < window)
    return mask


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_core(q, k, v, dims: AttnDims, q_offset: int, s_kv_true: int):
    out, _lse = _flash_fwd_impl(q, k, v, dims, q_offset, s_kv_true)
    return out


def _flash_fwd_impl(q, k, v, dims: AttnDims, q_offset: int, s_kv_true: int):
    """q: (B, nQ*QB, Hkv, G, hd) padded; k, v: (B, nKV*KB, Hkv, hd) padded.
    Returns (out same shape as q, lse (B, Hkv, G, nQ*QB))."""
    b, s_qp, hkv, g, hd = q.shape
    qb, kb = dims.q_block, dims.kv_block
    qb, kb = min(qb, s_qp), min(kb, k.shape[1])
    n_q, n_kv = s_qp // qb, k.shape[1] // kb
    qg = q.reshape(b, n_q, qb, hkv, g, hd)
    kv_idx = jnp.arange(n_kv)
    pin = attn_carry_pin(hkv, g)

    def q_block_fn(q_blk, iq):
        q_pos = q_offset + iq * qb + jnp.arange(qb)

        def kv_step(carry, jk):
            m, l, acc = carry
            k_blk = jax.lax.dynamic_slice_in_dim(k, jk * kb, kb, axis=1)
            v_blk = jax.lax.dynamic_slice_in_dim(v, jk * kb, kb, axis=1)
            s = _scores(q_blk, k_blk, dims)  # (B, Hkv, G, QB, KB) f32
            k_pos = jk * kb + jnp.arange(kb)
            mask = _block_mask(q_pos, k_pos, s_kv_true, dims.window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = pin(jnp.maximum(m, jnp.max(s, axis=-1)))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = pin(l * corr + jnp.sum(p, axis=-1))
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p, v_blk.astype(jnp.float32))
            acc_new = pin(acc * corr[..., None] + pv)
            return (m_new, l_new, acc_new), None

        m0 = pin(jnp.full((b, hkv, g, qb), NEG_INF, jnp.float32))
        l0 = pin(jnp.zeros((b, hkv, g, qb), jnp.float32))
        a0 = pin(jnp.zeros((b, hkv, g, qb, hd), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), kv_idx)
        out = acc / jnp.maximum(l[..., None], 1e-30)  # (B, Hkv, G, QB, hd)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))  # (B, Hkv, G, QB)
        return jnp.transpose(out, (0, 3, 1, 2, 4)), lse

    def scan_body(_, inputs):
        q_blk, iq = inputs
        return None, q_block_fn(q_blk, iq)

    _, (out, lse) = jax.lax.scan(
        scan_body, None, (jnp.moveaxis(qg, 1, 0), jnp.arange(n_q))
    )
    out = jnp.moveaxis(out, 0, 1).reshape(b, s_qp, hkv, g, hd).astype(q.dtype)
    lse = jnp.moveaxis(lse, 0, 3).reshape(b, hkv, g, s_qp)
    return out, lse


def _flash_fwd(q, k, v, dims, q_offset, s_kv_true):
    out, lse = _flash_fwd_impl(q, k, v, dims, q_offset, s_kv_true)
    return out, (q, k, v, out, lse)


def _flash_bwd(dims: AttnDims, q_offset: int, s_kv_true: int, res, dout):
    """True flash backward: recompute score blocks; O(block) memory."""
    q, k, v, out, lse = res
    b, s_qp, hkv, g, hd = q.shape
    qb = min(dims.q_block, s_qp)
    kb = min(dims.kv_block, k.shape[1])
    n_q = s_qp // qb
    pin_c = attn_carry_pin(hkv, g)
    dout = dout.astype(jnp.float32)
    # D = rowsum(dout * out): (B, Hkv, G, Sq)
    dmat = jnp.einsum("bshgd,bshgd->bhgs", dout, out.astype(jnp.float32))
    qg = jnp.moveaxis(q.reshape(b, n_q, qb, hkv, g, hd), 1, 0)
    dog = jnp.moveaxis(dout.reshape(b, n_q, qb, hkv, g, hd), 1, 0)
    lse_g = jnp.moveaxis(lse.reshape(b, hkv, g, n_q, qb), 3, 0)
    d_g = jnp.moveaxis(dmat.reshape(b, hkv, g, n_q, qb), 3, 0)
    n_kv = k.shape[1] // kb
    kv_idx = jnp.arange(n_kv)

    def q_block_step(carry, inp):
        dk_full, dv_full = carry
        q_blk, do_blk, lse_blk, d_blk, iq = inp
        q_pos = q_offset + iq * qb + jnp.arange(qb)

        def kv_step(c, jk):
            dq_blk, dk_f, dv_f = c
            dk_f = _pin(dk_f)
            dv_f = _pin(dv_f)
            k_blk = jax.lax.dynamic_slice_in_dim(k, jk * kb, kb, axis=1)
            v_blk = jax.lax.dynamic_slice_in_dim(v, jk * kb, kb, axis=1)
            s = _scores(q_blk, k_blk, dims)
            k_pos = jk * kb + jnp.arange(kb)
            mask = _block_mask(q_pos, k_pos, s_kv_true, dims.window)
            s_masked = jnp.where(mask[None, None, None], s, NEG_INF)
            p = jnp.exp(s_masked - lse_blk[..., None])  # (B,Hkv,G,QB,KB)
            dv_b = jnp.einsum("bhgqk,bqhgd->bkhd", p, do_blk)
            dp = jnp.einsum("bqhgd,bkhd->bhgqk", do_blk,
                            v_blk.astype(jnp.float32))
            ds = p * (dp - d_blk[..., None])
            if dims.softcap_val is not None:
                # d/ds_raw of c*tanh(s_raw/c) = 1 - (s_capped/c)^2; use a
                # mask-safe s (masked lanes have p = 0 but 0 * inf = nan)
                s_safe = jnp.where(mask[None, None, None], s_masked, 0.0)
                ds = ds * (1.0 - (s_safe / dims.softcap_val) ** 2)
            ds = ds * dims.scale
            ds = jnp.where(mask[None, None, None], ds, 0.0)
            dq_b = jnp.einsum("bhgqk,bkhd->bqhgd", ds,
                              k_blk.astype(jnp.float32))
            dk_b = jnp.einsum("bhgqk,bqhgd->bkhd", ds, q_blk.astype(jnp.float32))
            dk_f = jax.lax.dynamic_update_slice_in_dim(
                dk_f, jax.lax.dynamic_slice_in_dim(dk_f, jk * kb, kb, 1) + dk_b,
                jk * kb, axis=1,
            )
            dv_f = jax.lax.dynamic_update_slice_in_dim(
                dv_f, jax.lax.dynamic_slice_in_dim(dv_f, jk * kb, kb, 1) + dv_b,
                jk * kb, axis=1,
            )
            return (dq_blk + dq_b, dk_f, dv_f), None

        dq0 = jnp.zeros((b, qb, hkv, g, hd), jnp.float32)
        (dq_blk, dk_full, dv_full), _ = jax.lax.scan(
            kv_step, (dq0, dk_full, dv_full), kv_idx
        )
        return (dk_full, dv_full), dq_blk  # dq layout (B, QB, Hkv, G, hd)

    # keep grad-accumulator carries in the same (heads-on-model / replicated
    # for MQA) layout as k/v: without the pin, GSPMD reshards them with
    # all-to-alls every block
    gspec = attn_grad_spec(hkv, g)

    def _pin(x):
        if gspec is None:
            return x
        mesh, spec = gspec
        try:
            from jax.sharding import NamedSharding
            return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
        except (ValueError, TypeError):
            return x

    dk0 = _pin(jnp.zeros(k.shape, jnp.float32))
    dv0 = _pin(jnp.zeros(v.shape, jnp.float32))
    (dk, dv), dq = jax.lax.scan(
        q_block_step, (dk0, dv0),
        (qg, dog, lse_g, d_g, jnp.arange(n_q)),
    )
    dq = jnp.moveaxis(dq, 0, 1).reshape(b, s_qp, hkv, g, hd)
    # softcap note: _scores applies softcap BEFORE masking; ds above already
    # includes the tanh jacobian, and dq/dk absorbed dims.scale.
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_core.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, dims: AttnDims, q_offset=0):
    """Blocked causal attention, O(S*S) compute, O(S*KB) memory, with a true
    flash (recompute-based, custom_vjp) backward.

    q: (B, S, Hq, hd); k, v: (B, Skv, Hkv, hd).  Returns (B, S, Hq, hd).
    ``q_offset``: absolute position of q[0] relative to k[0] (0 for self-attn).
    """
    b, s_q, hq, hd = q.shape
    _, s_kv, hkv, _ = k.shape
    g = hq // hkv
    if g > 1 and attn_expand_groups(hkv, g):
        # GQA -> MHA expansion for clean head sharding (dk/dv fold back
        # through the AD of the repeat)
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        hkv, g = hq, 1
    qb, kb = min(dims.q_block, s_q), min(dims.kv_block, s_kv)
    n_q = -(-s_q // qb)
    n_kv = -(-s_kv // kb)
    pad_q = n_q * qb - s_q
    pad_kv = n_kv * kb - s_kv
    q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    qg, k, v = ws_attn(q.reshape(b, n_q * qb, hkv, g, hd), k, v)
    out = _flash_core(qg, k, v, dims, q_offset, s_kv)
    return out[:, :s_q].reshape(b, s_q, hq, hd)


def banded_attention(q, k, v, dims: AttnDims):  # noqa: C901
    """Exact sliding-window attention with O(S * W) compute: each q block only
    reads the [qo - W, qo + QB) slice of K/V (front-padded)."""
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    w = dims.window
    qb = min(dims.q_block, s)
    n_q = -(-s // qb)
    pad_q = n_q * qb - s
    q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    # front-pad K/V by W so every block slice is in range
    k_p = jnp.pad(k, ((0, 0), (w, pad_q), (0, 0), (0, 0)))
    v_p = jnp.pad(v, ((0, 0), (w, pad_q), (0, 0), (0, 0)))
    span = w + qb
    qg = q.reshape(b, n_q, qb, hkv, g, hd)

    def q_block_fn(q_blk, iq):
        start = iq * qb  # in padded coords this is qo - W + W
        k_blk = jax.lax.dynamic_slice_in_dim(k_p, start, span, axis=1)
        v_blk = jax.lax.dynamic_slice_in_dim(v_p, start, span, axis=1)
        s_mat = _scores(q_blk, k_blk, dims)  # (B, Hkv, G, QB, span)
        q_pos = iq * qb + jnp.arange(qb)
        k_pos = iq * qb - w + jnp.arange(span)  # absolute (may be < 0 = pad)
        mask = (
            (q_pos[:, None] >= k_pos[None, :])
            & (q_pos[:, None] - k_pos[None, :] < w)
            & (k_pos >= 0)[None, :]
            & (k_pos < s)[None, :]
        )
        s_mat = jnp.where(mask[None, None, None], s_mat, NEG_INF)
        p = jax.nn.softmax(s_mat, axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bhgqd", p, v_blk.astype(jnp.float32))
        return jnp.transpose(out, (0, 3, 1, 2, 4))

    def scan_body(_, inputs):
        q_blk, iq = inputs
        return None, q_block_fn(q_blk, iq)

    # checkpoint: backward recomputes each banded score block
    _, out = jax.lax.scan(
        jax.checkpoint(scan_body, prevent_cse=False), None,
        (jnp.moveaxis(qg, 1, 0), jnp.arange(n_q)),
    )
    out = jnp.moveaxis(out, 0, 1).reshape(b, n_q * qb, hq, hd)
    return out[:, :s].astype(q.dtype)


def attention_forward(
    params,
    x,  # (B, S, d)
    dims: AttnDims,
    positions,  # (B, S) absolute positions
    imc: IMCConfig = DIGITAL,
    rng=None,
    site_prefix: str = "attn",
):
    q, k, v = _project_qkv(params, x, dims, positions, imc, rng, site_prefix)
    if dims.window is not None and dims.window < x.shape[1]:
        ctx = banded_attention(q, k, v, dims)
    else:
        # window >= S covers every causal pair: run flash with the window
        # mask dropped instead of relying on it being a causal no-op
        ctx = flash_attention(q, k, v, dims._replace(window=None))
    b, s = x.shape[:2]
    ctx = ctx.reshape(b, s, dims.n_heads * dims.head_dim)
    return linear(params["wo"], ctx, imc, rng, site=f"{site_prefix}.wo")


# ---------------------------------------------------------------------------
# decode (one new token against a cache)
# ---------------------------------------------------------------------------


def init_kv_cache(batch: int, cache_len: int, n_kv: int, head_dim: int, dtype):
    shape = (batch, cache_len, n_kv, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_kv_cache(batch: int, num_blocks: int, block_size: int,
                        max_blocks: int, n_kv: int, head_dim: int, dtype):
    """Paged KV cache: a shared block pool plus a per-slot block table.

    ``pk``/``pv`` are the physical pools (num_blocks, block_size, Hkv, hd);
    ``bt`` maps each slot's logical block j to a physical block id.  Physical
    block 0 is the GARBAGE block: it is never allocated to a request, block
    tables point to it for unallocated logical blocks, and inactive rows'
    decode writes are routed to it (see ``attention_decode``).
    """
    shape = (num_blocks, block_size, n_kv, head_dim)
    return {
        "pk": jnp.zeros(shape, dtype),
        "pv": jnp.zeros(shape, dtype),
        "bt": jnp.zeros((batch, max_blocks), jnp.int32),
    }


def _decode_attend(params, x, q, k, v, valid, dims: AttnDims, imc, rng,
                   site_prefix: str = "attn"):
    """Single-token attention over a (B, Skv, Hkv, hd) K/V view with a
    (B, Skv) validity mask; shared by the contiguous and paged cache paths."""
    b = x.shape[0]
    hq, hkv, hd = dims.n_heads, dims.n_kv, dims.head_dim
    g = hq // hkv
    qg = q.reshape(b, hkv, g, hd)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32), k.astype(jnp.float32))
    s = s * dims.scale
    if dims.softcap_val is not None:
        s = dims.softcap_val * jnp.tanh(s / dims.softcap_val)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    # softmax over the (possibly model-axis-sharded) sequence dim: GSPMD emits
    # the partial-max/sum + all-reduce flash-decode pattern automatically
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhgk,bkhd->bhgd", p, v.astype(jnp.float32))
    ctx = ctx.reshape(b, 1, hq * hd).astype(x.dtype)
    return linear(params["wo"], ctx, imc, rng, site=f"{site_prefix}.wo")


def _attention_decode_paged(params, x, cache, pos_b, dims: AttnDims, imc, rng,
                            active, site_prefix: str = "attn"):
    """Paged decode: scatter the new K/V into the tail block and attend over
    the block table.

    Default path (``dims.paged_kernel``): the fused kernel in
    ``repro.kernels.paged_attention`` walks each slot's live blocks in-kernel,
    several per step, into an online-softmax accumulator and writes the new
    token once inside the same kernel - the gathered ``pool[bt]`` copy never
    exists.  Escape hatch (``cfg.decode_attn =
    "gather"``): scatter, then materialize the gathered view and run a
    full-row softmax (the reference math, kept selectable for debugging).

    Masked (invalid) lanes read garbage from unallocated blocks but contribute
    exactly zero probability, so both paths reproduce the contiguous layout
    token-for-token.  New-token writes follow the garbage-block-0 routing
    contract (``paged_attention.write_routing``): rows with ``active ==
    False`` (a retired slot's stale table may point at physical blocks the
    allocator already handed to another request) AND rows whose position
    overran the slot's capacity (clipping the logical block index would
    clobber the slot's last LIVE block) write to garbage block 0.
    """
    assert dims.window is None, "paged KV caches are global-attention only"
    b = x.shape[0]
    positions = pos_b[:, None]
    q, k_new, v_new = _project_qkv(params, x, dims, positions, imc, rng,
                                   site_prefix)
    pk, pv, bt = cache["pk"], cache["pv"], cache["bt"]
    block = pk.shape[1]
    max_blocks = bt.shape[1]
    hq, hkv, hd = dims.n_heads, dims.n_kv, dims.head_dim
    if dims.paged_kernel:
        g = hq // hkv
        qg = q.reshape(b, hkv, g, hd)
        ctx, pk, pv = paged_attention_decode(
            qg, k_new[:, 0], v_new[:, 0], pk, pv, bt, pos_b, active,
            scale=dims.scale, softcap=dims.softcap_val)
        ctx = ctx.reshape(b, 1, hq * hd).astype(x.dtype)
        y = linear(params["wo"], ctx, imc, rng, site=f"{site_prefix}.wo")
        return y, {"pk": pk, "pv": pv, "bt": bt}
    dest, off = write_routing(bt, pos_b, block, active)
    pk = pk.at[dest, off].set(k_new[:, 0].astype(pk.dtype))
    pv = pv.at[dest, off].set(v_new[:, 0].astype(pv.dtype))
    s_kv = max_blocks * block
    # head-sharded logical name: the pools themselves are head-sharded under
    # the tensor-parallel serve engine, so the gathered view must keep heads
    # on the model axis (sequence-sharding here would all-to-all every step)
    k = ws(pk[bt].reshape(b, s_kv, hkv, hd), "paged_kv_bshd")
    v = ws(pv[bt].reshape(b, s_kv, hkv, hd), "paged_kv_bshd")
    valid = jnp.arange(s_kv)[None, :] <= pos_b[:, None]
    y = _decode_attend(params, x, q, k, v, valid, dims, imc, rng, site_prefix)
    return y, {"pk": pk, "pv": pv, "bt": bt}


def attention_decode(
    params,
    x,  # (B, 1, d)
    cache,  # {"k","v"}: (B, Skv, Hkv, hd) (ring buffer when window), or a
    #         paged {"pk","pv","bt"} block pool (global attention only)
    pos,  # int32 scalar OR (B,) per-slot vector: tokens already in the cache
    dims: AttnDims,
    imc: IMCConfig = DIGITAL,
    rng=None,
    active=None,  # optional (B,) bool: rows allowed to write their K/V slot
    site_prefix: str = "attn",
):
    b = x.shape[0]
    # per-slot positions: a scalar broadcasts to the whole batch (wave-style
    # synchronized decode); a (B,) vector lets every slot sit at its own depth
    # (continuous batching with unequal prompt lengths)
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    if "pk" in cache:
        return _attention_decode_paged(params, x, cache, pos_b, dims, imc,
                                       rng, active, site_prefix)
    positions = pos_b[:, None]
    q, k_new, v_new = _project_qkv(params, x, dims, positions, imc, rng,
                                   site_prefix)
    s_kv = cache["k"].shape[1]
    # ring buffer for sliding windows; plain append for global attention
    if dims.window is not None:
        slot = pos_b % s_kv
    else:
        slot = jnp.minimum(pos_b, s_kv - 1)
    rows = jnp.arange(b)
    k = cache["k"].at[rows, slot].set(k_new[:, 0].astype(cache["k"].dtype))
    v = cache["v"].at[rows, slot].set(v_new[:, 0].astype(cache["v"].dtype))
    k = ws(k, "kv_bshd")
    v = ws(v, "kv_bshd")
    idx = jnp.arange(s_kv)
    if dims.window is not None:
        valid = jnp.where(
            (pos_b + 1 >= s_kv)[:, None],
            jnp.ones((b, s_kv), bool),
            idx[None, :] <= pos_b[:, None],
        )
    else:
        valid = idx[None, :] <= pos_b[:, None]
    y = _decode_attend(params, x, q, k, v, valid, dims, imc, rng, site_prefix)
    return y, {"k": k, "v": v}
