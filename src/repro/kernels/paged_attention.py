"""Pallas paged-attention decode kernel: online softmax over the block table.

One decode step of attention for a batch of slots whose K/V live in a shared
paged block pool (``repro.models.attention.init_paged_kv_cache`` layout:
pools (num_blocks, block_size, Hkv, hd), per-slot block table (B, max_blocks),
physical block 0 reserved as the GARBAGE block).  Instead of materializing the
gathered ``pool[bt]`` copy (O(B * max_blocks * block) KV bytes per step) and
running a dense softmax over it, the kernel walks the block table in-kernel:
each grid step streams ONE physical block from the pool and folds it into an
online-softmax accumulator, so the gathered copy never exists and the resident
KV working set per step is O(1) in the context length.

Grid / accumulator layout (TPU mapping notes, in the style of
``imc_mvm.py``):

  * grid = (B, max_blocks) with the logical-block axis j innermost: each
    (b, j) step DMAs pool block ``bt[b, j]`` - the physical block id comes
    from the scalar-prefetched block table via the BlockSpec index_map
    (``pltpu.PrefetchScalarGridSpec``), the canonical paged-attention idiom.
    The walk order is LOGICAL block order, so the output is invariant to the
    physical block ids the allocator happened to hand out (preemption/resume
    and defragmentation cannot perturb tokens).
  * VMEM scratch carries the online-softmax state across the j steps of one
    slot: running row-max ``m`` (Hkv, G), row-sum ``l`` (Hkv, G), weighted
    accumulator ``acc`` (Hkv, G, hd) - the same m/l/corr recurrence as
    ``_flash_fwd_impl`` (models/attention.py).  State is (re)initialized at
    j == 0 and the normalized context ``acc / max(l, 1e-30)`` is flushed to
    the output block at j == max_blocks - 1 (the output BlockSpec revisits
    the same (1, Hkv, G, hd) block for every j, so only the final flush
    survives).
  * the new token's K/V is scattered into the tail block INSIDE the kernel:
    the tail (b, j == pos[b] // bs) step overlays k_new/v_new onto row
    ``pos[b] % bs`` of the streamed block in-register, and the pools are
    aliased in-out (``input_output_aliases``) so each step writes its
    (possibly overlaid) block back to a scalar-prefetched write destination.

Garbage-block-0 write contract: the per-step write destination ``wdest[b, j]``
is the slot's physical tail block ONLY for the tail step of an active,
in-range row; every other step - non-tail j, rows with ``active == False``
(a retired slot's stale table may point at blocks the allocator already
reused), and OVERRUN rows (``pos >= max_blocks * bs``, which previously
clobbered the slot's last live block) - is routed to physical block 0, whose
content is garbage by pool contract.  ``write_routing`` below is the single
source of truth for this routing; the gather escape-hatch path in
``models/attention.py`` and the ``ref.py`` oracle share it.

CPU / interpret story (the ``kernels/prng.py`` precedent): on non-TPU
backends ``paged_attention_decode`` dispatches to a pure-JAX fallback
(`lax.scan` over logical blocks) implementing the identical streamed
recurrence - bit-reproducible math, no Pallas interpreter overhead inside the
serve decode scan.  The Pallas kernel itself runs under ``interpret=True``
only in the dedicated equivalence tests (tests/test_paged_attention.py),
which check it against the fallback and against the gather-path oracle
``ref.paged_attention_ref``.  On real TPU the aliased in-out pool revisits
physical block 0 from multiple grid steps; the only step whose write targets
a block read by a LATER step is the tail step of the owning slot itself
(slots own disjoint blocks), which reads and writes within the same step, so
the sequential grid semantics are preserved.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# shared write routing (kernel, fallback, gather path and oracle all use this)
# ---------------------------------------------------------------------------


def write_routing(bt: jax.Array, pos_b: jax.Array, block_size: int,
                  active: Optional[jax.Array]
                  ) -> Tuple[jax.Array, jax.Array]:
    """(dest, off): physical block and in-block row for each slot's new K/V.

    ``dest`` follows the garbage-block-0 contract (module docstring): the
    slot's tail block for active in-range rows, block 0 for inactive or
    overrun rows.
    """
    b, max_blocks = bt.shape
    rows = jnp.arange(b)
    tail = pos_b // block_size
    dest = bt[rows, jnp.clip(tail, 0, max_blocks - 1)]
    dest = jnp.where(tail >= max_blocks, 0, dest)
    if active is not None:
        dest = jnp.where(active, dest, 0)
    return dest, pos_b % block_size


# ---------------------------------------------------------------------------
# pure-JAX fallback: the same streamed recurrence, lax.scan over blocks
# ---------------------------------------------------------------------------


def _decode_jax(q, k_new, v_new, pk, pv, bt, pos_b, dest, off,
                scale: float, softcap: Optional[float]):
    """Streamed online-softmax walk over logical blocks (CPU serving path).

    Scatters the new K/V first (same pool state as the kernel's in-kernel
    overlay + aliased write-back), then folds one (B, bs, Hkv, hd) block per
    scan step into the m/l/acc recurrence.  The gathered ``pool[bt]`` copy is
    never materialized.
    """
    b, max_blocks = bt.shape
    bs = pk.shape[1]
    pk = pk.at[dest, off].set(k_new)
    pv = pv.at[dest, off].set(v_new)
    qf = q.astype(jnp.float32)
    hkv, g, hd = q.shape[1], q.shape[2], q.shape[3]

    def blk_step(carry, j):
        m, l, acc = carry
        phys = bt[:, j]
        k_blk = pk[phys].astype(jnp.float32)  # (B, bs, Hkv, hd)
        v_blk = pv[phys].astype(jnp.float32)
        s = jnp.einsum("bhgd,bkhd->bhgk", qf, k_blk) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = j * bs + jnp.arange(bs)
        valid = k_pos[None, :] <= pos_b[:, None]
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv_blk = jnp.einsum("bhgk,bkhd->bhgd", p, v_blk)
        acc_new = acc * corr[..., None] + pv_blk
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(blk_step, (m0, l0, a0),
                                  jnp.arange(max_blocks))
    ctx = acc / jnp.maximum(l[..., None], 1e-30)
    return ctx, pk, pv


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------


def _paged_kernel(bt_ref, wdest_ref, pos_ref, act_ref, q_ref, kn_ref, vn_ref,
                  pk_ref, pv_ref, ctx_ref, opk_ref, opv_ref,
                  m_scr, l_scr, acc_scr, *, bs: int, scale: float,
                  softcap: Optional[float]):
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_blocks = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[b]
    k_blk = pk_ref[0]  # (bs, Hkv, hd) - the streamed physical block
    v_blk = pv_ref[0]
    # in-register overlay of the new token onto the tail block's row -
    # gated on the write mask: an inactive row's write goes to garbage, so
    # its tail lane must keep attending the STALE pool value (gather-path
    # semantics; the row's output is discarded by the engine anyway)
    # (masks are built at full rank: Mosaic cannot reshape an i1 vector)
    row = jax.lax.broadcasted_iota(jnp.int32, k_blk.shape, 0)
    sel = (row == pos % bs) & (j == pos // bs) & (act_ref[b] != 0)
    k_blk = jnp.where(sel, kn_ref[0][None], k_blk)
    v_blk = jnp.where(sel, vn_ref[0][None], v_blk)
    # aliased write-back: the tail step persists the overlay into the slot's
    # tail block; every other step's destination is garbage block 0
    opk_ref[0] = k_blk
    opv_ref[0] = v_blk

    qf = q_ref[0].astype(jnp.float32)  # (Hkv, G, hd)
    s = jnp.einsum("hgd,khd->hgk", qf, k_blk.astype(jnp.float32)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(k_pos <= pos, s, NEG_INF)
    m = m_scr[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * corr[..., None] + jnp.einsum(
        "hgk,khd->hgd", p, v_blk.astype(jnp.float32))
    m_scr[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _flush():
        ctx_ref[0] = acc_scr[...] / jnp.maximum(l_scr[...][..., None], 1e-30)


def _decode_pallas(q, k_new, v_new, pk, pv, bt, pos_b, dest, off, act,
                   scale: float, softcap: Optional[float], interpret: bool):
    """pallas_call wrapper: scalar-prefetched block table + write routing."""
    b, max_blocks = bt.shape
    bs, hkv, hd = pk.shape[1], pk.shape[2], pk.shape[3]
    g = q.shape[2]
    # per-(b, j) write destination: garbage block 0 everywhere except the
    # (in-range, active) tail step, which gets the slot's real tail block
    wdest = jnp.zeros((b, max_blocks), jnp.int32).at[
        jnp.arange(b), jnp.clip(pos_b // bs, 0, max_blocks - 1)].set(dest)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # bt, wdest, pos, active
        grid=(b, max_blocks),
        in_specs=[
            pl.BlockSpec((1, hkv, g, hd),
                         lambda bb, jj, bt_, wd, ps, ac: (bb, 0, 0, 0)),
            pl.BlockSpec((1, hkv, hd),
                         lambda bb, jj, bt_, wd, ps, ac: (bb, 0, 0)),
            pl.BlockSpec((1, hkv, hd),
                         lambda bb, jj, bt_, wd, ps, ac: (bb, 0, 0)),
            pl.BlockSpec((1, bs, hkv, hd),
                         lambda bb, jj, bt_, wd, ps, ac: (bt_[bb, jj], 0, 0, 0)),
            pl.BlockSpec((1, bs, hkv, hd),
                         lambda bb, jj, bt_, wd, ps, ac: (bt_[bb, jj], 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hkv, g, hd),
                         lambda bb, jj, bt_, wd, ps, ac: (bb, 0, 0, 0)),
            pl.BlockSpec((1, bs, hkv, hd),
                         lambda bb, jj, bt_, wd, ps, ac: (wd[bb, jj], 0, 0, 0)),
            pl.BlockSpec((1, bs, hkv, hd),
                         lambda bb, jj, bt_, wd, ps, ac: (wd[bb, jj], 0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((hkv, g), jnp.float32),
            pltpu.VMEM((hkv, g), jnp.float32),
            pltpu.VMEM((hkv, g, hd), jnp.float32),
        ],
    )
    ctx, opk, opv = pl.pallas_call(
        functools.partial(_paged_kernel, bs=bs, scale=scale, softcap=softcap),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, g, hd), jnp.float32),
            jax.ShapeDtypeStruct(pk.shape, pk.dtype),
            jax.ShapeDtypeStruct(pv.shape, pv.dtype),
        ],
        # operand indices count the 4 scalar-prefetch args: pk = 7, pv = 8
        input_output_aliases={7: 1, 8: 2},
        interpret=interpret,
        name="paged_attention",
    )(bt, wdest, pos_b, act, q, k_new, v_new, pk, pv)
    return ctx, opk, opv


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


@jax.named_scope("paged_attention")
def paged_attention_decode(
    q: jax.Array,       # (B, Hkv, G, hd) grouped queries (rope applied)
    k_new: jax.Array,   # (B, Hkv, hd) new token K (any float dtype)
    v_new: jax.Array,   # (B, Hkv, hd) new token V
    pk: jax.Array,      # (num_blocks, bs, Hkv, hd) key pool
    pv: jax.Array,      # (num_blocks, bs, Hkv, hd) value pool
    bt: jax.Array,      # (B, max_blocks) int32 block table
    pos_b: jax.Array,   # (B,) int32: tokens already in the cache per slot
    active: Optional[jax.Array] = None,  # (B,) bool write-permission mask
    *,
    scale: float,
    softcap: Optional[float] = None,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
):
    """Fused scatter + block-table walk + online-softmax decode attention.

    Returns ``(ctx (B, Hkv, G, hd) f32, pk, pv)`` with the new token's K/V
    scattered into the pools per the garbage-block-0 contract.  Dispatch
    mirrors ``kernels/prng.py``: the Pallas kernel on TPU, the pure-JAX
    streamed fallback (identical math) elsewhere; ``use_pallas``/``interpret``
    force either path for the interpret-mode equivalence tests.  Both paths
    run under the ``paged_attention`` named scope, which a profile's op
    names carry.
    """
    pos_b = pos_b.astype(jnp.int32)
    # cast ONCE to the pool dtype before both the scatter and the overlay so
    # the kernel attends over exactly the value the pool ends up holding
    # (bit-compat with the gather path, which scatters then re-reads)
    k_new = k_new.astype(pk.dtype)
    v_new = v_new.astype(pv.dtype)
    dest, off = write_routing(bt, pos_b, pk.shape[1], active)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        return _decode_jax(q, k_new, v_new, pk, pv, bt, pos_b, dest, off,
                           scale, softcap)
    if interpret is None:
        interpret = _interpret_default()
    act = (jnp.ones(pos_b.shape, jnp.int32) if active is None
           else active.astype(jnp.int32))
    return _decode_pallas(q, k_new, v_new, pk, pv, bt, pos_b, dest, off, act,
                          scale, softcap, interpret)
