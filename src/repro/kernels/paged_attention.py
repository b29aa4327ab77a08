"""Pallas paged-attention decode kernel: online softmax over a slot's live blocks.

One decode step of attention for a batch of slots whose K/V live in a shared
paged block pool (``repro.models.attention.init_paged_kv_cache`` layout:
pools (num_blocks, block_size, Hkv, hd), per-slot block table (B, max_blocks),
physical block 0 reserved as the GARBAGE block).  The kernel walks each
slot's block table in-kernel and folds the blocks into an online-softmax
accumulator, so the gathered ``pool[bt]`` copy never exists.

The walk (TPU mapping notes, in the style of ``imc_mvm.py``):

  * grid = (B,): one grid step per slot.  Inside it, a ``fori_loop`` runs
    over the slot's COMPUTE blocks of ``n_fold`` pool blocks each
    (``n_fold * bs`` positions).  Only LIVE blocks are walked: a slot's live
    count is ``min(pos // bs + 1, max_blocks)``, derived in-kernel from the
    scalar-prefetched ``pos``.  An inactive row walks its stale table the
    same way, as the gather path does: a dynamic imc substrate couples the
    rows of a batch through its statistics, so every row's context must be
    the one the reference semantics give.  ``n_fold`` comes from the shapes
    alone (``fold_blocks``: the pool block's VMEM bytes against
    ``FOLD_VMEM_BYTES``, capped by ``max_blocks``).
  * the pools stay where XLA puts them (``memory_space=pl.ANY``: HBM, or
    VMEM when a layer's pool fits there).  A compute block is one DMA per
    live pool block per pool, issued by hand in a scalar loop from the
    scalar-prefetched (flattened) block table into one of two VMEM buffers:
    the next compute block - of this slot, or the first of the next slot -
    is fetched while this one is computed.  The loop keeps the kernel's
    code, and the time to lower it into every program that holds it,
    independent of ``n_fold``.  Blocks past the live count are never
    fetched: the spare slots of a short last compute block keep whatever
    the buffer held, and their positions are masked out of both the scores
    and the values.  The walk order is LOGICAL block order, so the output
    is invariant to the physical block ids the allocator handed out.
  * the products keep the pool's (Hkv, hd) tile as it lands in VMEM: no
    per-head MXU product of one query row and no transpose.  Scores are a
    lane reduction of ``k * q`` per position and head, (P, Hkv, 1); the
    context is ``sum_P p * v`` over the leading axis.  All of it runs on the
    VPU in float32, one product per query group ``G``.
  * VMEM scratch carries the online-softmax state across the compute blocks
    of one slot: running max ``m`` (G, Hkv, 1), sum ``l`` (G, Hkv, 1) and
    accumulator ``acc`` (G, Hkv, hd) - the same m/l/corr recurrence as
    ``_flash_fwd_impl`` (models/attention.py).  The normalized context
    ``acc / max(l, 1e-30)`` is written once per slot.

The new token's K/V is written ONCE per slot: one (Hkv, hd) DMA per pool from
the token's VMEM block to row ``pos % bs`` of the scalar-prefetched write
destination.  An active, in-range slot attends it from an overlay: once the
tail compute block has landed in VMEM, the new row is stored over its stale
copy there.  The pools are aliased in-out (``input_output_aliases``), so
nothing else of either pool is written.

Garbage-block-0 write contract: the write destination ``dest[b]`` is the
slot's physical tail block ONLY for an active, in-range row; rows with
``active == False`` (a retired slot's stale table may point at blocks the
allocator already reused) and OVERRUN rows (``pos >= max_blocks * bs``)
write to physical block 0, whose content is garbage by pool contract, and
take no overlay: an inactive row attends the STALE tail value, as the gather
path does.  ``write_routing`` below is the single source of truth for this
routing; the gather escape-hatch path in ``models/attention.py`` and the
``ref.py`` oracle share it.

CPU / interpret story (the ``kernels/prng.py`` precedent): on non-TPU
backends ``paged_attention_decode`` dispatches to a pure-JAX fallback
(``lax.scan`` over compute blocks) implementing the identical recurrence -
the reference semantics, with no Pallas interpreter overhead inside the serve
decode scan.  The Pallas kernel itself runs under ``interpret=True`` in the
equivalence tests (tests/test_paged_attention.py), which check it against
the fallback and against the gather-path oracle ``ref.paged_attention_ref``;
the same file checks the compiled kernel against the oracle on a TPU.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# VMEM bytes of one compute block of one pool (one of its two buffers)
FOLD_VMEM_BYTES = 512 * 1024


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def fold_blocks(bs: int, hkv: int, hd: int, itemsize: int,
                max_blocks: int) -> int:
    """Pool blocks per compute block: the largest power of two whose DMA
    buffer (``bs`` slabs of (Hkv, hd), padded to 8 sublanes and 128 lanes)
    stays within ``FOLD_VMEM_BYTES``, and no more than the table needs."""
    tile = -(-hkv // 8) * 8 * (-(-hd // 128) * 128)
    n = max(1, FOLD_VMEM_BYTES // (bs * tile * itemsize))
    n = 1 << (n.bit_length() - 1)
    return min(n, 1 << (max_blocks - 1).bit_length())


def _lane_window(hd: int, interpret: bool) -> int:
    """Lanes each pool DMA moves: ``hd`` rounded up to whole 128-lane tiles
    on the chip.  A pool whose head dim is under 128 (musicgen's 64) lies in
    memory with each (Hkv, hd) slab padded to 128 lanes, and Mosaic slices only
    whole lane tiles, so a DMA moves the padded tile; the compute reads the
    first ``hd`` lanes.  The interpreter holds the logical array."""
    return hd if interpret else -(-hd // 128) * 128


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
# pure-JAX fallback: the same recurrence, lax.scan over compute blocks
# ---------------------------------------------------------------------------


def _decode_jax(q, k_new, v_new, pk, pv, bt, pos_b, dest, off,
                scale: float, softcap: Optional[float], n_fold: int):
    """Online-softmax walk over compute blocks of ``n_fold`` logical blocks
    (CPU serving path).

    Scatters the new K/V first (same pool state as the kernel's single
    write), then folds one (B, n_fold * bs, Hkv, hd) block per scan step
    into the m/l/acc recurrence, with the kernel's products and masks.  The
    gathered ``pool[bt]`` copy is never materialized.  Every row walks its
    table through its tail block: an inactive row attends its stale table,
    as the gather path and the kernel do.
    """
    b, max_blocks = bt.shape
    bs = pk.shape[1]
    pk = pk.at[dest, off].set(k_new)
    pv = pv.at[dest, off].set(v_new)
    hkv, g, hd = q.shape[1], q.shape[2], q.shape[3]
    qf = q.astype(jnp.float32).transpose(0, 2, 1, 3)  # (B, G, Hkv, hd)
    n_cb = -(-max_blocks // n_fold)
    bt_pad = jnp.pad(bt, ((0, 0), (0, n_cb * n_fold - max_blocks)))
    p_len = n_fold * bs

    def blk_step(carry, c):
        m, l, acc = carry
        phys = jax.lax.dynamic_slice_in_dim(bt_pad, c * n_fold, n_fold, 1)
        k_blk = pk[phys].astype(jnp.float32).reshape(b, p_len, hkv, hd)
        v_blk = pv[phys].astype(jnp.float32).reshape(b, p_len, hkv, hd)
        s = jnp.sum(k_blk[:, None] * qf[:, :, None], axis=-1,
                    keepdims=True) * scale  # (B, G, P, Hkv, 1)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = c * p_len + jnp.arange(p_len)
        valid = ((k_pos[None, :] <= pos_b[:, None])
                 & (k_pos[None, :] < max_blocks * bs))
        s = jnp.where(valid[:, None, :, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=2))
        p = jnp.exp(s - m_new[:, :, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=2)
        acc_new = acc * corr + jnp.sum(p * v_blk[:, None], axis=2)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, g, hkv, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, g, hkv, 1), jnp.float32)
    a0 = jnp.zeros((b, g, hkv, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(blk_step, (m0, l0, a0), jnp.arange(n_cb))
    ctx = acc / jnp.maximum(l, 1e-30)
    return ctx.transpose(0, 2, 1, 3), pk, pv


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------


def _paged_kernel(bt_ref, dest_ref, pos_ref, act_ref, q_ref, kn_ref, vn_ref,
                  pk_hbm, pv_hbm, ctx_ref, opk_hbm, opv_hbm,
                  kbuf, vbuf, sems, wsems, next_buf, m_scr, l_scr, acc_scr,
                  *, n_fold: int, bs: int, max_blocks: int, scale: float,
                  softcap: Optional[float]):
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    p_len = n_fold * bs
    g_count, hkv, hd = q_ref.shape[1:]
    # every DMA moves whole lane tiles (``_lane_window``)
    lanes = pl.ds(0, kbuf.shape[-1])

    def live(row):
        """Blocks ``row`` walks: through its tail block, within the table
        (at least one: a row's position 0 lies in its first block)."""
        return jnp.minimum(pos_ref[row] // bs + 1, max_blocks)

    def count(row, c):
        """Pool blocks in compute block ``c`` of ``row``: ``n_fold``, fewer
        in a short last one."""
        return jnp.minimum(n_fold, live(row) - c * n_fold)

    def copies(blk, i, buf):
        """Pool block ``blk`` into slot ``i`` of buffer ``buf``, K and V."""
        return (pltpu.make_async_copy(pk_hbm.at[blk, :, :, lanes],
                                      kbuf.at[buf, i], sems.at[0, buf]),
                pltpu.make_async_copy(pv_hbm.at[blk, :, :, lanes],
                                      vbuf.at[buf, i], sems.at[1, buf]))

    def start(row, c, buf):
        """Fetch the live blocks of compute block ``c`` of ``row`` into
        buffer ``buf``."""
        first = row * max_blocks + c * n_fold

        def issue(i, carry):
            for cp in copies(bt_ref[first + i], i, buf):
                cp.start()
            return carry

        jax.lax.fori_loop(0, count(row, c), issue, 0)

    def wait(row, c, buf):
        def one(i, carry):
            for cp in copies(0, i, buf):  # a wait reads only the size
                cp.wait()
            return carry

        jax.lax.fori_loop(0, count(row, c), one, 0)

    pos = pos_ref[b]
    n_live = live(b)
    n_cb = (n_live + n_fold - 1) // n_fold

    # every row walks at least one block, so the previous row has fetched
    # this row's first compute block into ``next_buf``
    @pl.when(b == 0)
    def _first_row():
        next_buf[0] = 0
        start(b, 0, 0)

    buf0 = next_buf[0]

    # the single write of the new token (dest is garbage block 0 unless the
    # row is active and in range)
    off = pos % bs
    writes = (
        pltpu.make_async_copy(kn_ref.at[0],
                              opk_hbm.at[dest_ref[b], off, :, lanes],
                              wsems.at[0]),
        pltpu.make_async_copy(vn_ref.at[0],
                              opv_hbm.at[dest_ref[b], off, :, lanes],
                              wsems.at[1]),
    )
    for cp in writes:
        cp.start()

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    tail = pos // bs
    # only a row whose write lands in its tail block attends the new token
    tail_c = jnp.where((tail < max_blocks) & (act_ref[b] != 0),
                       tail // n_fold, -1)
    nxt_row = jnp.minimum(b + 1, n_rows - 1)

    def body(c, buf):
        other = 1 - buf

        @pl.when(c + 1 < n_cb)
        def _prefetch_same_row():
            start(b, c + 1, other)

        @pl.when((c + 1 == n_cb) & (b + 1 < n_rows))
        def _prefetch_next_row():
            start(nxt_row, 0, other)

        wait(b, c, buf)

        @pl.when(c == tail_c)
        def _overlay():
            kbuf[buf, tail % n_fold, off] = kn_ref[0]
            vbuf[buf, tail % n_fold, off] = vn_ref[0]

        k = kbuf[buf, :, :, :, :hd].astype(jnp.float32).reshape(
            p_len, hkv, hd)
        v = vbuf[buf, :, :, :, :hd].astype(jnp.float32).reshape(
            p_len, hkv, hd)
        k_pos = c * p_len + jax.lax.broadcasted_iota(
            jnp.int32, (p_len, hkv, 1), 0)
        valid = (k_pos <= pos) & (k_pos < max_blocks * bs)
        # the spare slots of a short last compute block hold stale VMEM
        # (the mask is built at full rank: Mosaic cannot broadcast an i1
        # vector across lanes)
        v_pos = c * p_len + jax.lax.broadcasted_iota(
            jnp.int32, (p_len, hkv, hd), 0)
        v = jnp.where(v_pos < n_live * bs, v, 0.0)
        for g in range(g_count):
            qg = q_ref[0, g].astype(jnp.float32)  # (Hkv, hd)
            s = jnp.sum(k * qg[None], axis=-1, keepdims=True) * scale
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(valid, s, NEG_INF)  # (P, Hkv, 1)
            m = m_scr[g]
            m_new = jnp.maximum(m, jnp.max(s, axis=0))
            p = jnp.exp(s - m_new[None])
            corr = jnp.exp(m - m_new)
            l_scr[g] = l_scr[g] * corr + jnp.sum(p, axis=0)
            acc_scr[g] = acc_scr[g] * corr + jnp.sum(p * v, axis=0)
            m_scr[g] = m_new
        return other

    next_buf[0] = jax.lax.fori_loop(0, n_cb, body, buf0)
    ctx_ref[0] = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
    for cp in writes:
        cp.wait()


def _decode_pallas(q, k_new, v_new, pk, pv, bt, pos_b, act, dest,
                   scale: float, softcap: Optional[float], n_fold: int,
                   interpret: bool):
    """pallas_call wrapper: scalar-prefetched block table, write
    destination, positions and activity."""
    b, max_blocks = bt.shape
    bs, hkv, hd = pk.shape[1], pk.shape[2], pk.shape[3]
    g = q.shape[2]
    lanes = _lane_window(hd, interpret)
    qg = q.transpose(0, 2, 1, 3)  # (B, G, Hkv, hd): one (Hkv, hd) tile a group
    pad = ((0, 0), (0, 0), (0, lanes - hd))
    k_new, v_new = jnp.pad(k_new, pad), jnp.pad(v_new, pad)
    row = lambda bb, *_: (bb, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # bt (flattened), dest, pos, active
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, g, hkv, hd), lambda bb, *_: (bb, 0, 0, 0)),
            pl.BlockSpec((1, hkv, lanes), row),
            pl.BlockSpec((1, hkv, lanes), row),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, g, hkv, hd), lambda bb, *_: (bb, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, n_fold, bs, hkv, lanes), pk.dtype),
            pltpu.VMEM((2, n_fold, bs, hkv, lanes), pv.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((g, hkv, 1), jnp.float32),
            pltpu.VMEM((g, hkv, 1), jnp.float32),
            pltpu.VMEM((g, hkv, hd), jnp.float32),
        ],
    )
    ctx, opk, opv = pl.pallas_call(
        functools.partial(_paged_kernel, n_fold=n_fold, bs=bs,
                          max_blocks=max_blocks, scale=scale,
                          softcap=softcap),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, g, hkv, hd), jnp.float32),
            jax.ShapeDtypeStruct(pk.shape, pk.dtype),
            jax.ShapeDtypeStruct(pv.shape, pv.dtype),
        ],
        # operand indices count the 4 scalar-prefetch args: pk = 7, pv = 8
        input_output_aliases={7: 1, 8: 2},
        # the walk carries its DMA buffers from one row to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(bt.reshape(-1), dest, pos_b, act, qg, k_new, v_new, pk, pv)
    return ctx.transpose(0, 2, 1, 3), opk, opv


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
    """Fused single write + live-block walk + online-softmax decode attention.

    Returns ``(ctx (B, Hkv, G, hd) f32, pk, pv)`` with the new token's K/V
    written into the pools per the garbage-block-0 contract; an inactive
    row attends its stale table (the engine discards its context).
    Dispatch mirrors ``kernels/prng.py``: the Pallas kernel on TPU, the
    pure-JAX fallback (identical math) elsewhere; ``use_pallas``/``interpret``
    force either path for the equivalence tests.  Both walk compute blocks
    of ``fold_blocks`` pool blocks, from the shapes.  Both paths run under
    the ``paged_attention`` named scope, which a profile's op names carry.
    """
    pos_b = pos_b.astype(jnp.int32)
    # cast ONCE to the pool dtype before both the write and the overlay so
    # the kernel attends over exactly the value the pool ends up holding
    # (bit-compat with the gather path, which scatters then re-reads)
    k_new = k_new.astype(pk.dtype)
    v_new = v_new.astype(pv.dtype)
    bs, hkv, hd = pk.shape[1], pk.shape[2], pk.shape[3]
    n_fold = fold_blocks(bs, hkv, hd, pk.dtype.itemsize, bt.shape[1])
    dest, off = write_routing(bt, pos_b, bs, active)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        return _decode_jax(q, k_new, v_new, pk, pv, bt, pos_b, dest, off,
                           scale, softcap, n_fold)
    if interpret is None:
        interpret = _interpret_default()
    act = (jnp.ones(pos_b.shape, jnp.int32) if active is None
           else active.astype(jnp.int32))
    return _decode_pallas(q, k_new, v_new, pk, pv, bt, pos_b, act, dest,
                          scale, softcap, n_fold, interpret)
