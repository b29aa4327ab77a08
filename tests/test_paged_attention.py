"""Paged-attention decode kernel equivalence suite.

Unit level: the live-block online-softmax walk (pure-JAX fallback AND the
Pallas kernel under interpret mode) against the gather-path oracle
``ref.paged_attention_ref`` - per-step ctx allclose, pool updates bit-exact
outside the garbage block, the garbage-block-0 write-routing contract
(inactive rows, OVERRUN rows), block-boundary crossing, and, for several
compute-block folds ``n_fold`` (set through ``FOLD_VMEM_BYTES``), positions at
compute-block edges, live counts of 1 and of ``max_blocks``, and slots of very
different lengths in one batch.  Every path gives an inactive row the context
of its stale table.  On a TPU the compiled kernel is checked against the
oracle at both served geometries.

Serve level: greedy token streams from the fused-kernel engine
(``decode_attn="kernel"``, the default) are bit-identical to the gather
escape hatch (``decode_attn="gather"``) across digital / imc_analytic /
imc_bitserial under FROZEN calibration on the committed mixed 4..48-token
workload - including multi-block slots and recompute-preemption/resume under
a tight physical pool.

Plus the two satellite pins that ride along this PR: the
``attention_forward`` window >= S dispatch equivalence and the
``slo_summary`` zero-elapsed goodput guard.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.imc_linear import IMCConfig
from repro.core.substrate import as_substrate, calibrate_model
from repro.kernels import paged_attention as pa
from repro.kernels.paged_attention import (fold_blocks, paged_attention_decode,
                                          write_routing)
from repro.kernels.ref import paged_attention_ref
from repro.launch.serve import Engine, Request, serve
from repro.models import init_params
from repro.models.attention import AttnDims, attention_forward, init_attention

SCALE = 0.25

# the committed serve-bench mixed short/long workload (serve_bench.MIXED_LENS)
MIXED_LENS = [4, 6, 48, 5, 8, 44, 6, 7]


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------


def _paged_state(seed=0, b=4, mb=6, bs=8, nb=24, hkv=2, g=2, hd=16,
                 pos=(3, 11, 29, 47)):
    """Random pools + a disjoint block table (block 0 = garbage)."""
    rng = np.random.default_rng(seed)
    f32 = jnp.float32
    q = jnp.asarray(rng.normal(size=(b, hkv, g, hd)), f32)
    kn = jnp.asarray(rng.normal(size=(b, hkv, hd)), f32)
    vn = jnp.asarray(rng.normal(size=(b, hkv, hd)), f32)
    pk = jnp.asarray(rng.normal(size=(nb, bs, hkv, hd)), f32)
    pv = jnp.asarray(rng.normal(size=(nb, bs, hkv, hd)), f32)
    bt = np.zeros((b, mb), np.int32)
    ids = iter(range(1, nb))
    for row, p in enumerate(pos):
        for j in range(min(p // bs + 1, mb)):
            bt[row, j] = next(ids)
    return q, kn, vn, pk, pv, jnp.asarray(bt), jnp.asarray(pos, jnp.int32)


def _all_paths(state, active=None, softcap=None):
    q, kn, vn, pk, pv, bt, pos_b = state
    ref = paged_attention_ref(q, kn, vn, pk, pv, bt, pos_b, active,
                              scale=SCALE, softcap=softcap)
    fb = paged_attention_decode(q, kn, vn, pk, pv, bt, pos_b, active,
                                scale=SCALE, softcap=softcap,
                                use_pallas=False)
    pal = paged_attention_decode(q, kn, vn, pk, pv, bt, pos_b, active,
                                 scale=SCALE, softcap=softcap,
                                 use_pallas=True, interpret=True)
    return ref, fb, pal


# ---------------------------------------------------------------------------
# kernel vs gather-path oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("softcap", [None, 30.0])
def test_kernel_matches_gather_oracle(softcap):
    """Fallback and Pallas-interpret kernel vs the full-softmax gather
    oracle: ctx to tight allclose (online vs full softmax round differently
    in the last ulps), pools bit-exact outside garbage block 0."""
    state = _paged_state()
    active = jnp.asarray([True, True, False, True])
    (ctx_r, pk_r, pv_r), (ctx_f, pk_f, pv_f), (ctx_p, pk_p, pv_p) = \
        _all_paths(state, active, softcap)
    np.testing.assert_allclose(ctx_f, ctx_r, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(ctx_p, ctx_r, atol=1e-5, rtol=1e-5)
    # the walk is the same math in both implementations
    np.testing.assert_allclose(ctx_p, ctx_f, atol=1e-6, rtol=1e-6)
    for got_k, got_v in ((pk_f, pv_f), (pk_p, pv_p)):
        assert jnp.array_equal(got_k[1:], pk_r[1:])
        assert jnp.array_equal(got_v[1:], pv_r[1:])


def test_kernel_matches_oracle_per_step_across_block_boundary():
    """Walk a slot's position across a block boundary one token at a time
    (bs-2 .. bs+2): the kernel must match the oracle at EVERY step, with the
    pool state threaded through (tail block fills up, then a fresh block)."""
    bs, mb, nb, hkv, g, hd = 4, 4, 10, 2, 1, 8
    rng = np.random.default_rng(3)
    f32 = jnp.float32
    pk = jnp.asarray(rng.normal(size=(nb, bs, hkv, hd)), f32)
    pv = jnp.asarray(rng.normal(size=(nb, bs, hkv, hd)), f32)
    bt = jnp.asarray([[1, 2, 3, 0]], jnp.int32)
    pk_k, pv_k = pk, pv  # kernel-path pool state
    pk_o, pv_o = pk, pv  # oracle-path pool state
    for pos in range(bs - 2, bs + 3):
        q = jnp.asarray(rng.normal(size=(1, hkv, g, hd)), f32)
        kn = jnp.asarray(rng.normal(size=(1, hkv, hd)), f32)
        vn = jnp.asarray(rng.normal(size=(1, hkv, hd)), f32)
        pos_b = jnp.asarray([pos], jnp.int32)
        ctx_o, pk_o, pv_o = paged_attention_ref(
            q, kn, vn, pk_o, pv_o, bt, pos_b, None, scale=SCALE)
        ctx_k, pk_k, pv_k = paged_attention_decode(
            q, kn, vn, pk_k, pv_k, bt, pos_b, None, scale=SCALE,
            use_pallas=True, interpret=True)
        np.testing.assert_allclose(ctx_k, ctx_o, atol=1e-5, rtol=1e-5)
        assert jnp.array_equal(pk_k[1:], pk_o[1:]), pos
        assert jnp.array_equal(pv_k[1:], pv_o[1:]), pos


# ---------------------------------------------------------------------------
# the live-block walk at several compute-block folds
# ---------------------------------------------------------------------------

FOLDS = [1, 2, 4, 8]


def _walk_state(n_fold, pos, active, bs=4, hkv=2, g=2, hd=8, seed=9):
    """A batch whose every row owns a full table of distinct blocks (stale
    entries past ``pos`` included), ``max_blocks = 2 * n_fold + 3``."""
    rng = np.random.default_rng(seed)
    b, mb = len(pos), 2 * n_fold + 3
    nb = b * mb + 1
    f32 = jnp.float32
    q = jnp.asarray(rng.normal(size=(b, hkv, g, hd)), f32)
    kn = jnp.asarray(rng.normal(size=(b, hkv, hd)), f32)
    vn = jnp.asarray(rng.normal(size=(b, hkv, hd)), f32)
    pk = jnp.asarray(rng.normal(size=(nb, bs, hkv, hd)), f32)
    pv = jnp.asarray(rng.normal(size=(nb, bs, hkv, hd)), f32)
    bt = jnp.asarray(1 + rng.permutation(nb - 1).reshape(b, mb), jnp.int32)
    return (q, kn, vn, pk, pv, bt, jnp.asarray(pos, jnp.int32),
            jnp.asarray(active))


def _check_walk(monkeypatch, n_fold, pos, active):
    q, kn, vn, pk, pv, bt, pos_b, act = _walk_state(n_fold, pos, active)
    # one pool block of the state takes 16 KiB of VMEM (4 rows of an
    # (8, 128) float32 tile), so this budget folds ``n_fold`` of them
    monkeypatch.setattr(pa, "FOLD_VMEM_BYTES", n_fold * 16 * 1024)
    assert fold_blocks(4, 2, 8, 4, bt.shape[1]) == n_fold
    ctx_r, pk_r, pv_r = paged_attention_ref(q, kn, vn, pk, pv, bt, pos_b,
                                            act, scale=SCALE)
    outs = [paged_attention_decode(q, kn, vn, pk, pv, bt, pos_b, act,
                                   scale=SCALE, use_pallas=pallas,
                                   interpret=True)
            for pallas in (False, True)]
    (ctx_f, _, _), (ctx_p, _, _) = outs
    np.testing.assert_allclose(ctx_f, ctx_r, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(ctx_p, ctx_r, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(ctx_p, ctx_f, atol=1e-6, rtol=1e-6)
    for _, pk2, pv2 in outs:
        assert jnp.array_equal(pk2[1:], pk_r[1:])
        assert jnp.array_equal(pv2[1:], pv_r[1:])


@pytest.mark.parametrize("n_fold", FOLDS)
def test_walk_at_compute_block_edges(monkeypatch, n_fold):
    """Positions one before, at and one after the end of the first and the
    second compute block (``k * n_fold * bs - 1``, ``k * n_fold * bs``,
    ``k * n_fold * bs + 1``): the tail lands in the last or the first block
    of a compute block, and the walk stops there."""
    edge = n_fold * 4
    pos = [edge - 1, edge, edge + 1, 2 * edge - 1, 2 * edge, 2 * edge + 1]
    _check_walk(monkeypatch, n_fold, pos, [True] * len(pos))


@pytest.mark.parametrize("n_fold", FOLDS)
def test_walk_mixed_batch(monkeypatch, n_fold):
    """One batch of very different rows: a live count of 1 (pos 0) and of
    ``max_blocks`` (the last position), a short and a long slot, an
    inactive row whose stale table runs past its ``pos``, an inactive row at
    ``pos`` 0, and an OVERRUN row (its write to garbage, its walk clamped
    to the table)."""
    cap = (2 * n_fold + 3) * 4  # max_blocks * bs
    pos = [0, cap - 1, 3, cap - 6, 5, 0, cap + 2]
    active = [True, True, True, True, False, False, True]
    _check_walk(monkeypatch, n_fold, pos, active)


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="the compiled kernel runs only on a TPU")
@pytest.mark.parametrize("hkv,g,hd", [(24, 1, 64), (8, 2, 128)],
                         ids=["musicgen", "internvl2"])
def test_compiled_kernel_matches_oracle_on_the_chip(hkv, g, hd):
    """The kernel as the chip runs it (``interpret=False``: whole-lane-tile
    DMAs over the pool as it lies in HBM, at the fold the shapes give)
    against the gather oracle, at both served geometries: a live count of 1
    and of ``max_blocks``, a short last compute block, an inactive row and
    an OVERRUN row.  Context to float32 tolerance, pools bit-exact outside
    garbage block 0."""
    rng = np.random.default_rng(11)
    bs, mb = 8, 40
    pos = [0, mb * bs - 1, 37, 150, 200, mb * bs + 3]
    active = [True, True, True, True, False, True]
    b, nb = len(pos), len(pos) * mb + 1
    bf = jnp.bfloat16
    q = jnp.asarray(rng.normal(size=(b, hkv, g, hd)), bf)
    kn = jnp.asarray(rng.normal(size=(b, hkv, hd)), bf)
    vn = jnp.asarray(rng.normal(size=(b, hkv, hd)), bf)
    pk = jnp.asarray(rng.normal(size=(nb, bs, hkv, hd)), bf)
    pv = jnp.asarray(rng.normal(size=(nb, bs, hkv, hd)), bf)
    bt = jnp.asarray(1 + rng.permutation(nb - 1).reshape(b, mb), jnp.int32)
    args = (q, kn, vn, pk, pv, bt, jnp.asarray(pos, jnp.int32),
            jnp.asarray(active))
    scale = hd ** -0.5
    with jax.default_matmul_precision("highest"):
        ctx_r, pk_r, pv_r = jax.jit(functools.partial(
            paged_attention_ref, scale=scale))(*args)
    ctx, pk2, pv2 = jax.jit(functools.partial(
        paged_attention_decode, scale=scale, use_pallas=True,
        interpret=False))(*args)
    np.testing.assert_allclose(np.asarray(ctx), np.asarray(ctx_r),
                               atol=1e-4, rtol=1e-4)
    assert jnp.array_equal(pk2[1:], pk_r[1:])
    assert jnp.array_equal(pv2[1:], pv_r[1:])


def test_fold_comes_from_the_shapes():
    """``n_fold`` is a power of two set by the block's VMEM footprint, never
    more than the table needs, at least one."""
    for bs, hkv, hd, mb in ((8, 24, 64, 250), (8, 8, 128, 288),
                            (4, 2, 16, 6), (8, 24, 64, 1)):
        n = fold_blocks(bs, hkv, hd, 2, mb)
        assert n >= 1 and n & (n - 1) == 0
        assert n < 2 * mb
    # a wider block folds fewer blocks a step
    assert fold_blocks(8, 24, 64, 2, 4096) < fold_blocks(8, 8, 128, 2, 4096)


# ---------------------------------------------------------------------------
# garbage-block-0 write routing (the tail-clobber bugfix)
# ---------------------------------------------------------------------------


def test_write_routing_contract():
    bt = jnp.asarray([[3, 4, 0], [5, 6, 7]], jnp.int32)
    # in-range: tail block; overrun (pos // bs >= max_blocks): garbage 0
    dest, off = write_routing(bt, jnp.asarray([9, 27], jnp.int32), 8, None)
    assert dest.tolist() == [4, 0]  # row 1 overran 3 blocks * 8
    assert off.tolist() == [1, 3]
    # inactive rows always route to garbage 0
    dest, _ = write_routing(bt, jnp.asarray([9, 9], jnp.int32), 8,
                            jnp.asarray([False, True]))
    assert dest.tolist() == [0, 6]


@pytest.mark.parametrize("path", ["gather", "fallback", "pallas"])
def test_overrun_write_does_not_clobber_tail_block(path):
    """The satellite bugfix pin: a position past the slot's capacity used to
    clip into the LAST logical block, overwriting a live token.  All three
    implementations must route the overrun write to garbage block 0 and
    leave every allocated block untouched."""
    bs, mb = 4, 3
    state = _paged_state(seed=5, b=2, mb=mb, bs=bs, nb=8, hkv=2, g=1, hd=8,
                         pos=(mb * bs, 5))  # row 0 exactly one past capacity
    q, kn, vn, pk, pv, bt, pos_b = state
    if path == "gather":
        _, pk2, pv2 = paged_attention_ref(q, kn, vn, pk, pv, bt, pos_b, None,
                                          scale=SCALE)
    else:
        _, pk2, pv2 = paged_attention_decode(
            q, kn, vn, pk, pv, bt, pos_b, None, scale=SCALE,
            use_pallas=path == "pallas", interpret=True)
    # row 0's allocated blocks (all of bt[0]) keep their pre-step contents
    for blk in np.asarray(bt[0]):
        if blk == 0:
            continue
        assert jnp.array_equal(pk2[blk], pk[blk]), blk
        assert jnp.array_equal(pv2[blk], pv[blk]), blk
    # row 1 (in range) still landed its write at its tail block
    tail = int(bt[1, pos_b[1] // bs])
    assert jnp.array_equal(pk2[tail, pos_b[1] % bs], kn[1].astype(pk.dtype))


def test_inactive_row_writes_garbage_and_attends_stale():
    """An inactive row's write must land in garbage block 0 on every path,
    leaving its stale tail in place, and every path must attend the STALE
    tail value (the new K/V never reached the row's tail block)."""
    state = _paged_state(seed=6, b=2, mb=3, bs=4, nb=8, hkv=2, g=1, hd=8,
                         pos=(5, 6))
    active = jnp.asarray([True, False])
    (ctx_r, pk_r, _), (ctx_f, pk_f, _), (ctx_p, pk_p, _) = \
        _all_paths(state, active)
    q, kn, vn, pk, pv, bt, pos_b = state
    tail1 = int(bt[1, pos_b[1] // 4])
    for got in (pk_r, pk_f, pk_p):
        assert jnp.array_equal(got[tail1], pk[tail1])  # stale tail kept
    # the oracle attends the stale value: an active row whose new K/V IS
    # the stale value reads the same context
    off1 = int(pos_b[1] % 4)
    kn_s, vn_s = kn.at[1].set(pk[tail1, off1]), vn.at[1].set(pv[tail1, off1])
    ctx_s, _, _ = paged_attention_ref(q, kn_s, vn_s, pk, pv, bt, pos_b, None,
                                      scale=SCALE)
    np.testing.assert_allclose(ctx_r[1], ctx_s[1], atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(ctx_f, ctx_r, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(ctx_p, ctx_r, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# serve level: kernel vs gather escape hatch, three substrates, frozen calib
# ---------------------------------------------------------------------------


def _frozen_cfg(substrate):
    base = configs.get_smoke("musicgen-medium")
    if substrate == "digital":
        return base, init_params(jax.random.PRNGKey(0), base)
    cfg_dyn = base.replace(
        imc=IMCConfig(mode=substrate, bx=7, bw=7, v_wl=0.7))
    params = init_params(jax.random.PRNGKey(0), cfg_dyn)
    ref_batch = np.random.default_rng(1).integers(
        0, base.vocab_size, (2, 24))
    cfg = calibrate_model(cfg_dyn, params, [ref_batch])
    assert as_substrate(cfg.imc).policy == "frozen"
    return cfg, params


def _serve_tokens(cfg, params, lens, max_new, kv_blocks=None, block=8):
    rnp = np.random.default_rng(11)
    reqs = [Request(rid=i, prompt=rnp.integers(0, cfg.vocab_size, l),
                    max_new=max_new)
            for i, l in enumerate(lens)]
    cache_len = 48 + max_new + 8
    engine = Engine(cfg, params, batch_slots=4, cache_len=cache_len,
                    max_chunk=4, block_size=block, kv_blocks=kv_blocks)
    done = serve(engine, reqs)
    assert all(r.error is None for r in done)
    return {r.rid: r.out for r in done}, engine


@pytest.mark.parametrize("substrate",
                         ["digital", "imc_analytic", "imc_bitserial"])
def test_serve_kernel_vs_gather_bit_identical(substrate):
    """The acceptance pin: on the committed mixed 4..48-token workload the
    fused-kernel engine emits bit-identical greedy token streams to the
    gather escape hatch on every substrate (frozen calibration: batch
    composition cannot leak in).  The 44/48-token prompts make multi-block
    slots (6 blocks of 8) and generation crosses block boundaries."""
    cfg, params = _frozen_cfg(substrate)
    lens = MIXED_LENS if substrate != "imc_bitserial" else MIXED_LENS[:4]
    max_new = 6 if substrate != "imc_bitserial" else 4
    out_k, _ = _serve_tokens(cfg.replace(decode_attn="kernel"), params,
                             lens, max_new)
    out_g, _ = _serve_tokens(cfg.replace(decode_attn="gather"), params,
                             lens, max_new)
    assert out_k == out_g, (substrate, out_k, out_g)


def test_serve_kernel_preemption_resume_bit_identical():
    """Recompute-preemption under a tight pool (lazy alloc) with the kernel
    enabled: the preempted-and-resumed run must reproduce the ample-pool
    kernel run AND the gather-path run token for token."""
    cfg, params = _frozen_cfg("imc_analytic")
    lens, max_new = [4, 6, 48, 5], 6
    cfg_k = cfg.replace(decode_attn="kernel")
    out_ample, _ = _serve_tokens(cfg_k, params, lens, max_new)
    out_tight, eng = _serve_tokens(cfg_k, params, lens, max_new, kv_blocks=12)
    assert eng.preempt_count >= 1, "tight pool never preempted"
    out_gather, _ = _serve_tokens(cfg.replace(decode_attn="gather"), params,
                                  lens, max_new, kv_blocks=12)
    assert out_tight == out_ample
    assert out_tight == out_gather


# ---------------------------------------------------------------------------
# satellite pins: window >= S dispatch, slo_summary zero-elapsed guard
# ---------------------------------------------------------------------------


def test_attention_forward_window_ge_seq_matches_no_window():
    """window >= S must take the flash path with the window mask DROPPED and
    reproduce the window=None result bit-exactly (a window covering every
    causal pair is a no-op) - the old dispatch kept the window in dims and
    silently relied on the flash mask being a causal no-op."""
    b, s, hq, hkv, hd = 2, 12, 4, 2, 8
    params = init_attention(jax.random.PRNGKey(2), 32, hq, hkv, hd,
                            jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (b, s, 32), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    base = dict(n_heads=hq, n_kv=hkv, head_dim=hd, scale=hd**-0.5,
                softcap_val=None, q_block=8, kv_block=8, rope_theta=1e4,
                use_rope=True)
    y_nowin = attention_forward(params, x, AttnDims(**base, window=None), positions)
    for window in (s, s + 5, 10**6):
        y_win = attention_forward(params, x, AttnDims(**base, window=window),
                                  positions)
        assert jnp.array_equal(y_win, y_nowin), window


def test_slo_summary_zero_elapsed():
    """elapsed == 0 (empty or instantly-drained workload) must not raise and
    must not fabricate a ~1e9x goodput: 0.0 when nothing met its SLO, NaN
    (undefined rate, like percentile() on empty input) when something did."""
    from repro.launch.metering import slo_summary

    s = slo_summary([], elapsed=0.0)
    assert s["goodput"] == 0.0 and s["goodput_tokens"] == 0.0
    assert s["requests"] == 0

    class _Req:
        preemptions = 0
        shed = False
        error = None
        ttft_deadline = None
        itl_deadline = None
        out = [1, 2, 3]
        token_times = []
        arrive_at = 0.0
        t_submit = 0.0
        t_first = 0.0

    s = slo_summary([_Req()], elapsed=0.0)
    assert s["slo_met"] == 1
    assert np.isnan(s["goodput"]) and np.isnan(s["goodput_tokens"])
    # sane elapsed still divides
    s = slo_summary([_Req()], elapsed=2.0)
    assert s["goodput"] == 0.5 and s["goodput_tokens"] == 1.5
