"""Substrate tests: data pipeline, checkpointing, optimizer, fault tolerance."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import manager as ckpt
from repro.data import DataConfig, MemmapCorpus, Prefetcher, SyntheticLM, host_slice
from repro.optim import AdamWConfig, adamw
from repro.runtime import FaultConfig, StepTimeout, TrainLoopRunner


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------


def test_synthetic_deterministic():
    cfg = DataConfig(seed=3, vocab_size=1000, seq_len=32, global_batch=4)
    s1, s2 = SyntheticLM(cfg), SyntheticLM(cfg)
    b1, b2 = s1.batch(17), s2.batch(17)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = s1.batch(18)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    assert b1["tokens"].min() >= 0 and b1["tokens"].max() < 1000


def test_host_slice_disjoint_cover():
    cfg = DataConfig(seed=0, vocab_size=100, seq_len=8, global_batch=8)
    b = SyntheticLM(cfg).batch(0)
    slices = [host_slice(b, i, 4)["tokens"] for i in range(4)]
    assert all(s.shape[0] == 2 for s in slices)
    np.testing.assert_array_equal(np.concatenate(slices), b["tokens"])


def test_memmap_corpus(tmp_path):
    data = np.arange(10_000, dtype=np.uint16) % 500
    path = str(tmp_path / "corpus.bin")
    data.tofile(path)
    cfg = DataConfig(seed=1, vocab_size=500, seq_len=64, global_batch=3,
                     corpus_path=path)
    src = MemmapCorpus(cfg)
    b = src.batch(5)
    assert b["tokens"].shape == (3, 64)
    np.testing.assert_array_equal(b["tokens"], src.batch(5)["tokens"])


def test_prefetcher():
    cfg = DataConfig(seed=0, vocab_size=50, seq_len=4, global_batch=2)
    pf = Prefetcher(SyntheticLM(cfg), start_step=10)
    steps = [next(pf)[0] for _ in range(3)]
    pf.close()
    assert steps == [10, 11, 12]


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "a": jax.random.normal(k, (16, 8)),
        "nested": {"b": jnp.arange(5, dtype=jnp.int32),
                   "c": jnp.float32(3.5)},
    }


def test_checkpoint_roundtrip(tmp_path):
    d = str(tmp_path)
    tree = _tree()
    ckpt.save(d, 7, tree, extra={"next_step": 7})
    assert ckpt.latest_step(d) == 7
    restored, extra = ckpt.restore(d, 7, tree)
    assert extra["next_step"] == 7
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_atomicity_and_corruption(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, _tree(1))
    ckpt.save(d, 2, _tree(2))
    # corrupt step 2: remove an array file
    step2 = os.path.join(d, "step_00000002")
    victim = [f for f in os.listdir(step2) if f.endswith(".npy")][0]
    os.remove(os.path.join(step2, victim))
    assert ckpt.latest_step(d) == 1  # falls back to the last valid one
    # a stray .tmp dir must not count either
    os.makedirs(os.path.join(d, "step_00000009.tmp"), exist_ok=True)
    assert ckpt.latest_step(d) == 1


def test_checkpoint_digest_detects_bitrot(tmp_path):
    d = str(tmp_path)
    tree = _tree()
    ckpt.save(d, 3, tree)
    step = os.path.join(d, "step_00000003")
    f = sorted(os.listdir(step))[0]
    if f == "manifest.json":
        f = sorted(os.listdir(step))[1]
    arr = np.load(os.path.join(step, f))
    arr_fl = arr.reshape(-1)
    arr_fl[0] = arr_fl[0] + 1 if arr.dtype != np.float32 else arr_fl[0] + 1.0
    np.save(os.path.join(step, f), arr)
    with pytest.raises(IOError):
        ckpt.restore(d, 3, tree)


def test_checkpoint_cleanup(tmp_path):
    d = str(tmp_path)
    for s in range(6):
        ckpt.save(d, s, {"x": jnp.zeros(3)})
    ckpt.cleanup(d, keep=2)
    assert ckpt.latest_step(d) == 5
    remaining = [n for n in os.listdir(d) if n.startswith("step_")]
    assert len(remaining) == 2


def test_async_saver(tmp_path):
    d = str(tmp_path)
    s = ckpt.AsyncSaver()
    s.save(d, 4, _tree())
    s.wait()
    assert ckpt.latest_step(d) == 4


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------


def test_adamw_converges_quadratic():
    w = {"w": jnp.asarray([5.0, -3.0, 2.0])}
    opt = adamw.init(w)
    cfg = AdamWConfig(lr=0.3, weight_decay=0.0)
    for _ in range(150):
        g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(w)
        w, opt, _ = adamw.update(g, opt, w, cfg, jnp.float32(0.3))
    assert float(jnp.max(jnp.abs(w["w"]))) < 0.05


def test_adamw_grad_clip():
    w = {"w": jnp.ones(4)}
    opt = adamw.init(w)
    cfg = AdamWConfig(lr=0.1, grad_clip=1.0)
    g = {"w": jnp.full((4,), 100.0)}
    _, _, m = adamw.update(g, opt, w, cfg, jnp.float32(0.1))
    assert float(m["grad_norm"]) == pytest.approx(200.0)
    assert float(m["clip_scale"]) < 0.01


def test_adamw_no_decay_mask():
    params = {"mlp": {"wi": jnp.ones((2, 2))}, "norm1": {"scale": jnp.ones(2)}}
    cfg = AdamWConfig()
    mask = adamw._decay_mask(params, cfg)
    assert mask == [True, False]


def test_warmup_cosine_shape():
    sched = adamw.warmup_cosine(1e-3, warmup=10, total=100)
    assert float(sched(jnp.int32(0))) == pytest.approx(0.0)
    assert float(sched(jnp.int32(10))) == pytest.approx(1e-3, rel=1e-3)
    assert float(sched(jnp.int32(100))) == pytest.approx(1e-4, rel=1e-2)
    assert float(sched(jnp.int32(55))) < 1e-3


# ---------------------------------------------------------------------------
# gradient compression (error feedback)
# ---------------------------------------------------------------------------


def test_compression_error_feedback_single_device():
    """Under a 1-member axis, compressed_psum must reproduce the gradient up
    to int8 quantization, and error feedback must keep the *running sum*
    accurate (residual carries over)."""
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.optim import compressed_psum, init_residual

    mesh = make_mesh((1,), ("pod",))
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(64,)),
                          jnp.float32)}
    r = init_residual(g)

    def f(g, r):
        return compressed_psum(g, r, "pod")

    fm = jax.shard_map(f, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()))
    acc = jnp.zeros(64)
    acc_true = jnp.zeros(64)
    for i in range(20):
        gi = {"w": g["w"] * (1 + 0.1 * i)}
        out, r = fm(gi, r)
        acc = acc + out["w"]
        acc_true = acc_true + gi["w"]
    # error feedback: accumulated transmitted sum tracks the true sum to
    # within one quantization step (not 20 steps' worth)
    step = float(jnp.max(jnp.abs(acc_true)) / 127.0) * 3
    assert float(jnp.max(jnp.abs(acc - acc_true))) < step


# ---------------------------------------------------------------------------
# fault-tolerant loop
# ---------------------------------------------------------------------------


def _counter_runner(tmp_path, injector=None, deadline=None):
    def step_fn(state, batch):
        new = {"x": state["x"] + batch["inc"]}
        return new, {"loss": float(state["x"][0])}

    return TrainLoopRunner(
        step_fn=step_fn,
        init_state_fn=lambda: {"x": jnp.zeros(2)},
        batch_fn=lambda step: {"inc": jnp.ones(2)},
        cfg=FaultConfig(ckpt_dir=str(tmp_path), save_every=5,
                        max_step_retries=1, step_deadline_s=deadline,
                        max_restarts=5, async_save=False),
        failure_injector=injector,
    )


def test_fault_loop_clean_run(tmp_path):
    runner = _counter_runner(tmp_path)
    state, hist = runner.run(12)
    assert float(state["x"][0]) == 12.0
    assert hist["restarts"] == 0
    assert ckpt.latest_step(str(tmp_path)) == 12


def test_fault_loop_recovers_from_crash(tmp_path):
    crashed = {"done": False}

    def injector(step):
        if step == 7 and not crashed["done"]:
            crashed["done"] = True
            raise RuntimeError("injected device failure")

    runner = _counter_runner(tmp_path, injector=injector)
    state, hist = runner.run(12)
    # retry path absorbs it (max_step_retries=1) without a full restart
    assert float(state["x"][0]) == 12.0


def test_fault_loop_restart_from_checkpoint(tmp_path):
    boom = {"count": 0}

    def injector(step):
        if step == 8 and boom["count"] < 2:
            boom["count"] += 1
            raise RuntimeError("persistent failure")

    runner = _counter_runner(tmp_path, injector=injector)
    state, hist = runner.run(12)
    assert float(state["x"][0]) == 12.0
    assert hist["restarts"] >= 1  # exhausted retries once -> restarted


def test_straggler_deadline(tmp_path):
    import time

    def step_fn(state, batch):
        time.sleep(0.3)
        return state, {"loss": 0.0}

    runner = TrainLoopRunner(
        step_fn=step_fn,
        init_state_fn=lambda: {"x": jnp.zeros(1)},
        batch_fn=lambda s: {},
        cfg=FaultConfig(ckpt_dir=str(tmp_path), save_every=100,
                        step_deadline_s=0.05, max_restarts=0,
                        async_save=False),
    )
    with pytest.raises(StepTimeout):
        runner.run(2)


def test_elastic_remesh():
    from repro.runtime import elastic_remesh

    mesh = elastic_remesh()
    assert "data" in mesh.axis_names and "model" in mesh.axis_names
