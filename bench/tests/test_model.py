"""The benchmark's weights fit the program's parameter layout, and the
plain float32 reference agrees with the program's own float32 forward."""
import jax
import numpy as np
import pytest
import reference
import weights
from conftest import SMOKE_MODEL, smoke_cell

CELLS = ["musicgen-medium.render", "internvl2-2b.vqa"]


def _program_cfg(conf):
    import run

    return run.arch_config(conf)


@pytest.mark.parametrize("workload", CELLS)
def test_weights_match_program_layout(workload):
    from repro.models import init_params

    conf = smoke_cell(workload)["config"]
    mine = jax.eval_shape(lambda: weights.make_params(conf["model"], 0))
    theirs = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                                _program_cfg(conf)))
    assert (jax.tree_util.tree_structure(mine)
            == jax.tree_util.tree_structure(theirs))
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_weights_fixed_by_seed():
    m = dict(smoke_cell(CELLS[0])["config"]["model"])
    a = weights.make_params(m, 2 ** 31 + 5)
    b = weights.make_params(m, 2 ** 31 + 5)
    c = weights.make_params(m, 6)
    np.testing.assert_array_equal(a["embed"], b["embed"])
    assert not np.array_equal(a["embed"], c["embed"])


@pytest.mark.parametrize("workload", CELLS)
def test_reference_matches_program_forward(workload):
    """At float32 the program's full-sequence forward and the reference
    give the same logits (to float32 rounding of 2 layers)."""
    from repro.models import forward

    conf = smoke_cell(workload)["config"]
    m = dict(conf["model"], dtype="float32")
    params = weights.make_params(m, 11)
    toks = np.random.default_rng(0).integers(0, m["vocab_size"], 37)
    cfg = _program_cfg(dict(conf, model=m)).replace(remat=False)
    with jax.default_matmul_precision("highest"):
        prog, _ = forward(params, cfg, jax.numpy.asarray(toks[None]))
    prog = np.asarray(prog[0, :, : m["vocab_size"]])
    ref = reference.logits_at(params, m, toks, np.arange(37))
    np.testing.assert_allclose(ref, prog, atol=2e-4, rtol=2e-4)


def test_control_rounds_coarser_than_bfloat16():
    x = np.random.default_rng(1).standard_normal((8, 64)).astype(np.float32)
    for mode in ("fp8_e4m3", "int8", "int4"):
        err = float(np.abs(np.asarray(reference._round(x, mode, -1)) - x)
                    .max())
        assert err > 2 ** -8
    assert sorted(SMOKE_MODEL) == ["internvl2-2b", "musicgen-medium"]
