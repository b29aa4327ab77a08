"""The check that decides ``correct``: a sound run passes it, and a run
whose timed path is broken underneath fails it, once for each fault a
serving cell can have.  The harness's look for a chip is skipped; the rest
of a run is the cell's own at a CPU-test size.  (One chip: no exchange
between chips to leave out.)"""
import jax.numpy as jnp
import numpy as np
import pytest
import run
import spec
from conftest import smoke_cell

SEED = 2 ** 31 + 424242


def _wrap_decode(engine, change):
    """Make every decode chunk the engine builds pass through ``change``."""
    make = engine._make_decode

    def make_broken(n_steps):
        fn = make(n_steps)

        def broken(params, cache, last_tok, pos, active, key, calib):
            return change(fn, (params, cache, last_tok, pos, active, key,
                               calib))

        return broken

    engine._make_decode = make_broken


def state_unchanged(engine):
    """A decode step that hands back the cache it was given: no token's
    K/V ever lands."""
    def change(fn, args):
        _, tok, pos, toks = fn(*args)
        return args[1], tok, pos, toks
    _wrap_decode(engine, change)


def half_batch_left_out(engine):
    """The odd slots are left out of every step (their rows repeat)."""
    def change(fn, args):
        active = args[4]
        keep = active & (jnp.arange(active.shape[0]) % 2 == 0)
        return fn(*args[:4], keep, *args[5:])
    _wrap_decode(engine, change)


def token_altered(engine):
    """The first token of every slot's decode chunk is altered where it is
    made (the next step still reads the token the model chose)."""
    vocab = engine.cfg.vocab_size

    def change(fn, args):
        cache, tok, pos, toks = fn(*args)
        return cache, tok, pos, toks.at[:, 0].set((toks[:, 0] + 1) % vocab)
    _wrap_decode(engine, change)


@pytest.fixture(scope="module")
def cell():
    return smoke_cell("musicgen-medium.render")


def test_sound_run_is_correct(cell):
    res = run.run_cell(cell, SEED, 2.0, False, require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["checks"]["checked_tokens"]["value"] >= 32
    assert list(res)[-2:] == ["checks", "_info"]


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      spec.benchmark()["workloads"]])
def test_nothing_compiles_in_the_window(workload):
    """Warm-up reaches every program shape the window drives: each decode
    chunk length, prefill and insert, and each padded block-table extend
    that a chunk of ``max_chunk`` steps can ask for."""
    res = run.run_cell(smoke_cell(workload), SEED, 2.0, False,
                       require_tpu=False)
    assert res["_info"]["window_compiles"] == 0, res["_info"]["warmed"]


@pytest.mark.parametrize("fault", [state_unchanged, half_batch_left_out,
                                   token_altered])
def test_broken_timed_path_is_not_correct(cell, fault):
    res = run.run_cell(cell, SEED, 2.0, False, require_tpu=False,
                       fault=fault)
    assert not res["correct"], (fault.__name__, res["checks"])


@pytest.mark.parametrize("workload", ["musicgen-medium.render",
                                      "internvl2-2b.vqa"])
def test_control_in_the_programs_place_is_not_correct(workload):
    """The lower-precision control (the reference at fp8, its tokens at the
    served positions) goes through the run's own check and fails it, while
    the program's tokens at the same positions read far below it."""
    res = run.run_cell(smoke_cell(workload), SEED, 2.0, False,
                       require_tpu=False, control="fp8_e4m3")
    info = res["_info"]["checked"]
    ctl = res["checks"]["max_logit_gap"]
    assert res["correct"] is False, res["checks"]
    assert ctl["value"] > ctl["limit"] > info["program_logit_gap"], info
    assert np.isfinite(ctl["value"])
