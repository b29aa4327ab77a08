"""Serving driver tests + vocab padding semantics."""
import importlib.util
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch import serve as serve_mod
from repro.models import forward, init_params


@pytest.mark.slow
def test_serve_batched_requests():
    out = serve_mod.main([
        "--arch", "musicgen-medium", "--smoke", "--batch", "4",
        "--requests", "8", "--prompt-len", "12", "--gen", "6",
    ])
    assert len(out) == 8
    assert all(len(r.out) == 6 for r in out)


@pytest.mark.slow
def test_serve_imc_mode_changes_tokens():
    """IMC analog noise at a low design point must alter generations."""
    base = serve_mod.main([
        "--arch", "musicgen-medium", "--smoke", "--batch", "2",
        "--requests", "2", "--prompt-len", "12", "--gen", "6",
    ])
    noisy = serve_mod.main([
        "--arch", "musicgen-medium", "--smoke", "--batch", "2",
        "--requests", "2", "--prompt-len", "12", "--gen", "6",
        "--imc-mode", "imc_analytic", "--imc-vwl", "0.55",
    ])
    agree = np.mean([
        np.mean(np.array(a.out) == np.array(b.out))
        for a, b in zip(base, noisy)
    ])
    assert agree < 1.0  # low-SNR analog core perturbs decoding


def test_serve_main_fails_on_errored_request():
    """A request that finishes with an error status fails the run: a 48-token
    prompt can never fit a 3-block pool."""
    with pytest.raises(RuntimeError, match="error status"):
        serve_mod.main([
            "--arch", "musicgen-medium", "--smoke", "--batch", "2",
            "--requests", "2", "--prompt-lens", "4,48", "--gen", "4",
            "--kv-blocks", "4",
        ])


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = sys.modules["chip_smoke"] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_digital_phase_at_smoke_size():
    """The chip smoke's digital phase, at the smoke preset on the CPU: every
    request is served in full, and the CPU decode chunk walks the blocks in
    JAX (no Pallas custom call)."""
    cs = _chip_smoke()
    line = cs.serve_phase("digital", cs.SMOKE)
    assert line["requests"] == cs.SMOKE.requests
    assert line["tokens"] == cs.SMOKE.requests * cs.SMOKE.gen
    assert line["decode_attn"] == "jax_block_walk"
    assert not line["decode_chunk_has_tpu_custom_call"]
    assert line["compile_s"] > 0 and line["serve_s"] > 0


def test_chip_smoke_refuses_cpu():
    """Without a TPU the chip smoke exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                          capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_compile_cache_dir(monkeypatch):
    """The persistent compilation cache lives where JAX_COMPILATION_CACHE_DIR
    says (JAX reads it itself), else at the fixed <repo>/.jax_cache."""
    from repro.runtime.compile_cache import use_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        default = os.path.join(ROOT, ".jax_cache")
        assert use_compile_cache() == default
        assert jax.config.jax_compilation_cache_dir == default
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_vocab_padding_masked():
    """Padded vocab rows must never win argmax and never receive probability."""
    cfg = configs.get_smoke("internvl2-2b")  # vocab 512 -> padded 512 (even)
    cfg = cfg.replace(vocab_size=500)  # force padding to 512
    assert cfg.padded_vocab == 512
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 500)
    pe = jax.random.normal(jax.random.PRNGKey(2), (2, cfg.prefix_len, cfg.d_model))
    logits, _ = forward(params, cfg, toks, pe)
    assert logits.shape[-1] == 512
    assert bool(jnp.all(logits[..., 500:] <= -1e8))
    assert bool(jnp.all(jnp.argmax(logits, -1) < 500))


def test_param_count_excludes_padding():
    cfg = configs.get("internvl2-2b")
    assert cfg.padded_vocab == 92672
    # param_count uses true vocab (MODEL_FLOPS bookkeeping)
    assert cfg.param_count() < 92672 * cfg.d_model * 2 + 10_000_000_000
