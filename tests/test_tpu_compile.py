"""Ahead-of-time compiles of the served kernels for a described TPU v5e chip.

Nothing here runs on a device: each test lowers a Pallas kernel at the
published ``musicgen-medium`` widths and hands it to the TPU compiler that
ships with jaxlib, which refuses what the chip would refuse (layouts Mosaic
cannot infer, casts it cannot lower, VMEM overuse).  Interpret-mode tests
cannot see any of that.  The topology is described inside a fixture, never at
import: only one process may load the TPU compiler library at a time.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import imc_mvm
from repro.kernels.paged_attention import paged_attention_decode
from repro.kernels.ref import AnalyticSpec, BitSerialSpec

# musicgen-medium decode widths: 8 slots, 24 MHA KV heads (G=1), head_dim 64,
# 8-token blocks; projection K=d_model=1536, M=d_ff=6144
SLOTS, HKV, G, HD, BS = 8, 24, 1, 64, 8
K, M = 1536, 6144


@pytest.fixture(scope="module")
def topo():
    # (conftest keeps the persistent compilation cache off: an executable
    # compiled for a described chip could not be read back from it here)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("max_blocks", [64, 512, 4096])
def test_paged_attention_compiles_for_v5e(one_chip, max_blocks):
    num_blocks = SLOTS * max_blocks + 1
    pool = _sds((num_blocks, BS, HKV, HD), jnp.bfloat16, one_chip)
    args = (
        _sds((SLOTS, HKV, G, HD), jnp.bfloat16, one_chip),
        _sds((SLOTS, HKV, HD), jnp.bfloat16, one_chip),
        _sds((SLOTS, HKV, HD), jnp.bfloat16, one_chip),
        pool, pool,
        _sds((SLOTS, max_blocks), jnp.int32, one_chip),
        _sds((SLOTS,), jnp.int32, one_chip),
        _sds((SLOTS,), jnp.bool_, one_chip),
    )

    def step(q, kn, vn, pk, pv, bt, pos, active):
        return paged_attention_decode(q, kn, vn, pk, pv, bt, pos, active,
                                      scale=HD ** -0.5, use_pallas=True,
                                      interpret=False)

    compiled = jax.jit(step).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_attention_kernel_is_named_and_scoped(one_chip):
    """A profile finds the kernel by its ``paged_attention`` scope, and the
    benchmark's older reader by the pools' aliasing (outputs 1 and 2 alias
    operands 7 and 8): the compiled custom call carries both."""
    import re

    max_blocks = 64
    pool = _sds((SLOTS * max_blocks + 1, BS, HKV, HD), jnp.bfloat16, one_chip)
    args = (
        _sds((SLOTS, HKV, G, HD), jnp.bfloat16, one_chip),
        _sds((SLOTS, HKV, HD), jnp.bfloat16, one_chip),
        _sds((SLOTS, HKV, HD), jnp.bfloat16, one_chip),
        pool, pool,
        _sds((SLOTS, max_blocks), jnp.int32, one_chip),
        _sds((SLOTS,), jnp.int32, one_chip),
        _sds((SLOTS,), jnp.bool_, one_chip),
    )

    def step(q, kn, vn, pk, pv, bt, pos, active):
        return paged_attention_decode(q, kn, vn, pk, pv, bt, pos, active,
                                      scale=HD ** -0.5, use_pallas=True,
                                      interpret=False)

    text = jax.jit(step).lower(*args).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1
    assert re.search(r'op_name="[^"]*/paged_attention/', calls[0])
    assert re.search(r"output_to_operand_aliasing=\{\{1\}: \(7, \{\}\), "
                     r"\{2\}: \(8, \{\}\)\}", calls[0])


@pytest.mark.parametrize("hw_prng", [False, True], ids=["counter", "hardware"])
@pytest.mark.parametrize("kernel", ["analytic", "bitserial"])
def test_imc_kernel_with_noise_compiles_for_v5e(one_chip, monkeypatch,
                                                kernel, hw_prng):
    # the PRNG branch keys on jax.default_backend(), which is the CPU here
    monkeypatch.setattr(imc_mvm, "_hw_prng_available",
                        lambda interpret: hw_prng)
    x = _sds((SLOTS, K), jnp.float32, one_chip)
    w = _sds((K, M), jnp.float32, one_chip)
    seed = _sds((), jnp.int32, one_chip)
    if kernel == "analytic":
        spec = AnalyticSpec(b_adc=8, sigma_out=3.0, y_clip=4e4)

        def step(x, w, seed):
            return imc_mvm.imc_analytic_matmul(x, w, spec, seed=seed,
                                               interpret=False)
    else:
        spec = BitSerialSpec(bx=7, bw=7, b_adc=8, rows=512, k_h=60.0,
                             v_c=55.0, sigma_noise=0.5)

        def step(x, w, seed):
            return imc_mvm.imc_bitserial_matmul(x, w, None, spec, seed=seed,
                                                interpret=False)

    compiled = jax.jit(step).lower(x, w, seed).compile()
    assert "tpu_custom_call" in compiled.as_text()
