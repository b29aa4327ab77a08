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
# the paged kernel's served geometries, (slots, Hkv, G, hd, max_blocks):
# musicgen-medium's MHA at several table sizes (render: 3 slots of 250
# blocks) and internvl2-2b's GQA (vqa: 14 slots of 288 blocks)
PAGED = {
    "64": (SLOTS, HKV, G, HD, 64),
    "512": (SLOTS, HKV, G, HD, 512),
    "4096": (SLOTS, HKV, G, HD, 4096),
    "render": (3, HKV, G, HD, 250),
    "internvl2-64": (SLOTS, 8, 2, 128, 64),
    "internvl2-4096": (SLOTS, 8, 2, 128, 4096),
    "vqa": (14, 8, 2, 128, 288),
}


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


def _paged_compile(one_chip, geometry):
    """Compile one kernel call with the pools donated and laid out as the
    kernel takes them (row-major), so that the program holds the call and
    nothing XLA adds around it; returns (compiled, bytes of one pool)."""
    from jax.experimental.layout import Format, Layout

    slots, hkv, g, hd, max_blocks = PAGED[geometry]
    pool_fmt = Format(Layout(major_to_minor=(0, 1, 2, 3)), one_chip)
    pool = jax.ShapeDtypeStruct((slots * max_blocks + 1, BS, hkv, hd),
                                jnp.bfloat16, sharding=pool_fmt)
    args = (
        _sds((slots, hkv, g, hd), jnp.bfloat16, one_chip),
        _sds((slots, hkv, hd), jnp.bfloat16, one_chip),
        _sds((slots, hkv, hd), jnp.bfloat16, one_chip),
        pool, pool,
        _sds((slots, max_blocks), jnp.int32, one_chip),
        _sds((slots,), jnp.int32, one_chip),
        _sds((slots,), jnp.bool_, one_chip),
    )

    def step(q, kn, vn, pk, pv, bt, pos, active):
        return paged_attention_decode(q, kn, vn, pk, pv, bt, pos, active,
                                      scale=hd ** -0.5, use_pallas=True,
                                      interpret=False)

    compiled = jax.jit(step, donate_argnums=(3, 4),
                       out_shardings=(one_chip, pool_fmt, pool_fmt)
                       ).lower(*args).compile()
    # a pool's bytes as it lies in HBM: head rows padded to 128 lanes
    pool_bytes = 2 * (pool.size // hd) * (-(-hd // 128) * 128)
    return compiled, pool_bytes


@pytest.mark.parametrize("geometry", list(PAGED))
def test_paged_attention_compiles_for_v5e(one_chip, geometry):
    """The kernel compiles at each served geometry, and the call adds no
    temporary as large as one pool: both pools are read and written in
    place."""
    compiled, pool_bytes = _paged_compile(one_chip, geometry)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < pool_bytes, (mem, pool_bytes)
    assert mem.alias_size_in_bytes >= 2 * pool_bytes, (mem, pool_bytes)


def _assert_named_and_aliased(one_chip, geometry):
    import re

    compiled, _ = _paged_compile(one_chip, geometry)
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    assert len(calls) == 1
    assert re.search(r'op_name="[^"]*/paged_attention/', calls[0])
    assert re.search(r"output_to_operand_aliasing=\{\{1\}: \(7, \{\}\), "
                     r"\{2\}: \(8, \{\}\)\}", calls[0])


def test_paged_attention_kernel_is_named_and_scoped(one_chip):
    """A profile finds the kernel by its ``paged_attention`` scope, and the
    benchmark's older reader by the pools' aliasing (outputs 1 and 2 alias
    operands 7 and 8): the compiled custom call carries both (musicgen's
    MHA geometry)."""
    _assert_named_and_aliased(one_chip, "64")


@pytest.mark.parametrize("geometry", ["render", "vqa"])
def test_served_paged_kernel_is_named_and_scoped(one_chip, geometry):
    """The same at each served cell's geometry: render's MHA (3 slots of 250
    blocks) and vqa's GQA (14 slots of 288 blocks, Hkv 8, G 2, hd 128)."""
    _assert_named_and_aliased(one_chip, geometry)


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
