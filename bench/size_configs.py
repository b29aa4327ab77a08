"""Size a configuration's slots from the TPU compiler's memory analysis.

    JAX_PLATFORMS=cpu python bench/size_configs.py <config> <traffic> [slots ...]

Compiles, for a described v5e chip (nothing runs; no chip needed), the
programs a cell's engine runs at their real sizes - the decode chunk at the
largest chunk length, the largest prefill (``prefill_rows_max`` rows at the
largest bucket) and its insert - for each slot count given, and prints
``compiled.memory_analysis()`` of each with the resident bytes.  Resident
between calls: the weights and one copy of the KV pool.  During a call the
arguments stay live beside the program's outputs (nothing is donated) and
its temporaries, so the peak of a call is arguments + outputs + temporaries
less what aliases; the compiler's own peak (``peak_memory_in_bytes``) is
what the slots are sized by.
"""
from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402
import traffic as traffic_lib  # noqa: E402
import weights  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


def _on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _report(name, compiled):
    ma = compiled.memory_analysis()
    peak = ma.peak_memory_in_bytes
    print(f"  {name}: arguments {ma.argument_size_in_bytes} outputs "
          f"{ma.output_size_in_bytes} temporaries {ma.temp_size_in_bytes} "
          f"aliased {ma.alias_size_in_bytes} -> compiler's peak {peak} "
          f"({peak / 1e9:.2f} GB)", flush=True)
    return peak


def size(config: str, mix_name: str, slot_counts):
    from jax.experimental import topologies

    from repro.kernels import paged_attention
    from repro.launch.serve import Engine, prefill_bucket
    from repro.models import init_paged_cache

    conf = spec.load_json(os.path.join(BENCH, "configs", config + ".json"))
    mix = spec.load_json(os.path.join(BENCH, "traffic", mix_name + ".json"))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    eng_conf = conf["engine"]
    cfg = run.arch_config(conf)
    sub = conf["substrate"]
    if sub["mode"] != "digital":
        # sizes only: the dynamic policy computes the same per-call
        # quantization the frozen one does, without a calibration pass
        from repro.core.imc_linear import IMCConfig
        from repro.core.substrate import as_substrate

        cfg = cfg.replace(imc=as_substrate(IMCConfig(
            mode=sub["mode"], bx=sub["bx"], bw=sub["bw"], v_wl=sub["v_wl"])))
    max_pos = traffic_lib.max_positions(mix)
    block = eng_conf["block"]
    max_blocks = -(-max_pos // block)
    params = _on(jax.eval_shape(
        lambda: weights.make_params(conf["model"], 0)), chip)
    print(f"{config} x {mix_name}: weights {weights.param_bytes(conf['model'])}"
          f" bytes; {max_pos} positions per request ({max_blocks} blocks); "
          f"budget rule gives {run.slots_for(conf, mix)} slots")
    # the Pallas kernel is what the chip runs: steer the backend test
    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    paged_attention._interpret_default = lambda: False
    try:
        for slots in slot_counts:
            kv_blocks = slots * max_blocks + 1
            eng = object.__new__(Engine)
            eng.cfg, eng.tp, eng._rules = cfg, 1, None
            eng.block, eng.bucketable = block, True
            cache = jax.eval_shape(lambda: init_paged_cache(
                cfg, slots, max_blocks * block, kv_blocks, block))
            cache.pop("pos")
            cache = _on(cache, chip)
            vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip)
            act = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=chip)
            pool = sum(x.size * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(cache))
            print(f" slots {slots}: kv pool {pool} bytes ({kv_blocks} "
                  f"blocks)", flush=True)
            dec = eng._make_decode(eng_conf["max_chunk"]).lower(
                params, cache, vec, vec, act, None, None).compile()
            _report(f"decode chunk T={eng_conf['max_chunk']}", dec)
            bucket = prefill_bucket(traffic_lib.prompt_bounds(mix)[1], True,
                                    max_blocks * block)
            r = eng_conf["prefill_rows_max"]
            toks = jax.ShapeDtypeStruct((r, bucket), jnp.int32, sharding=chip)
            tl = jax.ShapeDtypeStruct((r,), jnp.int32, sharding=chip)
            pf = eng._make_prefill().lower(params, toks, tl, None,
                                           None).compile()
            _report(f"prefill {r}x{bucket}", pf)
            tok0, cache1 = jax.eval_shape(eng._make_prefill(), params, toks,
                                          tl, None, None)
            cache1 = _on({k: v for k, v in cache1.items() if k != "pos"},
                         chip)
            rows = jax.ShapeDtypeStruct((r, max_blocks), jnp.int32,
                                        sharding=chip)
            ins = jax.jit(eng._insert_impl).lower(
                cache, cache1, tl, rows, _on(tok0, chip), tl, vec,
                vec).compile()
            _report(f"insert {r}x{bucket}", ins)
    finally:
        jax.default_backend = real_backend


if __name__ == "__main__":
    size(sys.argv[1], sys.argv[2], [int(s) for s in sys.argv[3:]] or [8])
