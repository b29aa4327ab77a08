"""Random weights from the seed, made on the device in one jitted call.

The tree has the layout the serving engine takes (``params["blocks"]["p0"]``
holds every layer stacked on a leading axis), in the dtype the model is
served in.  The benchmark makes it, not the program, so the plain reference
can read the same numbers without taking anything the program made.

Scales: projections ~ N(0, 1/fan_in), embeddings ~ N(0, 1), the LM head
~ N(0, 1/d_model) (logits of unit order), norm gains and biases ~ N(0, 0.1)
(the program stores a gain as ``1 + scale``).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NORM_STD = 0.1


def padded_vocab(m: Dict[str, Any]) -> int:
    return -(-m["vocab_size"] // 256) * 256


def leaf_specs(m: Dict[str, Any]) -> List[Tuple[Tuple[str, ...], tuple, float]]:
    """(path, shape, std) of every leaf, in a fixed order."""
    L, d, f = m["n_layers"], m["d_model"], m["d_ff"]
    hq = m["n_heads"] * m["head_dim"]
    hkv = m["n_kv_heads"] * m["head_dim"]
    vp = padded_vocab(m)
    specs = [(("embed",), (vp, d), 1.0),
             (("lm_head",), (d, vp), d ** -0.5)]

    def norm(prefix, lead):
        out = [(prefix + ("scale",), lead + (d,), NORM_STD)]
        if m["norm_kind"] == "layernorm":
            out.append((prefix + ("bias",), lead + (d,), NORM_STD))
        return out

    specs += norm(("final_norm",), ())
    blk = ("blocks", "p0")
    specs += norm(blk + ("norm1",), (L,))
    specs += norm(blk + ("norm2",), (L,))
    specs += [
        (blk + ("mixer", "wq"), (L, d, hq), d ** -0.5),
        (blk + ("mixer", "wk"), (L, d, hkv), d ** -0.5),
        (blk + ("mixer", "wv"), (L, d, hkv), d ** -0.5),
        (blk + ("mixer", "wo"), (L, hq, d), hq ** -0.5),
        (blk + ("mlp", "wi"), (L, d, f), d ** -0.5),
        (blk + ("mlp", "wo"), (L, f, d), f ** -0.5),
    ]
    if m["mlp_kind"] in ("swiglu", "geglu"):
        specs.append((blk + ("mlp", "wg"), (L, d, f), d ** -0.5))
    return specs


def weight_seed(seed: int) -> int:
    """A 31-bit PRNG seed derived from any whole-number ``--seed``."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0] >> 1)


@functools.lru_cache(maxsize=None)
def _maker(key_tuple):
    specs, dtype = key_tuple

    @jax.jit
    def make(key):
        leaves = []
        for i, (_, shape, std) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            leaves.append((jax.random.normal(k, shape, jnp.float32)
                           * std).astype(dtype))
        return leaves

    return make


def make_params(m: Dict[str, Any], seed: int) -> Dict[str, Any]:
    specs = tuple(leaf_specs(m))
    dtype = jnp.dtype(m["dtype"])
    leaves = _maker((specs, dtype))(jax.random.PRNGKey(weight_seed(seed)))
    tree: Dict[str, Any] = {}
    for (path, _, _), leaf in zip(specs, leaves):
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf
    return tree


def param_bytes(m: Dict[str, Any]) -> int:
    item = jnp.dtype(m["dtype"]).itemsize
    return sum(int(np.prod(s)) * item for _, s, _ in leaf_specs(m))
