"""Plain float32 reference of the served decoders, and its lower-precision
control.

One pre-norm decoder covers both configurations: multi-head or grouped-query
attention (no biases) with a causal softmax, RoPE (rotate-half, as in Llama
and InternLM2) or sinusoidal positions added to the embedding (MusicGen),
LayerNorm or RMSNorm, and a GELU or SwiGLU MLP; a final norm and an untied LM
head.  It imports nothing of the program.  Where it departs from the
published models it follows the served model's own definition, and
``PERF.md`` lists each departure: GELU in its tanh form, norm epsilon 1e-6,
sinusoidal features ordered sin then cos.

Everything is float32 at ``Precision.HIGHEST`` (a float32 matmul on a TPU is
otherwise a single bfloat16 pass), run one layer at a time over one sequence,
so the reference fits beside nothing else on the chip.

The control is the same computation with both operands of every weight
matmul rounded to a lower precision first: ``fp8_e4m3`` for a bfloat16
model, ``int4`` for a 7-bit substrate.  Each operand is scaled per token
(activations) or per output column (weights) onto the format's range.  Its
tokens stand in for the served ones, at the served positions, and go
through the same comparison.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6
PAD = 512  # sequences are padded to a multiple of this (fewer compiles)


def _round(x, mode: Optional[str], axis: int):
    """Round ``x`` onto ``mode``'s grid, scaled along ``axis``."""
    if mode is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if mode == "fp8_e4m3":
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    levels = {"int8": 127.0, "int4": 7.0}[mode]
    s = jnp.maximum(amax, 1e-30) / levels
    return jnp.clip(jnp.round(x / s), -levels, levels) * s


def _mm(x, w, quant):
    """x (S, k) @ w (k, m) in float32, operands optionally rounded."""
    w = w.astype(jnp.float32)
    return jnp.dot(_round(x, quant, -1), _round(w, quant, 0), precision=HI)


def _norm(x, p, kind):
    g = 1.0 + p["scale"].astype(jnp.float32)
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * g
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS) * g + p["bias"].astype(
        jnp.float32)


def _rope(x, theta):
    """x (S, H, hd) at positions 0..S-1, rotate-half pairing."""
    s, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(s)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _sinusoid(s, d):
    half = d // 2
    inv = np.exp(-math.log(10000.0) * np.arange(half) / half)
    ang = np.arange(s)[:, None] * inv[None, :]
    return jnp.asarray(np.concatenate([np.sin(ang), np.cos(ang)], -1),
                       jnp.float32)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _layer(p, x, m, quant):
    s = x.shape[0]
    H, Hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    h = _norm(x, p["norm1"], m["norm_kind"])
    q = _mm(h, p["wq"], quant).reshape(s, H, hd)
    k = _mm(h, p["wk"], quant).reshape(s, Hkv, hd)
    v = _mm(h, p["wv"], quant).reshape(s, Hkv, hd)
    if m["pos_kind"] == "rope":
        q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * hd ** -0.5
    causal = np.tril(np.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v,
                     precision=HI).reshape(s, H * hd)
    x = x + _mm(ctx, p["wo"], quant)
    h = _norm(x, p["norm2"], m["norm_kind"])
    up = _mm(h, p["wi"], quant)
    if m["mlp_kind"] == "swiglu":
        up = jax.nn.silu(_mm(h, p["wg"], quant)) * up
    else:
        up = _gelu_tanh(up)
    return x + _mm(up, p["wo_mlp"], quant)


def _static(m):
    keys = ("n_heads", "n_kv_heads", "head_dim", "norm_kind", "pos_kind",
            "rope_theta", "mlp_kind", "d_model", "vocab_size")
    return tuple((k, m[k]) for k in keys)


@functools.lru_cache(maxsize=None)
def _layer_fn(static, quant):
    return jax.jit(lambda p, x: _layer(p, x, dict(static), quant))


@functools.lru_cache(maxsize=None)
def _head_fn(static, quant):
    m = dict(static)

    def head(p_norm, w, x, rows):
        h = _norm(x[rows], p_norm, m["norm_kind"])
        return _mm(h, w, quant)[:, : m["vocab_size"]]

    return jax.jit(head)


def layer_params(params: Dict[str, Any], i: int) -> Dict[str, Any]:
    b = params["blocks"]["p0"]
    p = {"norm1": {k: v[i] for k, v in b["norm1"].items()},
         "norm2": {k: v[i] for k, v in b["norm2"].items()},
         "wo_mlp": b["mlp"]["wo"][i], "wi": b["mlp"]["wi"][i]}
    p.update({k: b["mixer"][k][i] for k in ("wq", "wk", "wv", "wo")})
    if "wg" in b["mlp"]:
        p["wg"] = b["mlp"]["wg"][i]
    return p


def logits_at(params, m: Dict[str, Any], tokens: np.ndarray,
              rows: np.ndarray, quant: Optional[str] = None) -> np.ndarray:
    """float32 logits (len(rows), vocab) at positions ``rows`` of the
    sequence ``tokens``; positions are 0..len-1."""
    s = len(tokens)
    s_pad = -(-s // PAD) * PAD
    toks = np.zeros(s_pad, np.int32)
    toks[:s] = tokens
    static = _static(m)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
        if m["pos_kind"] == "sinusoidal":
            x = x + _sinusoid(s_pad, m["d_model"])
        layer = _layer_fn(static, quant)
        for i in range(m["n_layers"]):
            x = layer(layer_params(params, i), x)
        out = _head_fn(static, quant)(params["final_norm"],
                                      params["lm_head"], x,
                                      jnp.asarray(rows, jnp.int32))
    return np.asarray(out)


def served_gaps(params, m, prompt: np.ndarray, served,
                quant: Optional[str] = None) -> Dict[str, np.ndarray]:
    """For each served token: how far its float32 reference logit lies below
    the reference's best (``gap``).  With ``quant``, the control in the
    program's place, read at the same positions: teacher-forced on the same
    prompt and served tokens, the token that the lower-precision reference
    puts first at each served position (``control_tokens``) and how far its
    float32 logit lies below the best (``control_gap``)."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    rows = np.arange(len(prompt) - 1, len(seq))
    ref = logits_at(params, m, seq, rows)
    best = ref.max(-1)
    at = np.arange(len(served))
    out = {"gap": best - ref[at, served]}
    if quant is not None:
        ctl = logits_at(params, m, seq, rows, quant).argmax(-1)
        out["control_tokens"] = ctl.astype(np.int32)
        out["control_gap"] = best - ref[at, ctl]
    return out
