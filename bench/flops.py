"""Operations and bytes the algorithm needs, from shapes alone.

These count the work a served token requires, not what an implementation
happens to do: padding rows and positions, dead cache blocks a kernel walks,
and recomputation are not counted.  So a later PR that makes the program do
less of that moves the achieved share, and a PR that counts differently
cannot.

Model FLOPs of one token at context ``c`` (the keys it attends to, itself
included), per layer: ``2 * (d*Hq*hd + 2*d*Hkv*hd + Hq*hd*d + mlp*d*f)`` for
the weight matmuls (``mlp`` = 3 for a gated MLP, else 2) and ``4*Hq*hd*c``
for attention (QK^T and PV).  The LM head, ``2*d*vocab``, is counted once
per prefill row (only its last position is needed) and once per decoded
token.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable


def _mlp_mults(m: Dict[str, Any]) -> int:
    return 3 if m["mlp_kind"] in ("swiglu", "geglu") else 2


def matmul_flops_per_token(m: Dict[str, Any]) -> float:
    d, f = m["d_model"], m["d_ff"]
    hq = m["n_heads"] * m["head_dim"]
    hkv = m["n_kv_heads"] * m["head_dim"]
    per_layer = 2 * (d * hq + 2 * d * hkv + hq * d + _mlp_mults(m) * d * f)
    return float(m["n_layers"] * per_layer)


def head_flops(m: Dict[str, Any]) -> float:
    return 2.0 * m["d_model"] * m["vocab_size"]


def attn_flops(m: Dict[str, Any], ctx: float) -> float:
    """QK^T and PV of one query over ``ctx`` keys, all layers."""
    return 4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * ctx


def prefill_flops(m: Dict[str, Any], length: int) -> float:
    """One prompt row of ``length`` true tokens (causal: position p attends
    to p + 1 keys)."""
    keys = length * (length + 1) / 2
    return (length * matmul_flops_per_token(m) + attn_flops(m, keys)
            + head_flops(m))


def decode_flops(m: Dict[str, Any], pos: int) -> float:
    """One decoded token with ``pos`` tokens already cached."""
    return (matmul_flops_per_token(m) + attn_flops(m, pos + 1)
            + head_flops(m))


def chunk_decode_flops(m: Dict[str, Any], positions: Iterable[int],
                       n_steps: int) -> float:
    """A fused decode chunk: every active slot (cached ``pos`` at the start)
    decodes ``n_steps`` tokens."""
    return sum(decode_flops(m, p + t) for p in positions
               for t in range(n_steps))


def paged_attn_cost(m: Dict[str, Any], pos: int, kv_bytes: int = 2,
                    q_bytes: int = 2, out_bytes: int = 4):
    """(flops, bytes) of one paged decode-attention call for one active
    slot, all layers: read the query, the new token's K and V and the K/V of
    the ``pos`` cached positions; write the new K/V into the pool and the
    float32 context."""
    hq = m["n_heads"] * m["head_dim"]
    hkv = m["n_kv_heads"] * m["head_dim"]
    per_layer_bytes = (q_bytes * hq + 2 * kv_bytes * hkv
                       + 2 * kv_bytes * hkv * pos
                       + 2 * kv_bytes * hkv + out_bytes * hq)
    flops = 4.0 * m["n_layers"] * hq * (pos + 1)
    return flops, float(m["n_layers"] * per_layer_bytes)


def kv_bytes_per_token(m: Dict[str, Any], kv_bytes: int = 2) -> int:
    return 2 * kv_bytes * m["n_layers"] * m["n_kv_heads"] * m["head_dim"]
