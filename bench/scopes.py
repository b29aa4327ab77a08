"""Named scopes and engine spans in a profile (``.xplane.pb``).

The program names its code with ``jax.named_scope`` (``embed``, ``block``,
``attn``, ``mlp``, ``head``, ``sample``, ``paged_attention``, each matmul's
site such as ``attn.wq`` or ``lm_head``, and ``imc`` for a substrate's
emulation inside a site), and XLA keeps the scope path in each HLO
instruction's ``op_name`` (a fusion carries its root's).  The profile stores
that name as the ``tf_op`` stat of each op's event metadata on the device
plane, which ``jax.profiler.ProfileData`` does not expose, so ``op_names``
reads it from the serialized XSpace itself (field numbers of
``tsl/profiler/protobuf/xplane.proto``).  An op's scope is the innermost of
these names on its path; an op with none is ``unscoped``: the layer scan's
own slicing and updating of the stacked weights and KV pools, and XLA's
loop-carry copies.

The engine's host work runs in ``engine.*`` spans (``launch/serve.py``)
nested in the benchmark's own (``xplane.HOST_SPANS``); ``idle_by_span``
puts each idle gap of the device down to the innermost span covering it.

A program without scopes or spans (an older checkout) reads as
uninstrumented, and the readers built on this module then find nothing.

    python3 bench/scopes.py <trace.xplane.pb>

prints the decode program's time by scope, its costliest ops with their
scopes, and the device's idle time by host span.
"""
from __future__ import annotations

import json
import os
import re
import sys
import traceback
from typing import Any, Dict, Iterator, List, Optional, Tuple

import numpy as np
import xplane

TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".bench_out", "trace")
SCOPES = frozenset({"embed", "block", "attn", "mlp", "ssm", "rglru", "head",
                    "sample", "paged_attention", "imc"})
SITE = re.compile(r"^(lm_head|[a-z_]+\.[a-z_0-9]+)$")
UNSCOPED = "unscoped"
ENGINE = "engine."


# ---------------------------------------------------------------------------
# the serialized XSpace
# ---------------------------------------------------------------------------


def _varint(buf, i: int) -> Tuple[int, int]:
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return v, i


def _fields(buf) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited field, raw bytes for fixed ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} not supported")
        yield key >> 3, v


def _stat_names(plane) -> Dict[int, str]:
    out = {}
    for f, entry in _fields(plane):
        if f == 5:  # stat_metadata: map<int64, XStatMetadata>
            meta = dict(_fields(dict(_fields(entry))[2]))
            out[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
    return out


def op_names(data: bytes) -> Dict[Tuple[int, str], str]:
    """(program id, op event name) -> the op's ``op_name``, over the device
    planes of a serialized XSpace."""
    out: Dict[Tuple[int, str], str] = {}
    for f, plane in _fields(memoryview(data)):
        if f != 1:
            continue
        name = next((bytes(v).decode() for g, v in _fields(plane) if g == 2),
                    "")
        if not name.startswith("/device:") or "CUSTOM" in name:
            continue
        stat_name = _stat_names(plane)
        for g, entry in _fields(plane):
            if g != 4:  # event_metadata: map<int64, XEventMetadata>
                continue
            ev_name, stats = "", {}
            for h, v in _fields(dict(_fields(entry))[2]):
                if h == 2:
                    ev_name = bytes(v).decode()
                elif h == 5:
                    st = dict(_fields(v))
                    stats[stat_name.get(st.get(1))] = st
            tf_op = stats.get("tf_op")
            if tf_op is None:
                continue
            if 5 in tf_op:
                text = bytes(tf_op[5]).decode()
            else:  # a reference to a stat name holding the string
                text = stat_name.get(tf_op.get(7), "")
            # "<op_name>:<op_type>"
            pid = stats.get("program_id", {}).get(3, 0)
            out[(pid, ev_name)] = text.rpartition(":")[0] or text
    return out


def scope_of(op_name: str) -> str:
    """The innermost program scope on an op's name path (XLA joins the names
    of merged ops with ';': the first is taken)."""
    parts = op_name.split(";")[0].split("/")
    for p in reversed(parts):
        if p in SCOPES or SITE.match(p):
            return p
    return UNSCOPED


def host_spans(path: str, window: Tuple[float, float]):
    """The benchmark's and the engine's host spans that overlap the window,
    as (name, start ns, end ns) sorted by start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                n = ev.name
                if n in xplane.HOST_SPANS or n.startswith(ENGINE):
                    a = ev.start_ns
                    b = a + ev.duration_ns
                    if b > window[0] and a < window[1]:
                        out.append((n, a, b))
    return sorted(out, key=lambda h: (h[1], -h[2]))


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def _module_pid(name: str) -> int:
    m = re.search(r"\((\d+)\)\s*$", name)
    return int(m.group(1)) if m else 0


def program_ops(trace: xplane.Trace, tl_index: int, program: str):
    """Indices of the non-container ops of one device that ran inside the
    window in a module of ``program``, with each op's program id."""
    ops, mods = trace.ops[tl_index], trace.modules[tl_index]
    lo, hi = trace.window
    sel = mods.select(xplane.NAMES[program])
    if not sel.any() or not len(ops.names):
        return np.zeros(0, int), []
    m_start, m_end = mods.start[sel], mods.end[sel]
    order = np.argsort(m_start)
    m_start, m_end = m_start[order], m_end[order]
    # program ids are unsigned 64-bit: kept as Python ints
    m_pid = [_module_pid(n) for n in np.asarray(mods.names, object)[sel][order]]
    j = np.searchsorted(m_start, ops.start, side="right") - 1
    ok = (j >= 0) & (ops.end > lo) & (ops.start < hi)
    ok &= ops.start < m_end[np.clip(j, 0, None)]
    idx = np.nonzero(ok)[0]
    container = {}
    for i in idx.tolist():
        n = ops.names[i]
        if n not in container:
            container[n] = bool(xplane.CONTAINER.match(n))
    idx = np.array([i for i in idx.tolist() if not container[ops.names[i]]],
                   int)
    return idx, [m_pid[k] for k in j[idx].tolist()]


def _scoper(names: Dict[Tuple[int, str], str]):
    """(program id, op name) -> scope, by the op's own program where the
    profile says which, else by any program holding an op of that name."""
    by_name = {n: s for (_, n), s in names.items()}
    memo: Dict[Tuple[int, str], str] = {}

    def scope(pid: int, name: str) -> str:
        key = (pid, name)
        if key not in memo:
            memo[key] = scope_of(names.get(key, by_name.get(name, "")))
        return memo[key]

    return scope


def split(trace: xplane.Trace, names: Dict[Tuple[int, str], str],
          program: str = "decode_chunk") -> Dict[str, float]:
    """Device seconds of ``program``'s ops in the window, by scope
    (averaged over the device planes that ran it)."""
    scope = _scoper(names)
    per_dev = []
    lo, hi = trace.window
    for d, ops in enumerate(trace.ops):
        idx, pids = program_ops(trace, d, program)
        if not len(idx):
            continue
        sec = (np.minimum(ops.end[idx], hi)
               - np.maximum(ops.start[idx], lo)) * 1e-9
        out: Dict[str, float] = {}
        for i, pid, s in zip(idx.tolist(), pids, sec.tolist()):
            k = scope(pid, ops.names[i])
            out[k] = out.get(k, 0.0) + s
        per_dev.append(out)
    if not per_dev:
        return {}
    keys = set().union(*per_dev)
    return {k: sum(p.get(k, 0.0) for p in per_dev) / len(per_dev)
            for k in keys}


def idle_by_span(trace: xplane.Trace, spans) -> Dict[str, float]:
    """Idle seconds of the first device in the window, each gap put down to
    the innermost host span covering its midpoint (else ``other``)."""
    if not trace.ops:
        return {}
    lo, hi = trace.window
    tl = trace.ops[0]
    keep = (tl.end > lo) & (tl.start < hi)
    busy = xplane.union(np.maximum(tl.start[keep], lo),
                        np.minimum(tl.end[keep], hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    a = np.asarray(edges[0::2], float)
    b = np.asarray(edges[1::2], float)
    gap = b > a
    a, b = a[gap], b[gap]
    mid = 0.5 * (a + b)
    owner = np.full(len(mid), -1)
    # spans sorted by start: an inner span, starting later, overwrites the
    # span around it
    for k, (_, s, e) in enumerate(spans):
        i0 = np.searchsorted(mid, s, side="right")
        i1 = np.searchsorted(mid, e, side="left")
        owner[i0:i1] = k
    out: Dict[str, float] = {}
    for k, sec in zip(owner.tolist(), ((b - a) * 1e-9).tolist()):
        name = spans[k][0] if k >= 0 else "other"
        out[name] = out.get(name, 0.0) + sec
    return out


def _path(ctx) -> Optional[str]:
    if ctx.get("xplane"):
        return ctx["xplane"]
    try:
        return xplane.find_xplane(TRACE_DIR)
    except FileNotFoundError:
        return None


def analyze(ctx) -> Dict[str, Any]:
    """The scope split of the decode program and the idle time by host span
    for a reader's ``ctx``, computed once per run and kept in it."""
    if "_scopes" not in ctx:
        out: Dict[str, Any] = {"decode": {}, "idle": {},
                               "scoped": False, "engine_spans": False}
        path = _path(ctx)
        try:
            if path is not None:
                with open(path, "rb") as f:
                    names = op_names(f.read())
                out["decode"] = split(ctx["trace"], names)
                out["scoped"] = any(k != UNSCOPED for k in out["decode"])
                spans = host_spans(path, ctx["trace"].window)
                out["engine_spans"] = any(s[0].startswith(ENGINE)
                                          for s in spans)
                out["idle"] = idle_by_span(ctx["trace"], spans)
        except Exception:  # noqa: BLE001 - a reader never fails a run
            print(f"bench: scopes: cannot read {path}", file=sys.stderr)
            traceback.print_exc()
            out.update(decode={}, idle={}, scoped=False, engine_spans=False)
        ctx["_scopes"] = out
    return ctx["_scopes"]


def decode_steps(ctx) -> int:
    return sum(c["steps"] for c in ctx["record"]["chunks"])


def top_ops(trace: xplane.Trace, names, program: str = "decode_chunk",
            top: int = 12) -> List[List[Any]]:
    """The first device's costliest ops of ``program`` in the window, each
    as ``"<name> [<scope>]"`` with its seconds."""
    if not trace.ops:
        return []
    ops = trace.ops[0]
    idx, pids = program_ops(trace, 0, program)
    lo, hi = trace.window
    scope = _scoper(names)
    tot: Dict[str, float] = {}
    for i, pid in zip(idx.tolist(), pids):
        n = ops.names[i]
        key = f"{xplane.short_name(n)} [{scope(pid, n)}]"
        tot[key] = tot.get(key, 0.0) + float(
            min(ops.end[i], hi) - max(ops.start[i], lo)) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            [:top]]


def report(path: str) -> Dict[str, Any]:
    trace = xplane.load(path)
    with open(path, "rb") as f:
        names = op_names(f.read())
    dec = split(trace, names)
    return {"window_s": trace.window_s,
            "decode_module_s": xplane.module_s(trace, "decode_chunk"),
            "decode_by_scope_s": dict(sorted(dec.items(),
                                             key=lambda kv: -kv[1])),
            "decode_top_ops": top_ops(trace, names),
            "idle_by_span_s": idle_by_span(
                trace, host_spans(path, trace.window))}


if __name__ == "__main__":
    print(json.dumps(report(sys.argv[1]), indent=1))
