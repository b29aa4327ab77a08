"""Reduce a profiler trace (``.xplane.pb``) to the intervals the metrics
read.

The device programs carry no named scopes, so programs and kernels are found
by the names XLA gives them, through the one table ``NAMES``: a jitted
function ``f`` runs as module ``jit_f(<id>)`` on the "XLA Modules" line of a
device plane, and each operation of it is an event on the "XLA Ops" line,
named by its whole HLO instruction.  A Pallas kernel appears there as a
``tpu_custom_call`` without its kernel's name; the paged decode-attention
kernel is the one whose outputs 1 and 2 alias operands 7 and 8 (the K and V
pools).
The benchmark's own ``jax.profiler.TraceAnnotation`` spans (the window and
the host calls inside it) sit on a host thread, on the same clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW_SPAN = "bench_window"
HOST_SPANS = ("admit", "decode_chunk", "wait")

# program or kernel -> pattern over an event's name and string stats
NAMES = {
    "decode_chunk": r"\bjit_chunk\b",
    "prefill": r"\bjit_pf\b",
    "insert": r"\bjit__insert_impl\b",
    "extend": r"\bjit__extend_impl\b",
    "paged_attention": (r'custom_call_target="tpu_custom_call".*'
                        r"output_to_operand_aliasing=\{\{1\}: \(7, \{\}\), "
                        r"\{2\}: \(8, \{\}\)\}"),
}
# control-flow ops whose events span the ops they run (left out of sums)
CONTAINER = re.compile(r"^%(while|conditional|call)[.\d]* = ")


def short_name(name: str) -> str:
    """An op's HLO name without its operands; kernels are marked."""
    head = name.split(" = ", 1)[0]
    return head + " (tpu_custom_call)" if "tpu_custom_call" in name else head


@dataclasses.dataclass
class Timeline:
    """Events of one line of one device plane, as parallel arrays."""
    names: List[str]
    start: np.ndarray  # ns
    end: np.ndarray
    labels: Dict[str, str]  # name -> its name and string stats, joined

    def select(self, pattern: str) -> np.ndarray:
        rx = re.compile(pattern)
        hit = {n for n, lab in self.labels.items() if rx.search(lab)}
        return np.array([n in hit for n in self.names], bool)


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]  # ns, trace clock
    ops: List[Timeline]  # one per device plane
    modules: List[Timeline]
    host: List[Tuple[str, float, float]]  # benchmark spans inside the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def _timeline(line) -> Timeline:
    names, start, end, labels = [], [], [], {}
    for ev in line.events:
        n = ev.name
        names.append(n)
        start.append(ev.start_ns)
        end.append(ev.start_ns + ev.duration_ns)
        if n not in labels:
            extra = [str(v) for _, v in ev.stats if isinstance(v, str)]
            labels[n] = " ".join([n] + extra)
    return Timeline(names, np.asarray(start, float), np.asarray(end, float),
                    labels)


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, host, window = [], [], [], None
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines:
                ops.append(_timeline(lines["XLA Ops"]))
                modules.append(_timeline(lines["XLA Modules"])
                               if "XLA Modules" in lines
                               else Timeline([], np.zeros(0), np.zeros(0),
                                             {}))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name in HOST_SPANS:
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    host = [h for h in host if h[2] > window[0] and h[1] < window[1]]
    return Trace(window, ops, modules, sorted(host, key=lambda h: h[1]))


def _clip(start, end, lo, hi):
    s, e = np.maximum(start, lo), np.minimum(end, hi)
    keep = e > s
    return s[keep], e[keep]


def union(start: np.ndarray, end: np.ndarray) -> List[Tuple[float, float]]:
    """Merged intervals of a set of [start, end)."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(zip(start.tolist(), end.tolist())):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_s(trace: Trace) -> Optional[float]:
    """Seconds in the window in which some operation ran, averaged over the
    device planes; None when the trace holds no device operation."""
    lo, hi = trace.window
    per_dev = []
    for tl in trace.ops:
        s, e = _clip(tl.start, tl.end, lo, hi)
        if len(s):
            per_dev.append(sum(b - a for a, b in union(s, e)) * 1e-9)
    return float(np.mean(per_dev)) if per_dev else None


def matched_s(timelines: List[Timeline], trace: Trace,
              pattern: str) -> Optional[float]:
    """Summed device seconds, inside the window, of the events matching
    ``pattern``, averaged over devices; None when nothing matches."""
    lo, hi = trace.window
    per_dev = []
    for tl in timelines:
        sel = tl.select(pattern)
        if sel.any():
            s, e = _clip(tl.start[sel], tl.end[sel], lo, hi)
            per_dev.append(float((e - s).sum()) * 1e-9)
    return float(np.mean(per_dev)) if per_dev else None


def module_s(trace: Trace, program: str) -> Optional[float]:
    return matched_s(trace.modules, trace, NAMES[program])


def op_s(trace: Trace, kernel: str) -> Optional[float]:
    return matched_s(trace.ops, trace, NAMES[kernel])


def breakdown(trace: Trace, top: int = 10) -> Dict[str, list]:
    """The device operations that took most time in the window, and the
    idle time of the first device summed by what the host was doing (the
    benchmark span that covers the gap's midpoint, else ``other``)."""
    lo, hi = trace.window
    totals: Dict[str, float] = {}
    if trace.ops:
        tl = trace.ops[0]
        s, e = tl.start, tl.end
        inside = (e > lo) & (s < hi)
        for n, a, b in zip(np.asarray(tl.names, object)[inside],
                           np.maximum(s[inside], lo),
                           np.minimum(e[inside], hi)):
            if CONTAINER.match(n):
                continue
            k = short_name(n)
            totals[k] = totals.get(k, 0.0) + float(b - a) * 1e-9
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    idle: Dict[str, float] = {}
    if trace.ops:
        s, e = _clip(trace.ops[0].start, trace.ops[0].end, lo, hi)
        busy = union(s, e)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        spans = trace.host  # sequential, sorted by start
        starts = [h[1] for h in spans]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            i = bisect.bisect_right(starts, mid) - 1
            what = spans[i][0] if i >= 0 and spans[i][2] > mid else "other"
            idle[what] = idle.get(what, 0.0) + float(b - a) * 1e-9
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, v] for n, v in ops],
            "idle_gaps": [[n, v] for n, v in gaps]}
