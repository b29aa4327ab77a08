"""The scope and span reduction (``scopes.py``) and the readers built on it:
on synthetic traces with nested host spans, on the scoped trace recorded on a
TPU v5e by ``record_fixture.py``, and on the older unscoped trace, where they
find nothing."""
import json
import os

import numpy as np
import pytest
import scopes
import spec
import xplane

DATA = os.path.join(os.path.dirname(__file__), "data")
SCOPED = os.path.join(DATA, "tpu_scoped_decode.xplane.pb")
UNSCOPED_TRACE = os.path.join(DATA, "tpu_paged_kernel.xplane.pb")
LOOP = "jit(chunk)/while/body/closed_call/while/body/closed_call"


@pytest.mark.parametrize("op_name,scope", [
    (f"{LOOP}/block/attn/attn.wq/imc/mul", "imc"),
    (f"{LOOP}/block/attn/attn.wq/...k,km->...m", "attn.wq"),
    (f"{LOOP}/block/attn/paged_attention/pallas_call", "paged_attention"),
    (f"{LOOP}/block/attn/squeeze;block/attn", "attn"),
    (f"{LOOP}/block/mlp/mlp.wi/imc/jit(clip)/max", "imc"),
    (f"{LOOP}/block/jit(_var)/reduce_sum", "block"),
    ("jit(chunk)/while/body/closed_call/head/lm_head/dot_general", "lm_head"),
    ("jit(chunk)/while/body/closed_call/sample/jit(_where)/select_n",
     "sample"),
    ("jit(chunk)/while/body/closed_call/while/body/dynamic_slice",
     "unscoped"),
    ("jit(chunk)/jit(_where)/select_n", "unscoped"),
    ("", "unscoped"),
])
def test_scope_of(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def _timeline(events):
    names = [e[0] for e in events]
    return xplane.Timeline(names, np.array([e[1] for e in events], float),
                           np.array([e[2] for e in events], float),
                           {n: n for n in names})


def _trace(ops, modules=(), host=()):
    return xplane.Trace((0.0, 1000.0), [_timeline(ops)],
                        [_timeline(modules)], list(host))


FUSION = "%fusion.1 = f32[8] fusion(f32[8] %p), calls=%fc"
COPY = "%copy.2 = bf16[4,8] copy(bf16[4,8] %q)"
WHILE = "%while.3 = (s32[]) while((s32[]) %t), body=%b"


def test_split_by_scope_within_the_program():
    trace = _trace(
        ops=[(WHILE, 100, 500), (FUSION, 110, 150), (COPY, 150, 230),
             (FUSION, 400, 420), (FUSION, 720, 800)],
        modules=[("jit_chunk(7)", 100, 500), ("jit_pf(8)", 700, 900)])
    names = {(7, FUSION): f"{LOOP}/block/attn/attn.wq/imc/mul",
             # the same op text in another program keeps that one's name
             (8, FUSION): f"{LOOP}/block/mlp/mlp.wi/dot_general",
             (7, COPY): ""}
    got = scopes.split(trace, names)
    assert got == pytest.approx({"imc": 60e-9, "unscoped": 80e-9})
    assert scopes.split(trace, names, "prefill") == pytest.approx(
        {"mlp.wi": 80e-9})
    assert scopes.split(trace, names, "insert") == {}


def test_idle_goes_to_the_innermost_span():
    # busy [0,100) [200,300) [500,600) [700,950); idle gaps with midpoints
    # 150, 400, 650 and 975
    trace = _trace(ops=[("a", 0, 100), ("b", 200, 300), ("c", 500, 600),
                        ("d", 700, 950)])
    spans = sorted([("decode_chunk", 50, 460), ("engine.decode", 50, 450),
                    ("engine.sync", 120, 180), ("engine.deliver", 350, 440),
                    ("wait", 610, 690)], key=lambda h: (h[1], -h[2]))
    got = scopes.idle_by_span(trace, spans)
    assert got == pytest.approx({"engine.sync": 100e-9,
                                 "engine.deliver": 200e-9, "wait": 100e-9,
                                 "other": 50e-9})
    assert sum(got.values()) == pytest.approx(
        trace.window_s - xplane.busy_s(trace))


def _ctx(path, chunks):
    return {"trace": xplane.load(path), "xplane": path,
            "record": {"chunks": chunks}}


def _read(metric, ctx):
    return spec.reader(metric)(ctx)


def test_unscoped_program_reads_nothing():
    """A program from before the scopes and spans: the readers find
    nothing, and say so with None."""
    ctx = _ctx(UNSCOPED_TRACE, [{"steps": 1}] * 3)
    for metric in ("unscoped_ms.render", "substrate_ms.render",
                   "host_turn_ms.render"):
        assert _read(metric, ctx) is None, metric
    missing = {"trace": ctx["trace"], "record": ctx["record"],
               "xplane": os.path.join(DATA, "no_such.xplane.pb")}
    assert _read("unscoped_ms.render", missing) is None


# --- the trace recorded on a TPU v5e (record_fixture.py): three 4-step
# decode calls of a 2-layer imc_analytic engine, each in ``decode_chunk``
# then a 5 ms ``wait``, inside ``bench_window``

@pytest.fixture(scope="module")
def scoped():
    with open(SCOPED.replace(".xplane.pb", ".json")) as f:
        record = json.load(f)
    return _ctx(SCOPED, record["chunks"])


def test_readers_on_the_recorded_trace(scoped):
    # computed by hand from the profile's events and the op names of the
    # program's optimized HLO (tpu_scoped_decode.hlo.txt), 12 steps and 3
    # decode calls: unscoped ops 64.524 us, imc ops 158.083 us; device idle
    # inside engine.grow 2878.925 us and engine.dispatch 0.021 us
    ctx = dict(scoped)
    assert _read("unscoped_ms.render", ctx) == pytest.approx(64.524e-3 / 12)
    assert _read("substrate_ms.render", ctx) == pytest.approx(158.083e-3 / 12)
    assert _read("host_turn_ms.render", ctx) == pytest.approx(
        (2878.925 + 0.021) * 1e-3 / 3)
    idle = scopes.analyze(ctx)["idle"]
    assert set(idle) == {"engine.grow", "engine.dispatch", "wait", "other"}
    # the benchmark's older readers still find the program and the kernel
    assert _read("decode_step_ms.render", ctx) > 0
    assert xplane.op_s(ctx["trace"], "paged_attention") > 0


def test_profile_names_match_the_compiled_program(scoped):
    """Each op's name in the profile gives the scope that its instruction's
    op_name in the compiled program gives; the split accounts for every op
    of the program's time."""
    import re

    with open(SCOPED.replace(".xplane.pb", ".hlo.txt")) as f:
        hlo = {m.group(1): m.group(2) for m in re.finditer(
            r'^\s*(?:ROOT )?%?(\S+) = [^\n]*?op_name="([^"]*)"', f.read(),
            re.M)}
    trace = scoped["trace"]
    with open(SCOPED, "rb") as f:
        names = scopes.op_names(f.read())
    idx, pids = scopes.program_ops(trace, 0, "decode_chunk")
    ops = trace.ops[0]
    seen = set()
    for i, pid in zip(idx.tolist(), pids):
        n = ops.names[i]
        inst = n.split(" = ", 1)[0].lstrip("%")
        assert (scopes.scope_of(names.get((pid, n), ""))
                == scopes.scope_of(hlo.get(inst, ""))), inst
        seen.add(scopes.scope_of(hlo.get(inst, "")))
    assert {"block", "attn.wq", "mlp.wi", "imc", "paged_attention", "embed",
            "head", "lm_head", "sample", "unscoped"} <= seen
    lo, hi = trace.window
    op_time = float((np.minimum(ops.end[idx], hi)
                     - np.maximum(ops.start[idx], lo)).sum()) * 1e-9
    assert sum(scopes.split(trace, names).values()) == pytest.approx(op_time)
    # at this toy size the gaps between ops are a fifth of the program's time
    assert op_time < xplane.module_s(trace, "decode_chunk")
