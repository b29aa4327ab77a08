"""What a profile can see of the serve path: the named scopes that the
compiled decode program's op names carry, and the order of the engine's host
spans (``jax.profiler.TraceAnnotation``)."""
import re

import jax
import numpy as np
import pytest

from repro import configs
from repro.core import substrate as substrate_lib
from repro.core.imc_linear import IMCConfig
from repro.launch import serve
from repro.launch.serve import Engine, Request
from repro.models import init_params

TOP_SCOPES = {"block", "embed", "head", "sample"}
# one HLO instruction: its name, opcode and the op_name of its metadata
INSTR = re.compile(r'^\s*(?:ROOT )?%?(\S+) = .*? ([a-z][\w-]*)\(.*?'
                   r'op_name="([^"]*)"', re.M)


def _engine(mode: str):
    cfg = configs.get_smoke("musicgen-medium").replace(decode_attn="kernel")
    params = init_params(jax.random.PRNGKey(0), cfg)
    if mode != "digital":
        cfg = cfg.replace(imc=substrate_lib.as_substrate(
            IMCConfig(mode=mode, bx=7, bw=7)))
    return Engine(cfg, params, 2, 32, rng=None, max_chunk=2, block_size=8)


def _scopes(op_name: str):
    """The components of an op name (the first of XLA's merged names)."""
    return op_name.split(";")[0].split("/")


@pytest.fixture(scope="module", params=["digital", "imc_analytic"])
def decode_ops(request):
    text = _engine(request.param).compiled_decode_text(2)
    return request.param, INSTR.findall(text)


def test_every_matmul_sits_under_a_top_scope(decode_ops):
    _, ops = decode_ops
    matmuls = [(n, on) for n, opc, on in ops if opc in ("dot", "custom-call")]
    assert matmuls
    for name, op_name in matmuls:
        assert TOP_SCOPES & set(_scopes(op_name)), (name, op_name)


def test_layer_scopes_nest(decode_ops):
    _, ops = decode_ops
    paths = [_scopes(on) for _, _, on in ops]
    # the paged walk (the pure-JAX path off the TPU) runs inside attention
    walk = [p for p in paths if "paged_attention" in p]
    assert walk and all(p.index("block") < p.index("attn")
                        < p.index("paged_attention") for p in walk)
    for site in ("attn.wq", "mlp.wi"):
        assert any(site in p and p.index("block") < p.index(site)
                   for p in paths), site
    assert any("mlp" in p and "block" in p for p in paths)
    assert any("lm_head" in p and p.index("head") < p.index("lm_head")
               for p in paths)


def test_substrate_scope_only_off_digital(decode_ops):
    mode, ops = decode_ops
    imc = [p for p in (_scopes(on) for _, _, on in ops) if "imc" in p]
    if mode == "digital":
        assert not imc
    else:
        assert imc
        # inside a matmul's site scope, never around the dot itself
        assert all(re.fullmatch(r"[a-z_]+\.[a-z_0-9]+|lm_head",
                                p[p.index("imc") - 1]) for p in imc)
        dots = [_scopes(on) for _, opc, on in ops if opc == "dot"]
        assert any("attn.wq" in p and "imc" not in p for p in dots)


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: records every enter
    and exit with the span's arguments."""

    def __init__(self):
        self.events = []

    def __call__(self, name, **kwargs):
        rec = self

        class _Span:
            def __enter__(self):
                rec.events.append(("enter", name, kwargs))

            def __exit__(self, *exc):
                rec.events.append(("exit", name, {}))

        return _Span()


def _tree(events):
    """The recorded spans as (name, args, children), checking each exit
    closes the innermost open span."""
    root = ("", {}, [])
    stack = [root]
    for kind, name, args in events:
        if kind == "enter":
            node = (name, args, [])
            stack[-1][2].append(node)
            stack.append(node)
        else:
            assert stack[-1][0] == name, (name, stack[-1][0])
            stack.pop()
    assert len(stack) == 1
    return root[2]


def test_engine_spans_nest(monkeypatch):
    rec = _Recorder()
    monkeypatch.setattr(serve, "TraceAnnotation", rec)
    eng = _engine("digital")
    reqs = [Request(rid=7, prompt=np.arange(5) % 50, max_new=4),
            Request(rid=9, prompt=np.arange(3) % 50, max_new=4)]
    pending = list(reqs)
    assert len(eng.admit_pending(pending)) == 2
    eng.decode_chunk(2)
    admit, decode = _tree(rec.events)

    name, _, kids = admit
    assert name == "engine.admit"
    assert [k[0] for k in kids] == ["engine.group", "engine.prefill",
                                    "engine.insert", "engine.first_token"]
    args = {k[0]: k[1] for k in kids}
    assert args["engine.prefill"]["rows"] == 2
    assert args["engine.prefill"]["bucket"] >= 5
    # every span of the group names its requests, without commas (which
    # the profiler's argument encoding reserves)
    for span in ("engine.prefill", "engine.insert", "engine.first_token"):
        assert args[span]["rids"] == "7 9"

    name, _, kids = decode
    assert name == "engine.decode"
    assert [k[0] for k in kids] == ["engine.grow", "engine.dispatch",
                                    "engine.sync", "engine.deliver"]
    assert kids[1][1] == {"steps": 2, "active": 2}
    assert all(not k[2] for k in kids)
    assert [len(r.out) for r in reqs] == [3, 3]


def test_spans_reach_the_profile(tmp_path):
    """The engine's spans, with their arguments, are what a profile of it
    records on the host."""
    from jax.profiler import ProfileData

    eng = _engine("digital")
    # compile outside the profile
    eng.admit_pending([Request(rid=2, prompt=np.arange(4), max_new=3),
                       Request(rid=3, prompt=np.arange(3), max_new=3)])
    eng.decode_chunk(2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.admit_pending([Request(rid=4, prompt=np.arange(4), max_new=3),
                           Request(rid=5, prompt=np.arange(3), max_new=3)])
        eng.decode_chunk(2)
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    seen = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("engine."):
                        seen[ev.name] = dict(ev.stats)
    assert {"engine.admit", "engine.prefill", "engine.decode",
            "engine.dispatch", "engine.sync"} <= set(seen)
    assert seen["engine.prefill"]["rows"] == 2
    assert seen["engine.prefill"]["rids"] == "4 5"
    assert seen["engine.dispatch"]["steps"] == 2
