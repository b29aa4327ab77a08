"""The trace reduction on a small trace recorded on a TPU v5e: three calls of
a jitted program around the Pallas paged decode-attention kernel, each in a
``decode_chunk`` span followed by a 10 ms ``wait`` span, all inside the
``bench_window`` span."""
import os

import pytest
import xplane

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "tpu_paged_kernel.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return xplane.load(DATA)


def test_window_and_host_spans(trace):
    assert 0.03 < trace.window_s < 0.05
    names = [h[0] for h in trace.host]
    assert names.count("decode_chunk") == 3 and names.count("wait") == 3


def test_programs_and_kernel_are_found(trace):
    chunk = xplane.module_s(trace, "decode_chunk")
    kernel = xplane.op_s(trace, "paged_attention")
    busy = xplane.busy_s(trace)
    assert chunk and kernel and busy
    # the kernel runs inside the program, which is busy inside the window
    assert kernel <= chunk <= trace.window_s
    assert kernel <= busy <= trace.window_s
    assert kernel > 0.5 * chunk


def test_nothing_found_is_none(trace):
    assert xplane.module_s(trace, "prefill") is None
    assert xplane.matched_s(trace.ops, trace, "no_such_op") is None


def test_breakdown(trace):
    b = xplane.breakdown(trace)
    assert b["device_ops"][0][0].endswith("(tpu_custom_call)")
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    idle = dict(b["idle_gaps"])
    # the device idles through every wait
    assert idle["wait"] > 0.9 * 3 * 0.01
    total_idle = sum(idle.values())
    assert abs(total_idle - (trace.window_s - xplane.busy_s(trace))) < 1e-6


def test_union():
    import numpy as np

    iv = xplane.union(np.array([0.0, 1.0, 5.0]), np.array([2.0, 3.0, 6.0]))
    assert iv == [(0.0, 3.0), (5.0, 6.0)]
