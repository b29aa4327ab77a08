"""The window arithmetic on constructed timelines, with and without a
stall: tok_s and both tails must move when the server stalls."""
import window
from window import Stamp

T0, T1 = 100.0, 110.0


def _timeline(stall: float):
    """Ten open-loop requests due every second; each gets its first token
    0.1 s after it is due and then 8 tokens every 0.2 s, 4 deliveries.  A
    stall of ``stall`` seconds at t = 104.5 delays everything after it."""
    stamps = []
    for i in range(10):
        due = T0 + i
        s = Stamp(rid=i, due=due)
        t = due + 0.1
        s.deliveries.append((t + (stall if t >= 104.5 else 0.0), 1, due))
        for _ in range(4):
            a = t
            t += 0.2
            late = stall if t >= 104.5 else 0.0
            s.deliveries.append((t + late, 8, a + late))
        stamps.append(s)
    return stamps


def test_no_stall_values():
    w = window.summarize(_timeline(0.0), T0, T1)
    assert w["ttft_samples"] == 10
    assert abs(w["ttft_p90_ms"] - 100.0) < 1e-6
    # (4 x 0.2 s) over the 32 tokens after the first delivery
    assert abs(w["tpot_p90_ms"] - 1e3 * 0.8 / 32) < 1e-6
    # the last request's last chunk ends 0.9 s past the window: 10.9 - 10.1
    # of its 8 tokens' 0.2 s fall inside - all of them, since 110.0 is past
    assert abs(w["tok_s"] - 33.0) < 1.0


def test_stall_moves_every_metric():
    calm = window.summarize(_timeline(0.0), T0, T1)
    stalled = window.summarize(_timeline(3.0), T0, T1)
    assert stalled["tok_s"] < calm["tok_s"]
    assert stalled["ttft_p90_ms"] > calm["ttft_p90_ms"]
    assert stalled["tpot_p90_ms"] > calm["tpot_p90_ms"]


def test_waiting_request_counts_its_wait():
    s = Stamp(rid=0, due=T1 - 2.0)  # never served
    assert window.ttfts([s], T0, T1) == [2.0]
    # due outside the window: not a sample
    assert window.ttfts([Stamp(rid=1, due=T0 - 1.0)], T0, T1) == []


def test_closed_loop_has_no_ttft():
    s = Stamp(rid=0)
    s.deliveries += [(101.0, 1, 100.5), (102.0, 8, 101.0), (120.0, 8, 102.0)]
    w = window.summarize([s], T0, T1)
    assert w["ttft_p90_ms"] is None and w["ttft_samples"] == 0
    # all of the first two; 8 of the 18 s of the third call fall inside
    assert abs(w["tok_s"] - (9 + 8 * 8 / 18) / 10.0) < 1e-9


def test_straddling_chunk_counts_its_share():
    s = Stamp(rid=0)
    s.deliveries.append((T0 + 0.5, 8, T0 - 0.5))  # half inside
    assert abs(window.tok_s([s], T0, T1) - 4 / 10.0) < 1e-9
