"""Online calibration (runtime.drift) + the hardened serve failure path.

Detector unit tests pin the one-sided superset test and its threshold
edges; engine tests pin the robustness contracts of ISSUE 6: bit-exact
chunk outputs across an atomic calibration hot-swap with zero recompiles,
passive shadow recording (outputs untouched), drift-injection detection +
SNR_T recovery within 1 dB of a fresh-frozen reference, and per-request
failure isolation (poison prefill, transient/persistent decode errors).
"""
import jax
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core.imc_linear import IMCConfig
from repro.core.substrate import (
    Calibration,
    SiteStats,
    as_substrate,
    calibrate_model,
)
from repro.launch.serve import Engine, Request, serve
from repro.models import init_params
from repro.runtime import fault as fault_lib
from repro.runtime.drift import (
    DriftConfig,
    DriftMonitor,
    DriftThresholds,
    detect_drift,
    effective_snr_t_db,
    estimated_clip_rate,
    refreshed_calibration,
    site_snr_table,
)

# ---------------------------------------------------------------------------
# detector unit tests (pure host-side, no jit)
# ---------------------------------------------------------------------------


def _cal(**sites):
    return Calibration(tuple(
        (name, SiteStats(*vals)) for name, vals in sites.items()))


FROZEN = _cal(**{"mlp.wi": (1.0, 2.0, 3.0), "attn.wq": (0.5, 1.0, 1.5),
                 "*": (1.0, 2.0, 3.0)})


def test_one_sided_superset_test():
    """observed <= frozen NEVER flags (running maxima: below-range traffic
    carries no evidence); observed > frozen does."""
    below = _cal(**{"mlp.wi": (0.5, 1.0, 1.5)})
    rep = detect_drift(FROZEN, below)
    assert not rep.drifted
    assert all(e.rel_excess == 0.0 for e in rep.entries)
    above = _cal(**{"mlp.wi": (2.0, 2.0, 3.0)})
    rep = detect_drift(FROZEN, above)
    assert rep.drifted
    assert rep.drifted_sites == ("mlp.wi",)
    (x_entry,) = [e for e in rep.entries if e.field == "x_max"]
    assert x_entry.drifted and x_entry.rel_excess == pytest.approx(1.0)
    # the other fields matched exactly: not drifted
    assert not any(e.drifted for e in rep.entries if e.field != "x_max")


def test_threshold_edges():
    """Strictly greater-than: a site sitting exactly at the threshold has
    not drifted; epsilon above it has."""
    thr = DriftThresholds(rel_excess=0.25, clip_rate=1.0)  # clip disabled
    at = _cal(**{"mlp.wi": (1.25, 2.0, 3.0)})  # rel excess exactly 0.25
    assert not detect_drift(FROZEN, at, thr).drifted
    above = _cal(**{"mlp.wi": (1.3125, 2.0, 3.0)})
    assert detect_drift(FROZEN, above, thr).drifted


def test_clip_rate_proxy():
    """The clip-rate backstop: Gaussian tail mass past the frozen range at
    the PAR assumption, monotone in the observed excess, and able to flag a
    site the rel-excess test was configured to ignore."""
    assert estimated_clip_rate(1.0, 0.5) < estimated_clip_rate(1.0, 1.0) \
        < estimated_clip_rate(1.0, 2.0)
    assert estimated_clip_rate(1.0, 0.5) < 1e-6  # over-provisioned: no clip
    thr = DriftThresholds(rel_excess=10.0, clip_rate=1e-3)  # rel disabled
    shifted = _cal(**{"mlp.wi": (1.5, 2.0, 3.0)})  # zeta_eff = 4/1.5 = 2.67
    rep = detect_drift(FROZEN, shifted, thr)
    assert rep.drifted
    (x_entry,) = [e for e in rep.entries if e.drifted]
    assert x_entry.field == "x_max" and x_entry.clip_rate > 1e-3


def test_unknown_site_checked_against_fallback():
    """An observed site the frozen calibration does not name is compared to
    the '*' entry (the stats the frozen engine actually serves it from); the
    '*' aggregate itself is skipped as a checked site."""
    obs = _cal(**{"new.site": (3.0, 2.0, 3.0), "*": (99.0, 99.0, 99.0)})
    rep = detect_drift(FROZEN, obs)
    assert rep.checked_sites == 1
    assert rep.drifted and rep.drifted_sites == ("new.site",)


def test_report_dict_shape():
    rep = detect_drift(FROZEN, _cal(**{"mlp.wi": (2.0, 2.0, 3.0)}))
    d = rep.to_dict()
    assert d["drifted"] is True
    assert d["drifted_sites"] == ["mlp.wi"]
    assert d["max_rel_excess"] == pytest.approx(1.0)
    assert all(e["drifted"] for e in d["entries"])
    assert "mlp.wi" in rep.summary_line()


# ---------------------------------------------------------------------------
# refresh: treedef preservation + monotonicity
# ---------------------------------------------------------------------------


def test_refreshed_preserves_treedef_and_is_monotone():
    obs = _cal(**{"mlp.wi": (2.5, 1.0, 9.0), "brand.new": (7.0, 7.0, 7.0)})
    ref = refreshed_calibration(FROZEN, obs)
    assert ref.site_names() == FROZEN.site_names()  # same pytree treedef
    _, td_frozen = jax.tree_util.tree_flatten(FROZEN)
    _, td_ref = jax.tree_util.tree_flatten(ref)
    assert td_frozen == td_ref
    for name, st in FROZEN.sites:
        for f in ("x_max", "w_max", "sigma_yo"):
            assert getattr(ref.get(name), f) >= getattr(st, f)
    # the drifted site took the observed max; the unknown site folded into *
    assert ref.get("mlp.wi").x_max == 2.5
    assert ref.get("*").x_max == 7.0


# ---------------------------------------------------------------------------
# analytic SNR_T proxy: degradation and recovery
# ---------------------------------------------------------------------------


def test_effective_snr_degrades_and_recovers():
    bx = 7
    fresh = effective_snr_t_db(1.0, 1.0, bx)
    stale = effective_snr_t_db(1.0, 2.0, bx)  # traffic 2x past the range
    assert stale < fresh - 3.0  # clipping costs real dB
    over = effective_snr_t_db(4.0, 1.0, bx)  # 4x over-provisioned range
    assert over == pytest.approx(fresh - 20 * np.log10(4.0), abs=0.2)
    # refresh to the observed max == the fresh-frozen reference exactly
    assert effective_snr_t_db(2.0, 2.0, bx) == pytest.approx(fresh)


def test_site_snr_table_recovery_gap():
    obs = _cal(**{"mlp.wi": (2.0, 2.0, 3.0)})
    ref = refreshed_calibration(FROZEN, obs)
    (row,) = [r for r in site_snr_table(FROZEN, ref, obs, bx=7)
              if r["site"] == "mlp.wi"]
    assert row["degradation_db"] > 3.0  # the stale range was clipping
    # drifted site: refreshed x_max == observed x_max -> exact recovery
    assert row["recovery_gap_db"] == pytest.approx(0.0, abs=1e-9)


# ---------------------------------------------------------------------------
# monitor cadence
# ---------------------------------------------------------------------------


def test_monitor_cadence():
    mon = DriftMonitor(DriftConfig(sample_every=2, check_every=3))
    pattern = [mon.take_sample() for _ in range(6)]
    assert pattern == [True, False, True, False, True, False]
    # checks fire every 3rd SAMPLE; with no observations they return None
    assert mon.check(FROZEN) is None and mon.check(FROZEN) is None
    assert mon.checks == 0
    mon.recorder.note("mlp.wi", SiteStats(9.0, 9.0, 9.0))
    assert mon.check(FROZEN) is not None  # third sample -> a check ran
    assert mon.checks == 1 and mon.drift_events == 1


def test_monitor_rejects_bad_cadence():
    with pytest.raises(ValueError):
        DriftConfig(sample_every=0)


# ---------------------------------------------------------------------------
# shared retry idiom (runtime.fault)
# ---------------------------------------------------------------------------


def _transient(msg="injected"):
    return jax.errors.JaxRuntimeError(msg)


def test_call_with_retries_transient_then_success():
    calls = []

    def fn():
        calls.append(1)
        if len(calls) == 1:
            raise _transient()
        return "ok"

    assert fault_lib.call_with_retries(
        fn, 1, retryable=fault_lib.is_transient_device_error) == "ok"
    assert len(calls) == 2


def test_call_with_retries_non_retryable_propagates():
    def fn():
        raise ValueError("bug")

    with pytest.raises(ValueError):
        fault_lib.call_with_retries(
            fn, 5, retryable=fault_lib.is_transient_device_error)


def test_call_with_retries_exhaustion():
    calls = []

    def fn():
        calls.append(1)
        raise _transient()

    with pytest.raises(jax.errors.JaxRuntimeError):
        fault_lib.call_with_retries(
            fn, 2, retryable=fault_lib.is_transient_device_error)
    assert len(calls) == 3


def test_is_transient_device_error():
    # the runtime's own device-error type, not a stand-in for it
    assert fault_lib.is_transient_device_error(jax.errors.JaxRuntimeError("x"))
    assert not fault_lib.is_transient_device_error(ValueError("x"))


# ---------------------------------------------------------------------------
# engine: atomic hot-swap, shadow passivity, drift injection, failure paths
# ---------------------------------------------------------------------------

TINY = dict(
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
    max_seq=128, flash_q_block=16, flash_kv_block=16, dtype="float32",
)
DENSE = ArchConfig(name="t", family="dense", **TINY)

_PARAMS = {}


def jax_params(cfg):
    key = id(cfg)
    if key not in _PARAMS:
        _PARAMS[key] = init_params(jax.random.PRNGKey(0), cfg)
    return _PARAMS[key]


def _frozen_cfg(mode, seed=1):
    cfg_dyn = DENSE.replace(imc=IMCConfig(mode=mode, bx=7, bw=7, v_wl=0.7))
    params = jax_params(DENSE)
    ref = np.random.default_rng(seed).integers(
        0, DENSE.vocab_size, (4, 24))
    cfg = calibrate_model(cfg_dyn, params, [ref])
    _PARAMS[id(cfg)] = params
    return cfg, params


def _requests(cfg, lens, max_new, seed=3):
    rnp = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rnp.integers(0, cfg.vocab_size, l),
                    max_new=max_new)
            for i, l in enumerate(lens)]


def _drive_chunks(engine, reqs, n_steps=2, swap_at=None, new_cal=None):
    """Admit everything, then decode in fixed-size chunks, optionally hot-
    swapping ``new_cal`` at the ``swap_at``-th chunk boundary.  Returns the
    list of (slots, n_steps) token blocks."""
    pending = [Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new)
               for r in reqs]
    engine.admit_pending(pending)
    assert not pending
    chunks = []
    while engine.active:
        if swap_at is not None and len(chunks) == swap_at:
            engine.swap_calibration(new_cal)
        chunks.append(engine.decode_chunk(n_steps).copy())
    return chunks


SWAP_MODES = ["fakequant", "imc_analytic", "imc_bitserial"]


@pytest.mark.parametrize("mode", SWAP_MODES)
def test_atomic_swap_bit_exact_no_recompile(mode):
    """The hot-swap contract on every quantized substrate: (a) a value-
    identical swap (rebuilt Calibration object, same stats) leaves every
    chunk bit-identical to the no-swap run; (b) a genuinely refreshed swap
    leaves all pre-swap chunks bit-identical; (c) neither swap triggers a
    recompile of the fused decode scan (the calibration is a traced
    argument, the jit cache is keyed on its treedef)."""
    cfg, params = _frozen_cfg(mode)
    lens, max_new = [5, 9], 7  # 1 prefill token + 3 decode chunks of 2
    reqs = _requests(cfg, lens, max_new)
    sub = as_substrate(cfg.imc)

    eng0 = Engine(cfg, params, batch_slots=2, cache_len=32, max_chunk=4)
    base = _drive_chunks(eng0, reqs)

    # (a) value-identical swap: a DIFFERENT Calibration object, same stats
    same_cal = Calibration.from_dict(sub.calibration.to_dict())
    assert same_cal is not sub.calibration
    eng1 = Engine(cfg, params, batch_slots=2, cache_len=32, max_chunk=4)
    swapped = _drive_chunks(eng1, reqs, swap_at=1, new_cal=same_cal)
    assert len(base) == len(swapped) and len(base) == 3
    for b, s in zip(base, swapped):
        np.testing.assert_array_equal(b, s)
    assert eng1.swap_count == 1

    # (b) + (c): a real refresh (one site's ranges grown 1.5x) - pre-swap
    # chunks identical, and the same compiled executable serves both
    grown = refreshed_calibration(
        sub.calibration,
        Calibration((("mlp.wi", SiteStats(
            1.5 * sub.calibration.get("mlp.wi").x_max,
            1.5 * sub.calibration.get("mlp.wi").w_max,
            1.5 * sub.calibration.get("mlp.wi").sigma_yo)),)))
    eng2 = Engine(cfg, params, batch_slots=2, cache_len=32, max_chunk=4)
    moved = _drive_chunks(eng2, reqs, swap_at=2, new_cal=grown)
    for b, s in zip(base[:2], moved[:2]):
        np.testing.assert_array_equal(b, s)
    fn = eng2._decode_fns[(2, False, eng2.substrate.trace_key)]
    if hasattr(fn, "_cache_size"):
        assert fn._cache_size() == 1  # swap never re-traced the scan


def test_swap_guards():
    """Swap requires a frozen substrate and a treedef-preserving refresh."""
    cfg, params = _frozen_cfg("imc_analytic")
    eng = Engine(cfg, params, batch_slots=2, cache_len=32, max_chunk=4)
    cal = as_substrate(cfg.imc).calibration
    smaller = Calibration(tuple(cal.sites[:-1]))
    with pytest.raises(ValueError, match="site-name"):
        eng.swap_calibration(smaller)
    dyn = Engine(DENSE, jax_params(DENSE), batch_slots=2, cache_len=32)
    with pytest.raises(ValueError, match="frozen"):
        dyn.swap_calibration(cal)
    with pytest.raises(ValueError, match="frozen"):
        Engine(DENSE, jax_params(DENSE), batch_slots=2, cache_len=32,
               drift_monitor=DriftMonitor())


def test_shadow_recording_is_passive():
    """Shadow-sampled chunks produce bit-identical outputs to unsampled ones
    (observation taps stats, never the execution path) and still deliver
    exactly one (slots, T) transfer per chunk."""
    cfg, params = _frozen_cfg("imc_analytic")
    reqs = _requests(cfg, [5, 9], 7)

    plain = Engine(cfg, params, batch_slots=2, cache_len=32, max_chunk=4)
    base = _drive_chunks(plain, reqs)

    mon = DriftMonitor(DriftConfig(sample_every=1, check_every=1,
                                   auto_swap=False))
    shadowed = Engine(cfg, params, batch_slots=2, cache_len=32, max_chunk=4,
                      drift_monitor=mon)
    got = _drive_chunks(shadowed, reqs)
    for b, s in zip(base, got):
        np.testing.assert_array_equal(b, s)
    assert mon.samples == len(got)
    jax.effects_barrier()
    observed = mon.recorder.finalize()
    assert observed.sites  # the shadow taps really ran
    assert shadowed.host_transfer_bytes == plain.host_transfer_bytes


def test_drift_injection_detected_and_recovered():
    """THE acceptance scenario: an activation-scale shift injected mid-serve
    is detected within a bounded number of chunks, hot-swapped without a
    recompile, and per-site SNR_T recovers to within 1 dB of a fresh-frozen
    reference; every request completes without error."""
    cfg, params = _frozen_cfg("imc_analytic")
    frozen0 = as_substrate(cfg.imc).calibration
    thr = DriftThresholds(rel_excess=0.5, clip_rate=0.05)
    mon = DriftMonitor(DriftConfig(sample_every=1, check_every=1,
                                   thresholds=thr))
    eng = Engine(cfg, params, batch_slots=2, cache_len=32, max_chunk=4,
                 drift_monitor=mon)

    serve(eng, _requests(cfg, [5, 9], 6, seed=3))
    assert mon.drift_events == 0  # calibrated traffic: no false positive
    chunks_before = mon.chunks_seen

    # inject a scale shift that SURVIVES pre-norm: growing every mlp.wi
    # weight 2.5x drifts w_max at mlp.wi and the activation range feeding
    # mlp.wo (an embed-scale shift would be normalized away)
    def _scale_wi(p, s):
        if isinstance(p, dict):
            return {k: (v * s if k == "wi" else _scale_wi(v, s))
                    for k, v in p.items()}
        return p

    eng.params = _scale_wi(eng.params, 2.5)
    n_decode_fns = len(eng._decode_fns)
    serve(eng, _requests(cfg, [5, 9], 6, seed=4))

    assert mon.drift_events >= 1 and eng.swap_count >= 1
    bound = mon.cfg.sample_every * mon.cfg.check_every + 1
    assert mon.first_drift_chunk - chunks_before <= bound
    assert len(eng._decode_fns) == n_decode_fns  # no new decode jits
    assert all(r.error is None for r in eng.finished)

    rows = site_snr_table(frozen0, eng._calib, mon.last_observed,
                          bx=as_substrate(cfg.imc).imc.bx)
    # drifted = observed EXCEEDED frozen (the one-sided direction); sites
    # whose frozen range merely over-provisions traffic carry a static
    # q-noise gap that is calibration conservatism, not drift
    drifted = [r for r in rows if r["x_max_observed"] > r["x_max_frozen"]]
    assert any(r["degradation_db"] > 1.0 for r in drifted)
    for r in drifted:
        assert abs(r["recovery_gap_db"]) <= 1.0, r


def test_poison_prefill_isolated():
    """A poison request in a batched prefill group errors out ALONE: the
    batch retries solo, the poison row retires with an error status, and
    its group-mates are served (failure isolation, never engine death)."""
    cfg = DENSE
    poison_rid = 1

    def injector(phase, info):
        if phase == "prefill" and poison_rid in info:
            raise _transient(f"poisoned rid {poison_rid}")

    eng = Engine(cfg, jax_params(cfg), batch_slots=4, cache_len=32,
                 max_chunk=4, failure_injector=injector)
    reqs = _requests(cfg, [5, 6, 7], 4)  # one bucket: one batched group
    out = {r.rid: r for r in serve(eng, reqs)}
    assert out[poison_rid].error is not None
    for rid in (0, 2):
        assert out[rid].error is None and len(out[rid].out) == 4
    assert eng.alloc.used_count == 0  # nothing leaked
    assert eng.failed_requests == 1


def test_transient_decode_error_retried_exactly():
    """A single transient decode fault is retried via the shared fault
    idiom; the chunk function is pure, so the re-run is exact and the
    served tokens are bit-identical to a fault-free run."""
    cfg = DENSE
    reqs = _requests(cfg, [5, 9], 6)
    clean = Engine(cfg, jax_params(cfg), batch_slots=2, cache_len=32,
                   max_chunk=4)
    want = {r.rid: r.out for r in serve(
        clean, [Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new)
                for r in reqs])}

    hits = []

    def injector(phase, info):
        if phase == "decode" and info == 0 and not hits:
            hits.append(1)
            raise _transient("blip")

    eng = Engine(cfg, jax_params(cfg), batch_slots=2, cache_len=32,
                 max_chunk=4, failure_injector=injector)
    got = {r.rid: r.out for r in serve(
        eng, [Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new)
              for r in reqs])}
    assert hits  # the fault really fired
    assert got == want
    assert eng.decode_failures == 0
    assert all(r.error is None for r in eng.finished)


def test_persistent_decode_error_fails_only_inflight():
    """A decode fault that survives the retry fails exactly the in-flight
    requests; the engine itself survives and serves new traffic."""
    cfg = DENSE
    boom = {"on": True}

    def injector(phase, info):
        if phase == "decode" and boom["on"]:
            raise _transient("dead lane")

    eng = Engine(cfg, jax_params(cfg), batch_slots=2, cache_len=32,
                 max_chunk=4, failure_injector=injector)
    out = serve(eng, _requests(cfg, [5, 9], 6))
    assert len(out) == 2
    assert all(r.done and r.error is not None for r in out)
    assert eng.decode_failures >= 1
    assert eng.alloc.used_count == 0

    boom["on"] = False  # the fault clears: same engine keeps serving
    fresh = _requests(cfg, [7], 4, seed=9)
    out2 = serve(eng, fresh)
    assert out2[-1].error is None and len(out2[-1].out) == 4
