"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration file and a traffic mix
file.  One run:

1. refuses any device but a TPU, and fewer chips than the cell asks for
   (exit 2, no result);
2. makes the weights on the device from ``--seed`` (``weights.py``), freezes
   the substrate's calibration where the configuration asks for it (as
   ``repro.launch.serve.main --imc-policy frozen`` does), and builds
   ``repro.launch.serve.Engine``;
3. warms up every program shape the cell's traffic can use - each prefill
   and insert (rows x bucket), each decode chunk length, each block-table
   extend - served from the persistent compile cache after a checkout's
   first run;
4. fills the engine (closed loop: every slot busy; open loop: the mix's
   pre-roll of arrivals), then opens the window and drives
   ``Engine.admit_pending`` / ``Engine.decode_chunk`` from a wall-clock
   loop for ``--seconds``;
5. reads the peak device memory, frees the engine, and compares a sample of
   the served tokens with the plain float32 reference (``reference.py``);
6. prints one JSON line: ``--trace 0`` gives the end-to-end metrics,
   ``--trace 1`` profiles the window and gives the per-layer metrics.

``setup_s`` runs from the start of this script to the window opening.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import zlib  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import flops  # noqa: E402
import numpy as np  # noqa: E402
import spec  # noqa: E402
import traffic as traffic_lib  # noqa: E402
import window as window_lib  # noqa: E402

OUT_DIR = os.path.join(ROOT, ".bench_out")


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class NoChip(SystemExit):
    pass


def devices_or_exit(chips: int, require_tpu: bool = True):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        log(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform!r} device(s) - no result")
        raise NoChip(2)
    return devs[:chips]


def use_compile_cache() -> str:
    """The persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when set,
    else the fixed ``<checkout>/.jax_cache``; every program is kept."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Counts the backend compiles JAX reports and their seconds (a
    persistent-cache hit reports its retrieval instead)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def _listen(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listen)


# ---------------------------------------------------------------------------
# building the system under test
# ---------------------------------------------------------------------------


def pow2s(limit: int) -> List[int]:
    out, p = [], 1
    while p <= limit:
        out.append(p)
        p *= 2
    return out


def slots_for(conf: Dict[str, Any], mix: Dict[str, Any]) -> int:
    """Slots that the configuration's KV budget holds at the mix's longest
    request, fully provisioned (no preemption can happen)."""
    eng = conf["engine"]
    blocks = -(-traffic_lib.max_positions(mix) // eng["block"])
    per_slot = blocks * eng["block"] * flops.kv_bytes_per_token(conf["model"])
    return max(1, min(eng["max_slots"], eng["kv_pool_bytes"] // per_slot))


def arch_config(conf: Dict[str, Any]):
    from repro import configs

    return configs.get(conf["arch"]).replace(decode_attn="kernel",
                                             **conf["model"])


def with_substrate(cfg, conf: Dict[str, Any], params):
    """Install the configuration's substrate; a frozen policy calibrates on
    a fixed reference batch and runs without the analog-noise RNG, as
    ``serve.main --imc-policy frozen`` does."""
    sub = conf["substrate"]
    if sub["mode"] == "digital":
        return cfg
    from repro.core import substrate as substrate_lib
    from repro.core.imc_linear import IMCConfig

    if sub["policy"] != "frozen":
        raise ValueError("only frozen substrates are benchmarked (the "
                         "dynamic policy's noise RNG is batch-shaped)")
    cfg = cfg.replace(imc=substrate_lib.as_substrate(IMCConfig(
        mode=sub["mode"], bx=sub["bx"], bw=sub["bw"], v_wl=sub["v_wl"])))
    ref = np.random.default_rng(1).integers(
        0, cfg.vocab_size, tuple(sub["calibration_batch"]))
    return substrate_lib.calibrate_model(cfg, params, [ref])


def build_engine(conf, mix, params):
    from repro.launch.metering import DPMeter
    from repro.launch.serve import Engine

    cfg = with_substrate(arch_config(conf), conf, params)
    eng = conf["engine"]
    meter = DPMeter(cfg)
    engine = Engine(cfg, params, slots_for(conf, mix),
                    traffic_lib.max_positions(mix), rng=None,
                    max_chunk=eng["max_chunk"], block_size=eng["block"],
                    kv_blocks=None, meter=meter,
                    prefix_cache=eng.get("prefix_cache", False))
    return engine, meter


def warm_up(engine, draws, rows_max: int) -> Dict[str, int]:
    """Run every program shape the traffic can reach, with dummy rows that
    write only to the garbage block and leave the engine's state as it was:
    the decode chunk at each length (first: its temporaries are the largest
    reservation), prefill and insert for each (rows, bucket), the
    block-table extend at each padded size a chunk can ask for: every slot
    may cross ``ceil(max_chunk / block) + 1`` block boundaries in one."""
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import prefill_bucket

    tk = engine.substrate.trace_key
    n = engine.batch_slots
    buckets = sorted({prefill_bucket(len(d.prompt), engine.bucketable,
                                     engine.cache_len) for d in draws})
    idle = jnp.asarray(np.zeros((n,), bool))
    for t in pow2s(engine.max_chunk):
        key = (t, False, tk)
        if key not in engine._decode_fns:
            engine._decode_fns[key] = engine._make_decode(t)
        jax.block_until_ready(engine._decode_fns[key](
            engine.params, engine.cache, engine.last_token, engine.pos, idle,
            None, engine._calib))
    for bucket in buckets:
        for r in pow2s(rows_max):
            key = (r, bucket, False, tk)
            if key not in engine._prefill_fns:
                engine._prefill_fns[key] = engine._make_prefill()
            true_len = jnp.asarray(np.full((r,), bucket, np.int32))
            tok0, cache1 = engine._prefill_fns[key](
                engine.params, jnp.asarray(np.zeros((r, bucket), np.int32)),
                true_len, None, engine._calib)
            out = engine._insert_fn(
                engine.cache, {k: v for k, v in cache1.items() if k != "pos"},
                jnp.asarray(np.full((r,), n, np.int32)),
                jnp.asarray(np.zeros((r, engine.max_blocks), np.int32)),
                tok0, true_len, engine.last_token, engine.pos)
            jax.block_until_ready(out)
            del out, cache1
    most = n * (-(-engine.max_chunk // engine.block) + 1)
    extends = pow2s(1 << (most - 1).bit_length())
    for k in extends:
        jax.block_until_ready(engine._extend_fn(
            engine.cache, jnp.asarray(np.full((k,), n, np.int32)),
            jnp.asarray(np.zeros((k,), np.int32)),
            jnp.asarray(np.zeros((k,), np.int32))))
    return {"buckets": len(buckets), "prefill_rows": len(pow2s(rows_max)),
            "decode_lengths": len(pow2s(engine.max_chunk)),
            "extend_sizes": len(extends)}


# ---------------------------------------------------------------------------
# the wall-clock loop
# ---------------------------------------------------------------------------


class Driver:
    """Drives the engine as ``serve.serve`` does, stamping every delivery on
    the host clock.  Admission hands the engine at most ``rows_max``
    requests per call, so a prefill group never exceeds the warmed rows."""

    def __init__(self, engine, draws, rows_max: int, trace: bool):
        from repro.launch.serve import Request

        self.engine = engine
        self.rows_max = rows_max
        self.trace = trace
        self.reqs = [Request(rid=d.rid, prompt=d.prompt, max_new=d.max_new)
                     for d in draws]
        self.stamps = {d.rid: window_lib.Stamp(d.rid) for d in draws}
        self.seen: Dict[int, int] = {}
        self.inflight: Dict[int, Any] = {}
        self.queue: List[Any] = []
        self.chunks: List[Dict[str, Any]] = []  # per decode call
        self.admits: List[Dict[str, Any]] = []  # per admission call
        self.lag: List[float] = []
        self.backlog: List[Any] = []  # open loop: (time, requests queued)
        self.next_due = 0  # open loop: index of the next arrival

    def _span(self, name):
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def _observe(self, t: float, start: float) -> None:
        for rid, req in list(self.inflight.items()):
            d = len(req.out) - self.seen[rid]
            if d > 0:
                self.stamps[rid].deliveries.append((t, d, start))
                self.seen[rid] = len(req.out)
            if req.done:
                del self.inflight[rid]

    def admit(self) -> None:
        eng = self.engine
        t_start = time.perf_counter()
        got = []
        with self._span("admit"):
            while self.queue and eng.active < eng.batch_slots:
                head = self.queue[: self.rows_max]
                n0 = len(head)
                got += eng.admit_pending(head)
                taken = n0 - len(head)
                if taken == 0:
                    break
                for req in self.queue[:taken]:
                    self.seen.setdefault(req.rid, 0)
                    self.inflight[req.rid] = req
                del self.queue[:taken]
        t = time.perf_counter()
        if got:
            self.admits.append({"t0": t_start, "t1": t,
                                "lens": [len(r.prompt) for r in got]})
        self._observe(t, t_start)

    def decode(self) -> None:
        eng = self.engine
        n_steps = eng.next_chunk()
        pos = [len(r.prompt) + len(r.out) - 1 for r in eng.slots
               if r is not None]
        t_start = time.perf_counter()
        with self._span("decode_chunk"):
            eng.decode_chunk(n_steps)
        t = time.perf_counter()
        self.chunks.append({"t0": t_start, "t1": t, "steps": n_steps,
                            "pos": pos})
        if eng.preempted:
            self.queue[:0] = eng.preempted
            eng.preempted.clear()
        self._observe(t, t_start)

    def wait(self, until: float) -> None:
        with self._span("wait"):
            time.sleep(max(0.0, until - time.perf_counter()))

    # -- loops ----------------------------------------------------------------
    def fill_closed(self) -> None:
        self.queue = list(self.reqs)
        while self.engine.active < self.engine.batch_slots and self.queue:
            self.admit()

    def run_closed(self, t1: float) -> None:
        while time.perf_counter() < t1:
            self.admit()
            if self.engine.active:
                self.decode()
            elif not self.queue:
                raise RuntimeError("closed-loop queue ran dry; raise the "
                                   "mix's 'requests'")

    def run_open(self, origin: float, due: List[float], t1: float) -> None:
        """Serve the schedule from ``origin`` until ``t1``; requests enter
        the queue once due (the lag is how late the loop saw them)."""
        i = self.next_due
        while True:
            now = time.perf_counter()
            if now >= t1:
                return
            while i < len(due) and origin + due[i] <= now:
                self.queue.append(self.reqs[i])
                self.stamps[self.reqs[i].rid].due = origin + due[i]
                self.lag.append(now - (origin + due[i]))
                i += 1
                self.next_due = i
            self.backlog.append((now, len(self.queue)))
            if self.queue and self.engine.active < self.engine.batch_slots:
                self.admit()
            if self.engine.active:
                self.decode()
            elif not self.queue:
                nxt = origin + due[i] if i < len(due) else t1
                self.wait(min(nxt, t1))


# ---------------------------------------------------------------------------
# correctness: the served tokens against the float32 reference
# ---------------------------------------------------------------------------


def sample_for_check(reqs, served_rids, seed: int, tokens: int,
                     max_requests: int):
    """The request with the most served tokens, then others drawn from the
    seed, until ``tokens`` served tokens or ``max_requests`` requests."""
    pool = sorted((r for r in reqs if r.rid in served_rids and r.out),
                  key=lambda r: r.rid)
    if not pool:
        return []
    longest = max(pool, key=lambda r: (len(r.out), -r.rid))
    rest = [r for r in pool if r is not longest]
    order = np.random.default_rng([seed, 7]).permutation(len(rest))
    chosen, total = [longest], len(longest.out)
    for j in order:
        if total >= tokens or len(chosen) >= max_requests:
            break
        chosen.append(rest[j])
        total += len(rest[j].out)
    return chosen


def logit_gaps(params, model, chosen, control: Optional[str] = None):
    """The widest gap by which a checked token's float32 reference logit
    lies below the reference's best.  The checked tokens are the served
    ones; with ``control``, the control's tokens at the same positions
    (``reference.served_gaps``) take their place, and the served tokens'
    own reading is kept beside as ``program_gap``."""
    import reference

    each = []
    for r in chosen:
        g = reference.served_gaps(params, model, r.prompt, r.out, control)
        each.append([len(r.out), float(g["gap"].max()),
                     float(g["control_gap"].max()) if control else None])
    served = max((e[1] for e in each), default=0.0)
    worst = served if control is None else max(e[2] for e in each)
    return {"max_logit_gap": worst, "program_gap": served,
            "tokens": sum(e[0] for e in each), "requests": len(chosen),
            "per_request": each}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def per_layer(cell, ctx) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in cell["per_layer"]:
        value = spec.reader(m["name"])(ctx)
        if value is None:
            log(f"per-layer metric {m['name']} found nothing to read; "
                "left out")
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, control: Optional[str] = None,
             fault=None) -> Dict[str, Any]:
    """One run of a cell; returns the result object.  ``control`` (a
    precision of ``reference.py``) puts the lower-precision reference in the
    program's place for the check that decides ``correct``; ``fault`` (a
    callable taking the engine) breaks the timed path, for the self-tests."""
    import jax

    conf, mix = cell["config"], cell["traffic"]
    devs = devices_or_exit(cell["workload"]["chips"], require_tpu)
    cache_dir = use_compile_cache()
    timings: Dict[str, float] = {}
    with CompileClock() as compiles:
        import weights

        t = time.perf_counter()
        params = weights.make_params(conf["model"], seed)
        jax.block_until_ready(params)
        timings["weights_s"] = time.perf_counter() - t
        t = time.perf_counter()
        engine, meter = build_engine(conf, mix, params)
        timings["engine_s"] = time.perf_counter() - t
        if fault is not None:
            fault(engine)
        draws = traffic_lib.generate(mix, conf["model"]["vocab_size"], seed,
                                     seconds)
        rows_max = conf["engine"]["prefill_rows_max"]
        t = time.perf_counter()
        warmed = warm_up(engine, draws, rows_max)
        timings["warm_s"] = time.perf_counter() - t
        setup_compiles = (compiles.count, compiles.seconds)

        drv = Driver(engine, draws, rows_max, trace)
        t = time.perf_counter()
        if mix["loop"] == "closed":
            drv.fill_closed()
            t0 = time.perf_counter()
        else:
            origin = time.perf_counter()
            t0 = origin + mix["preroll_s"]
            drv.run_open(origin, [d.due for d in draws], t0)
            t0 = time.perf_counter()
        timings["fill_s"] = t0 - t
        setup_s = t0 - T_PROCESS
        trace_dir = os.path.join(OUT_DIR, "trace")
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            t0 = time.perf_counter()
        t1 = t0 + seconds
        counters0 = engine_counters(engine)
        span = drv._span("bench_window")
        span.__enter__()
        if mix["loop"] == "closed":
            drv.run_closed(t1)
        else:
            drv.run_open(origin, [d.due for d in draws], t1)
        span.__exit__(None, None, None)
        t_end = time.perf_counter()
        window_compiles = compiles.count - setup_compiles[0]
    if trace:
        jax.profiler.stop_trace()

    stats = devs[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    stamps = list(drv.stamps.values())
    served = {s.rid for s in stamps
              if any(t0 <= t <= t1 for t, _, _ in s.deliveries)}
    if mix["loop"] == "open":
        attempted = {s.rid for s in stamps
                     if s.due is not None and t0 <= s.due < t1}
    else:
        attempted = served | {r.rid for r in drv.inflight.values()}
    failed = [r for r in drv.reqs
              if r.rid in attempted and r.error is not None]
    e2e = window_lib.summarize(stamps, t0, t1)
    info = {
        "workload": cell["workload"]["name"], "seed": seed,
        "slots": engine.batch_slots, "kv_blocks": engine.alloc.num_blocks,
        "cache_len": engine.cache_len, "warmed": warmed,
        "timings": timings, "setup_compiles": setup_compiles[0],
        "setup_compile_s": setup_compiles[1],
        "window_compiles": window_compiles,
        "requests_sent": len(attempted), "requests_finished": sum(
            1 for r in drv.reqs if r.rid in attempted and r.done),
        "requests_failed": len(failed),
        "generator_lag_p99_ms": (1e3 * float(np.percentile(drv.lag, 99))
                                 if drv.lag else None),
        "backlog_at_close": len(drv.queue),
        "backlog_by_quarter": [max([q for t, q in drv.backlog
                                    if t0 + i * (t1 - t0) / 4 <= t
                                    < t0 + (i + 1) * (t1 - t0) / 4],
                                   default=0) for i in range(4)],
        "engine": {k: v - counters0[k]
                   for k, v in engine_counters(engine).items()},
        "window": e2e, "compile_cache": cache_dir,
        "modelled": modelled(meter, engine),
    }

    record = {"t0": t0, "t1": t_end if trace else t1,
              "chunks": [c for c in drv.chunks
                         if c["t0"] >= t0 and c["t1"] <= (t_end if trace
                                                          else t1)],
              "admits": [a for a in drv.admits
                         if a["t0"] >= t0 and a["t1"] <= (t_end if trace
                                                          else t1)],
              "stamps": stamps, "engine": info["engine"]}
    del engine, meter
    drv.engine = None
    gc.collect()

    t = time.perf_counter()
    chosen = sample_for_check(drv.reqs, served, seed,
                              conf["correct"]["sample_tokens"],
                              conf["correct"]["sample_requests"])
    gaps = logit_gaps(params, conf["model"], chosen, control)
    info["reference_s"] = time.perf_counter() - t
    info["checked"] = {"requests": gaps["requests"], "tokens": gaps["tokens"],
                       "control": control,
                       "program_logit_gap": gaps["program_gap"],
                       "per_request": gaps["per_request"],
                       "token_crc32": zlib.crc32(np.concatenate(
                           [np.asarray(r.out, np.int32) for r in chosen]
                           or [np.zeros(0, np.int32)]).tobytes())}
    limit = conf["correct"]["max_logit_gap"]
    checks = {
        "max_logit_gap": {"value": gaps["max_logit_gap"], "limit": limit},
        "failed_requests": {"value": len(failed), "limit": 0},
        "checked_tokens": {"value": gaps["tokens"], "limit_at_least": 1},
    }
    correct = (gaps["tokens"] >= 1 and gaps["max_logit_gap"] <= limit
               and not failed)

    if trace:
        import xplane

        red = xplane.load(xplane.find_xplane(trace_dir))
        busy = xplane.busy_s(red)
        ctx = {"trace": red, "record": record, "model": conf["model"],
               "peaks": spec.peaks(devs[0].device_kind) if require_tpu
               else cell.get("peaks"), "busy_s": busy}
        metrics = per_layer(cell, ctx)
        device_extra = {"busy_s": busy, "window_s": red.window_s}
        brk = xplane.breakdown(red)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        metrics, device_extra, brk = {}, {}, None
        for m in cell["end_to_end"]:
            value = setup_s if m["name"] == "setup_s" else e2e.get(m["name"])
            if value is None:
                log(f"end-to-end metric {m['name']} has no sample; left out")
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    result = {
        "correct": bool(correct),
        "attempted": len(attempted),
        "failed": len(failed),
        "metrics": metrics,
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind,
                   "count": len(devs), "memory_peak_bytes": peak,
                   **device_extra},
    }
    if brk is not None:
        result["breakdown"] = brk
    result["checks"] = checks
    result["_info"] = info
    return result


COUNTERS = ("decode_calls", "decode_steps", "prefill_calls", "prefill_rows",
            "host_transfer_bytes", "preempt_count", "prefix_lookups",
            "prefix_hits", "prefix_hit_tokens", "failed_requests")


def engine_counters(engine) -> Dict[str, int]:
    """The engine's own counters (a per-layer reader takes their change over
    the window from ``ctx["record"]["engine"]``)."""
    return {k: getattr(engine, k) for k in COUNTERS}


def modelled(meter, engine) -> Dict[str, Any]:
    """The paper's modelled accounting of the served work (not a chip
    number): billed tokens and dot products, and J/token at the QR design
    point the engine's energy report uses."""
    from repro.core.design import optimize
    from repro.core.substrate import substrate_for_design
    from repro.launch.metering import serve_energy_report

    tokens = sum(len(r.out) for r in engine.finished) + sum(
        len(r.out) for r in engine.slots if r is not None)
    pt = optimize(n=512, snr_t_target_db=26.0, kinds=("qr",))
    rep = serve_energy_report(meter, substrate=substrate_for_design(pt),
                              generated_tokens=max(tokens, 1),
                              requests=max(len(engine.finished), 1))
    return {"billed_prefill_tokens": meter.prefill_billed_tokens,
            "billed_decode_tokens": meter.decode_billed_tokens,
            "dp_total": float(sum(meter.dp_counts("total").values())),
            "j_per_token_qr_26db": rep.j_per_token}


def emit(result: Dict[str, Any]) -> None:
    info = result.pop("_info")
    print("bench_info " + json.dumps(info), flush=True)
    for name, c in result["checks"].items():
        bound = ", ".join(f"{k} {v}" for k, v in c.items() if k != "value")
        print(f"check {name}: {c['value']} ({bound})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.cell(args.workload)
    except (KeyError, FileNotFoundError) as e:
        log(str(e))
        return 2
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        return int(e.code)
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
