"""Serving micro-bench: tok/s, time-to-first-token, host-transfer traffic and
KV memory per active token for the PAGED engine vs a FROZEN copy of the PR-2
contiguous-cache engine (and the seed wave server, kept as a reference).

Two frozen baselines live here (kept ONLY as perf references):

  ``WaveServer``        the seed design: one decode step per Python tick with
                        a host sync every token, host-side cache scatter, and
                        a single scalar cache position (equal-prompt waves).
  ``ContiguousEngine``  the PR-2 design: per-slot position vectors, fused
                        decode scan, bucketed prefill - but every slot owns a
                        contiguous cache_len KV slice sized for the LONGEST
                        request, and prefill admits one request per call.

The live engine (`repro.launch.serve.Engine`) replaces the contiguous cache
with a paged block pool + per-slot block tables (KV memory proportional to
tokens actually held) and admits the FIFO prefix of same-bucket pending
requests as one batched (R, bucket) prefill call.

Structural counters reported per configuration:

  sync_bytes_per_token      int32 token traffic copied to the host / token
  jit_out_bytes_per_tick    bytes leaving the jitted decode per tick
  host_syncs_per_token      blocking device->host round trips per token
  kv_bytes_per_active_token KV cache bytes held per token resident in an
                            active slot, sampled after every decode chunk
                            (contiguous: the full slots x cache_len
                            allocation; paged: allocated blocks only)
  prefill_calls             prefill dispatches (paged batches same-bucket
                            admissions; contiguous pays one per request)

CPU wall times are indicative; the structural counters transfer to TPU.
``bench_records()`` returns machine-readable dicts (consumed by
``benchmarks/run.py --json``); ``run()`` formats them as CSV rows.  The
committed ``BENCH_serve.json`` baseline is produced with (the sharded suite
needs the devices of its mesh)::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python benchmarks/run.py --only serve --json BENCH_serve.json
"""
from __future__ import annotations

import copy
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core.design import optimize
from repro.core.mapping import per_token_matmul_shapes
from repro.core.substrate import AnalyticIMC, BitSerialIMC, substrate_for_design
from repro.launch.metering import DPMeter, serve_energy_report
from repro.launch.serve import Engine, Request, needs_exact_prefill, prefill_bucket, serve
from repro.models import decode_step, init_cache, init_params, prefill

Row = Tuple[str, float, str]

ARCH = "musicgen-medium"
BATCH = 4
REQUESTS = 8
PROMPT_LEN = 12
GEN = 8
# the mixed short/long workload: mostly short prompts with occasional long
# ones - the contiguous engine must size EVERY slot for the longest
MIXED_LENS = [4, 6, 48, 5, 8, 44, 6, 7]
# measured request count per mode (bitserial is ~30x slower per token on the
# CPU reference path; fewer requests keep the suite inside the CI budget)
MODES = {None: REQUESTS, "imc_analytic": REQUESTS, "imc_bitserial": 4}
# warmup replays the FULL measured workload once: the paged engine compiles
# one prefill per (R-pad, bucket) group shape, and the group composition is a
# deterministic function of the request schedule, so an identical warmup pass
# is the only way to cover every shape (a short warmup leaves compiles inside
# the measured window and understates steady-state tok/s)
WARMUP_REQUESTS = 2  # wave-server warmup only (exact-length prefill)
REPEATS = 3  # measured runs per engine; best wall time is reported


# ---------------------------------------------------------------------------
# frozen seed wave server (pre-PR-2 design, perf reference only)
# ---------------------------------------------------------------------------


class WaveServer:
    """Fixed-slot wave server: scalar cache position (slots stay
    position-synchronized), per-tick host sync, host-side cache scatter."""

    def __init__(self, cfg, params, batch_slots: int, cache_len: int,
                 rng: Optional[jax.Array] = None):
        self.cfg = cfg
        self.params = params
        self.slots: List[Optional[Request]] = [None] * batch_slots
        self.cache = init_cache(cfg, batch_slots, cache_len)
        self.cache_len = cache_len
        self.slot_pos = np.zeros(batch_slots, np.int32)
        self.last_token = np.zeros(batch_slots, np.int32)
        self.rng = rng
        self.ticks = 0
        self.sync_bytes = 0
        self._decode = jax.jit(
            lambda p, t, c, key: decode_step(p, cfg, t, c, rng=key)
        )

    def admit(self, req: Request) -> bool:
        for i, s in enumerate(self.slots):
            if s is None:
                if req.t_submit is None:
                    req.t_submit = time.perf_counter()
                self.slots[i] = req
                self._prefill_slot(i, req)
                return True
        return False

    def _prefill_slot(self, i: int, req: Request):
        toks = jnp.asarray(req.prompt)[None, :]
        logits, cache1 = prefill(self.params, self.cfg, toks,
                                 cache_len=self.cache_len, rng=self.rng)

        # scatter the single-request cache into slot i of the batched cache
        def put(batched, single):
            if batched.ndim == 0 or batched.shape == single.shape == ():
                return batched
            for axis in range(batched.ndim):
                if (batched.shape[axis] == len(self.slots)
                        and single.shape[axis] == 1):
                    idx = [slice(None)] * batched.ndim
                    idx[axis] = i
                    sidx = [slice(None)] * single.ndim
                    sidx[axis] = 0
                    return batched.at[tuple(idx)].set(single[tuple(sidx)])
            return batched

        self.cache = jax.tree_util.tree_map(
            lambda b, s: put(b, s) if hasattr(b, "at") else b,
            {k: v for k, v in self.cache.items() if k != "pos"},
            {k: v for k, v in cache1.items() if k != "pos"},
        )
        self.cache["pos"] = jnp.asarray(int(cache1["pos"]), jnp.int32)
        self.slot_pos[i] = len(req.prompt)
        self.last_token[i] = int(jnp.argmax(logits[0, -1]))
        req.out.append(int(self.last_token[i]))
        req.t_first = time.perf_counter()

    def tick(self):
        toks = jnp.asarray(self.last_token)
        key = None
        if self.rng is not None:
            self.rng, key = jax.random.split(self.rng)
        logits, self.cache = self._decode(self.params, toks, self.cache, key)
        # np.array (copy): the per-token host sync the engine eliminates
        nxt = np.array(jnp.argmax(logits[:, 0], axis=-1), np.int32)
        self.ticks += 1
        self.sync_bytes += nxt.nbytes
        for i, req in enumerate(self.slots):
            if req is None or req.done:
                continue
            req.out.append(int(nxt[i]))
            self.slot_pos[i] += 1
            if len(req.out) >= req.max_new:
                req.done = True
                self.slots[i] = None
        self.last_token = nxt

    @property
    def active(self) -> int:
        return sum(1 for s in self.slots if s is not None)


def _serve_wave(server: WaveServer, requests: List[Request]) -> List[Request]:
    pending = list(requests)
    finished: List[Request] = []
    while pending or server.active:
        while pending and server.admit(pending[0]):
            pending.pop(0)
        before = [s for s in server.slots if s is not None]
        server.tick()
        finished.extend(r for r in before if r.done)
    return finished


# ---------------------------------------------------------------------------
# frozen PR-2 contiguous-cache engine (pre-paging design, perf reference only)
# ---------------------------------------------------------------------------


class ContiguousEngine:
    """FROZEN copy of the PR-2 engine: per-slot positions and a fused decode
    scan, but each slot owns a contiguous (cache_len, ...) KV slice and
    prefill admits exactly one request per call."""

    def __init__(self, cfg, params, batch_slots: int, cache_len: int,
                 rng: Optional[jax.Array] = None, max_chunk: int = 8):
        self.cfg = cfg
        self.params = params
        self.batch_slots = batch_slots
        self.cache_len = cache_len
        self.max_chunk = max_chunk
        self.rng = rng
        self.bucketable = not needs_exact_prefill(cfg)

        self.slots: List[Optional[Request]] = [None] * batch_slots
        cache = init_cache(cfg, batch_slots, cache_len)
        cache.pop("pos")
        self.cache = cache
        self.pos = jnp.zeros((batch_slots,), jnp.int32)
        self.last_token = jnp.zeros((batch_slots,), jnp.int32)
        self.finished: List[Request] = []

        self.decode_calls = 0
        self.decode_steps = 0
        self.host_transfer_bytes = 0
        self.prefill_calls = 0
        self.prefill_rows = 0

        self._prefill_fns: Dict[int, object] = {}
        self._decode_fns: Dict[int, object] = {}
        self._insert_fn = jax.jit(self._insert_impl)
        self._kv_bytes = sum(
            leaf.size * leaf.dtype.itemsize
            for key, leaf in _kv_leaves(self.cache)
        )

    def kv_bytes_in_use(self) -> int:
        """The whole slots x cache_len allocation backs every admission."""
        return self._kv_bytes

    def live_tokens(self) -> int:
        return sum(len(r.prompt) + len(r.out) for r in self.slots
                   if r is not None)

    def _next_key(self):
        if self.rng is None:
            return None
        self.rng, key = jax.random.split(self.rng)
        return key

    @property
    def active(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    def admit_pending(self, pending: List[Request]) -> List[Request]:
        admitted = []
        while pending and self.admit(pending[0]):
            admitted.append(pending.pop(0))
        return admitted

    def admit(self, req: Request) -> bool:
        free = next((i for i, s in enumerate(self.slots) if s is None), None)
        if free is None:
            return False
        if req.t_submit is None:
            req.t_submit = time.perf_counter()
        length = len(req.prompt)
        if length + req.max_new - 1 > self.cache_len:
            raise ValueError(
                f"prompt ({length}) + max_new ({req.max_new}) exceeds "
                f"cache_len ({self.cache_len})")
        bucket = prefill_bucket(length, self.bucketable, self.cache_len)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :length] = req.prompt
        pf = self._prefill_fns.get(bucket)
        if pf is None:
            pf = self._prefill_fns[bucket] = self._make_prefill()
        tok0, cache1 = pf(self.params, jnp.asarray(toks),
                          jnp.asarray([length], jnp.int32), self._next_key())
        self.cache, self.last_token, self.pos = self._insert_fn(
            self.cache, {k: v for k, v in cache1.items() if k != "pos"},
            jnp.asarray(free, jnp.int32), tok0[0],
            jnp.asarray(length, jnp.int32), self.last_token, self.pos,
        )
        self.prefill_calls += 1
        self.prefill_rows += 1
        self.slots[free] = req
        req.out.append(int(tok0[0]))  # 4-byte sync, once per request (TTFT)
        req.t_first = time.perf_counter()
        if len(req.out) >= req.max_new:
            self._retire(free)
        return True

    def _make_prefill(self):
        cfg, cache_len, bucketable = self.cfg, self.cache_len, self.bucketable

        def pf(params, toks, true_len, key):
            logits, cache1 = prefill(
                params, cfg, toks, cache_len=cache_len, rng=key,
                true_len=true_len if bucketable else None,
            )
            tok0 = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return tok0, cache1

        return jax.jit(pf)

    def _insert_impl(self, cache, cache1, slot, tok0, length, last_token, pos):
        n_slots = self.batch_slots

        def put(batched, single):
            if getattr(batched, "ndim", 0) == 0:
                return batched
            for axis in range(batched.ndim):
                if (batched.shape[axis] == n_slots
                        and single.shape[axis] == 1):
                    starts = [0] * batched.ndim
                    starts[axis] = slot
                    return jax.lax.dynamic_update_slice(
                        batched, single.astype(batched.dtype), tuple(starts)
                    )
            return batched

        new_cache = jax.tree_util.tree_map(put, cache, cache1)
        return (new_cache, last_token.at[slot].set(tok0),
                pos.at[slot].set(length))

    def _retire(self, i: int):
        req = self.slots[i]
        req.done = True
        self.slots[i] = None
        self.finished.append(req)

    def next_chunk(self) -> int:
        rem = [r.max_new - len(r.out) for r in self.slots if r is not None]
        if not rem:
            return 0
        cap = min(min(rem), self.max_chunk)
        t = 1
        while t * 2 <= cap:
            t *= 2
        return t

    def _make_decode(self, n_steps: int):
        cfg = self.cfg

        def chunk(params, cache, last_tok, pos, active, key):
            def step(carry, t):
                cache, tok, pos = carry
                k = None if key is None else jax.random.fold_in(key, t)
                logits, new_cache = decode_step(
                    params, cfg, tok, dict(cache, pos=pos), rng=k
                )
                nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                nxt = jnp.where(active, nxt, tok)
                new_pos = jnp.where(active, pos + 1, pos)
                new_cache.pop("pos")
                return (new_cache, nxt, new_pos), nxt

            (cache, tok, pos), toks = jax.lax.scan(
                step, (cache, last_tok, pos), jnp.arange(n_steps)
            )
            return cache, tok, pos, toks.T  # (slots, T)

        return jax.jit(chunk)

    def decode_chunk(self, n_steps: Optional[int] = None) -> np.ndarray:
        if n_steps is None:
            n_steps = self.next_chunk()
        if n_steps <= 0:
            return np.zeros((self.batch_slots, 0), np.int32)
        fn = self._decode_fns.get(n_steps)
        if fn is None:
            fn = self._decode_fns[n_steps] = self._make_decode(n_steps)
        active = jnp.asarray(
            np.array([s is not None for s in self.slots]))
        self.cache, self.last_token, self.pos, toks = fn(
            self.params, self.cache, self.last_token, self.pos, active,
            self._next_key(),
        )
        block = np.asarray(toks)
        self.decode_calls += 1
        self.decode_steps += n_steps
        self.host_transfer_bytes += block.nbytes
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            take = min(n_steps, req.max_new - len(req.out))
            req.out.extend(int(t) for t in block[i, :take])
            if len(req.out) >= req.max_new:
                self._retire(i)
        return block


def _kv_leaves(tree, prefix=""):
    """Yield (name, leaf) for attention KV leaves ("k"/"v") in a cache tree."""
    if isinstance(tree, dict):
        for key, sub in tree.items():
            if key in ("k", "v", "pk", "pv") and hasattr(sub, "size"):
                yield f"{prefix}{key}", sub
            elif isinstance(sub, dict):
                yield from _kv_leaves(sub, f"{prefix}{key}.")


def drive_engine(engine, requests: List[Request], sample=None) -> List[Request]:
    """Bench drive loop shared by both engines (same admit_pending /
    decode_chunk / finished interface); ``sample`` observes the engine after
    every decode chunk (KV utilization)."""
    pending = list(requests)
    while pending or engine.active:
        engine.admit_pending(pending)
        engine.decode_chunk()
        if sample is not None:
            sample(engine)
    return engine.finished


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------


# the first-class substrates the bench executes on (string flags retired)
_SUBSTRATES = {"imc_analytic": AnalyticIMC, "imc_bitserial": BitSerialIMC}


def _mk_cfg(mode: Optional[str]):
    cfg = configs.get_smoke(ARCH)
    if mode:
        cfg = cfg.replace(imc=_SUBSTRATES[mode](bx=7, bw=7, v_wl=0.7))
    return cfg


def _mk_requests(cfg, lens, n_requests) -> List[Request]:
    rnp = np.random.default_rng(0)
    return [
        Request(rid=i, prompt=rnp.integers(0, cfg.vocab_size, lens[i % len(lens)]),
                max_new=GEN)
        for i in range(n_requests)
    ]


def _ttft_ms(reqs) -> float:
    vals = [r.ttft for r in reqs if r.ttft is not None]
    return 1e3 * float(np.mean(vals)) if vals else float("nan")


class _KVSampler:
    """Samples KV bytes per token resident in an active slot after every
    decode chunk (the utilization signal paging is supposed to fix)."""

    def __init__(self):
        self.samples: List[float] = []

    def __call__(self, engine):
        live = engine.live_tokens()
        if live > 0:
            self.samples.append(engine.kv_bytes_in_use() / live)

    @property
    def mean(self) -> float:
        return float(np.mean(self.samples)) if self.samples else float("nan")


def _run_engine(engine, cfg, lens, n_requests):
    # the engine object is reused across warmup + measurement so its jit
    # caches stay warm; the perf counters and the finished list restart per
    # run (serve_* return engine.finished - a stale list would count prior
    # runs' tokens against this run's wall time)
    engine.decode_calls = engine.decode_steps = 0
    engine.host_transfer_bytes = 0
    engine.prefill_calls = engine.prefill_rows = 0
    engine.finished = []
    reqs = _mk_requests(cfg, lens, n_requests)
    sampler = _KVSampler()
    t0 = time.perf_counter()
    out = drive_engine(engine, reqs, sample=sampler)
    dt = time.perf_counter() - t0
    tokens = sum(len(r.out) for r in out)
    steps = max(engine.decode_steps, 1)
    return {
        "wall_s": round(dt, 3),
        "tok_s": round(tokens / dt, 1) if dt > 0 else float("nan"),
        "ttft_ms": round(_ttft_ms(out), 1),
        "tokens": tokens,
        "host_syncs_per_token": round(engine.decode_calls / steps, 3),
        "sync_bytes_per_token": round(
            engine.host_transfer_bytes / max(tokens, 1), 1),
        # only the (slots, T) int32 token block leaves the fused scan
        "jit_out_bytes_per_tick": round(
            engine.host_transfer_bytes / max(engine.decode_steps, 1), 1),
        "decode_chunks": engine.decode_calls,
        "decode_steps": engine.decode_steps,
        "prefill_calls": engine.prefill_calls,
        "prefill_rows": engine.prefill_rows,
        "kv_bytes_per_active_token": round(sampler.mean, 1),
    }


def _run_wave(cfg, rng, cache_len, n_requests):
    server = WaveServer(cfg, init_params(jax.random.PRNGKey(0), cfg),
                        BATCH, cache_len, rng=rng)
    reqs = _mk_requests(cfg, [PROMPT_LEN], n_requests)
    t0 = time.perf_counter()
    out = _serve_wave(server, reqs)
    dt = time.perf_counter() - t0
    tokens = sum(len(r.out) for r in out)
    return {
        "wall_s": round(dt, 3),
        "tok_s": round(tokens / dt, 1) if dt > 0 else float("nan"),
        "ttft_ms": round(_ttft_ms(out), 1),
        "tokens": tokens,
        "host_syncs_per_token": 1.0,
        "sync_bytes_per_token": round(server.sync_bytes / max(tokens, 1), 1),
        # the (slots, 1, vocab) f32 logits leave the jitted step every tick
        "jit_out_bytes_per_tick": BATCH * cfg.padded_vocab * 4,
    }


def _engines_for(cfg, rng, cache_len):
    params = init_params(jax.random.PRNGKey(0), cfg)
    cont = ContiguousEngine(cfg, params, BATCH, cache_len, rng=rng,
                            max_chunk=GEN)
    paged = Engine(cfg, params, BATCH, cache_len, rng=rng, max_chunk=GEN)
    return cont, paged


def bench_records() -> List[dict]:
    records: List[dict] = []
    # mixed workload: contiguous must size every slot for the longest prompt
    max_bucket = max(prefill_bucket(l, True, 10**9) for l in MIXED_LENS)
    cache_len = max_bucket + GEN + 8
    for mode, n_requests in MODES.items():
        cfg = _mk_cfg(mode)
        rng = jax.random.PRNGKey(7) if mode else None
        meta = {"bench": "serve", "arch": ARCH, "mode": mode or "digital",
                "substrate": mode or "digital",
                "slots": BATCH, "requests": n_requests,
                "prompt_lens": MIXED_LENS[:n_requests], "gen": GEN}
        # warmup both engines (compile time excluded, as in kernel_bench)
        cont, paged = _engines_for(cfg, rng, cache_len)
        _run_engine(cont, cfg, MIXED_LENS, n_requests)
        _run_engine(paged, cfg, MIXED_LENS, n_requests)
        # best-of-REPEATS per engine: CPU wall times on shared boxes swing
        # ~2x run to run; the structural counters are identical across runs
        cont_rec = max(
            (_run_engine(cont, cfg, MIXED_LENS, n_requests)
             for _ in range(REPEATS)), key=lambda r: r["tok_s"])
        paged_rec = max(
            (_run_engine(paged, cfg, MIXED_LENS, n_requests)
             for _ in range(REPEATS)), key=lambda r: r["tok_s"])
        # schema v2.4: every serve record names its decode-attention path -
        # "dense" for the contiguous/wave baselines (full-cache attention),
        # cfg.decode_attn for the paged engine (fused kernel vs gather)
        records.append({**meta, "config": "contiguous_engine",
                        "decode_attn": "dense", **cont_rec})
        records.append({**meta, "config": "paged_engine",
                        "decode_attn": cfg.decode_attn, **paged_rec})
        records.append({
            **meta, "bench": "serve_summary",
            "speedup_tok_s": round(paged_rec["tok_s"] / cont_rec["tok_s"], 2)
            if cont_rec["tok_s"] else float("nan"),
            "ttft_ratio": round(paged_rec["ttft_ms"] / cont_rec["ttft_ms"], 2)
            if cont_rec["ttft_ms"] else float("nan"),
            "kv_reduction": round(
                cont_rec["kv_bytes_per_active_token"]
                / paged_rec["kv_bytes_per_active_token"], 2),
            "kv_bytes_per_active_token_before":
                cont_rec["kv_bytes_per_active_token"],
            "kv_bytes_per_active_token_after":
                paged_rec["kv_bytes_per_active_token"],
            "prefill_calls_before": cont_rec["prefill_calls"],
            "prefill_calls_after": paged_rec["prefill_calls"],
        })
    # seed wave server reference (equal prompts - it cannot run mixed lengths)
    cfg = _mk_cfg(None)
    wave_cache_len = 2 * PROMPT_LEN + GEN + 8
    _run_wave(cfg, None, wave_cache_len, WARMUP_REQUESTS)
    wave = _run_wave(cfg, None, wave_cache_len, REQUESTS)
    records.append({"bench": "serve", "arch": ARCH, "mode": "digital",
                    "substrate": "digital",
                    "config": "wave_baseline", "slots": BATCH,
                    "requests": REQUESTS, "prompt_len": PROMPT_LEN,
                    "gen": GEN, "decode_attn": "dense", **wave})
    records.extend(drift_records())
    records.extend(slo_records())
    return records


# ---------------------------------------------------------------------------
# SLO overload scenario (scheduling + preemption + frontier degradation)
# ---------------------------------------------------------------------------

# the committed overload scenario: a seeded bursty workload offered at 2x the
# engine's service rate, on the frozen imc_analytic substrate at the QR
# high-SNR frontier point (ladder level 0 for the PressureController).  Every
# gated field is a deterministic function of (seed, overload, kv_blocks):
# time is virtual (runtime.workload.VirtualClock), so no wall clock leaks
# into the record.
SLO_SEED = 0
SLO_REQUESTS = 32
SLO_OVERLOAD = 2.0
SLO_ARRIVAL = "bursty"
# 10 usable blocks for 4 slots: tight enough that lazy growth must preempt
# under the burst, ample enough that worst-case reservation still admits
SLO_KV_BLOCKS = 11
SLO_RUNS = (
    # (config id, policy, alloc, degrade): A = status-quo baseline,
    # B = the full overload-resilience stack, C = isolates the lazy-alloc
    # utilization win from scheduling effects
    ("fifo_reserve", "fifo", "reserve", False),
    ("deadline_lazy_degrade", "deadline", "lazy", True),
    ("fifo_lazy", "fifo", "lazy", False),
)


def _slo_frozen():
    """Frozen imc_analytic smoke engine config at the committed QR frontier
    point (same freeze recipe as drift_records: rng(1) reference batch)."""
    from repro.core.substrate import calibrate_model

    pt = optimize(n=ENERGY_N, snr_t_target_db=ENERGY_SNR_HIGH, kinds=("qr",))
    cfg = configs.get_smoke(ARCH).replace(imc=substrate_for_design(pt))
    params = init_params(jax.random.PRNGKey(0), cfg)
    ref = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 24))
    return pt, calibrate_model(cfg, params, [ref]), params


def slo_records(seed: Optional[int] = None) -> List[dict]:
    """Goodput / latency / shed / preempt / degrade scoreboard for the three
    committed runs on identical seeded 2x-overload bursty traffic.

    The acceptance invariants (gated in ``check_regression`` and pinned by
    ``test_bench_schema``): the full stack (B) achieves strictly higher
    goodput than the FIFO+reserve baseline (A) with zero engine deaths and
    exact request conservation; lazy allocation alone (C) raises pool
    utilization over worst-case reservation (A)."""
    from repro.core.substrate import substrate_ladder
    from repro.launch.metering import slo_summary
    from repro.launch.scheduler import PressureController, make_policy
    from repro.launch.serve import serve_slo
    from repro.runtime.workload import (
        VirtualClock,
        generate,
        make_overload_config,
    )

    if seed is None:
        seed = SLO_SEED  # resolved late: run.py --workload-seed overrides
    pt, cfg, params = _slo_frozen()
    wcfg = make_overload_config(
        n_requests=SLO_REQUESTS, seed=seed, overload=SLO_OVERLOAD,
        slots=BATCH, max_new=GEN, arrival=SLO_ARRIVAL)
    records: List[dict] = []
    by_config: Dict[str, dict] = {}
    for config, policy_name, alloc, degrade in SLO_RUNS:
        reqs = generate(wcfg, cfg.vocab_size)
        engine = Engine(cfg, params, BATCH, 32 + GEN + 8, max_chunk=4,
                        kv_blocks=SLO_KV_BLOCKS, alloc_policy=alloc,
                        clock=VirtualClock())
        controller = (PressureController(engine,
                                         substrate_ladder(pt, steps=2))
                      if degrade else None)
        policy = make_policy(policy_name)
        deaths = 0
        try:
            finished = serve_slo(engine, reqs, policy=policy,
                                 controller=controller)
        except Exception:  # an engine death is a GATED failure, not a crash
            deaths = 1
            finished = engine.finished
        conserved = (len(finished) == SLO_REQUESTS and sorted(
            r.rid for r in finished) == list(range(SLO_REQUESTS)))
        summary = slo_summary(finished, elapsed=engine.clock.now,
                              policy=policy.name)
        rec = {
            "bench": "serve_slo", "arch": ARCH, "mode": "imc_analytic",
            "substrate": "imc_analytic", "config": config,
            "policy": policy.name, "alloc": alloc, "degrade": degrade,
            "workload_seed": seed, "overload": SLO_OVERLOAD,
            "arrival": SLO_ARRIVAL, "slots": BATCH,
            "requests": SLO_REQUESTS, "gen": GEN,
            "kv_blocks": SLO_KV_BLOCKS,
            **{k: (round(v, 4) if isinstance(v, float) else v)
               for k, v in summary.items() if k != "policy"},
            "preempt_count": engine.preempt_count,
            "substrate_swaps": engine.substrate_swaps,
            "degrade_steps": (controller.degrade_steps if controller else 0),
            "upgrade_steps": (controller.upgrade_steps if controller else 0),
            "pool_utilization": round(engine.pool_utilization(), 4),
            "engine_deaths": deaths,
            "conserved": conserved,
        }
        records.append(rec)
        by_config[config] = rec
    a = by_config["fifo_reserve"]
    b = by_config["deadline_lazy_degrade"]
    c = by_config["fifo_lazy"]
    records.append({
        "bench": "serve_slo_summary", "arch": ARCH, "mode": "imc_analytic",
        "substrate": "imc_analytic", "config": "overload_2x",
        "workload_seed": seed, "overload": SLO_OVERLOAD,
        "requests": SLO_REQUESTS, "slots": BATCH,
        "goodput_ratio": round(b["goodput"] / a["goodput"], 4)
        if a["goodput"] else float("nan"),
        "goodput_baseline": a["goodput"],
        "goodput_resilient": b["goodput"],
        "pool_util_gain": round(
            c["pool_utilization"] - a["pool_utilization"], 4),
        "preempt_count": b["preempt_count"],
        "degrade_steps": b["degrade_steps"],
        "shed_total": a["shed"] + b["shed"] + c["shed"],
        "engine_deaths": (a["engine_deaths"] + b["engine_deaths"]
                          + c["engine_deaths"]),
        "conserved": bool(a["conserved"] and b["conserved"]
                          and c["conserved"]),
    })
    return records


# ---------------------------------------------------------------------------
# drift-injection serve scenario (shadow calibration -> detect -> hot-swap)
# ---------------------------------------------------------------------------

DRIFT_SCALE = 2.5  # injected weight-scale shift on every mlp.wi
DRIFT_REQUESTS = 6  # half served clean, half after the injected shift


def drift_records() -> List[dict]:
    """Serve calibrated traffic, inject an ``mlp.wi`` weight-scale shift
    mid-stream, and record how the shadow-calibration loop behaves: chunks
    to detection (vs the cadence bound), hot-swap count, and the worst
    post-swap SNR_T gap to a fresh-frozen reference (schema v2.2: the
    acceptance invariant is ``recovery_gap_db_max <= 1``).  Every recorded
    field is a deterministic function of the request schedule and the
    injected scale - no wall clock.

    The shift is injected into the weights (not the embedding): the model
    is pre-norm, so an embedding-scale shift would be normalized away
    before every matmul site and no drift would ever reach the quantizers.
    """
    from repro.core.substrate import as_substrate, calibrate_model
    from repro.runtime import drift as drift_lib

    cfg_dyn = _mk_cfg("imc_analytic")
    params = init_params(jax.random.PRNGKey(0), cfg_dyn)
    ref = np.random.default_rng(1).integers(0, cfg_dyn.vocab_size, (4, 24))
    cfg = calibrate_model(cfg_dyn, params, [ref])
    sub = as_substrate(cfg.imc)
    # rel_excess bounds the post-swap gap to a fresh-frozen reference:
    # residual excess below the re-flag threshold never swaps again, so the
    # drifted-site gap is at most 20*log10(1 + rel_excess) = 0.83 dB here -
    # the structural guarantee behind the 1 dB acceptance ceiling
    mon = drift_lib.DriftMonitor(drift_lib.DriftConfig(
        sample_every=1, check_every=1,
        thresholds=drift_lib.DriftThresholds(rel_excess=0.1, clip_rate=0.05)))
    max_bucket = max(prefill_bucket(l, True, 10**9) for l in MIXED_LENS)
    engine = Engine(cfg, params, BATCH, max_bucket + GEN + 8, max_chunk=GEN,
                    drift_monitor=mon)
    reqs = _mk_requests(cfg, MIXED_LENS, DRIFT_REQUESTS)
    half = DRIFT_REQUESTS // 2
    drive_engine(engine, reqs[:half])
    clean_events = mon.drift_events
    chunks_clean = mon.chunks_seen

    def scale_wi(p):
        if isinstance(p, dict):
            return {k: (v * DRIFT_SCALE if k == "wi" else scale_wi(v))
                    for k, v in p.items()}
        return p

    engine.params = scale_wi(engine.params)
    drive_engine(engine, reqs[half:])

    rows = drift_lib.site_snr_table(sub.calibration, engine._calib,
                                    mon.last_observed, bx=sub.imc.bx)
    # drifted = observed range EXCEEDED the frozen one (the one-sided test's
    # direction); sites whose frozen range merely over-provisions live
    # traffic carry a static q-noise gap the monotone merge can never shrink
    # - that's calibration conservatism, not drift, and is not gated here
    drifted = [r for r in rows if r["x_max_observed"] > r["x_max_frozen"]]
    detected = mon.first_drift_chunk is not None
    return [{
        "bench": "serve_drift", "arch": ARCH, "mode": "imc_analytic",
        "substrate": "imc_analytic", "config": "paged_engine_drift",
        "slots": BATCH, "requests": DRIFT_REQUESTS, "gen": GEN,
        "inject_scale": DRIFT_SCALE,
        "drift_detected": detected,
        "false_positives_clean": clean_events,
        "chunks_to_detect": (mon.first_drift_chunk - chunks_clean
                             if detected else -1),
        "detection_bound_chunks": (mon.cfg.sample_every
                                   * mon.cfg.check_every + 1),
        "swaps": engine.swap_count,
        "shadow_samples": mon.samples,
        "sites_drifted": len(drifted),
        "degradation_db_max": round(max(
            (r["degradation_db"] for r in drifted), default=0.0), 3),
        "recovery_gap_db_max": round(max(
            (abs(r["recovery_gap_db"]) for r in drifted), default=0.0), 3),
        "failed_requests": engine.failed_requests,
    }]


# ---------------------------------------------------------------------------
# serve-path energy-delay accounting (J/token per design point)
# ---------------------------------------------------------------------------

# two SNR_T targets bracketing the serving EDAP frontier: at ENERGY_SNR_LOW
# every substrate (QS/QR/CM) still meets the target, at ENERGY_SNR_HIGH only
# QR remains feasible - the serve-workload form of the paper's "QS-based at
# low compute SNR, QR-based at high" guideline (QS's 512-row points cap out
# near 18-19 dB SNR_T; see core.design)
ENERGY_SNR_LOW = 14.0
ENERGY_SNR_HIGH = 26.0
ENERGY_N = 512  # the paper's 512-row SRAM bank


def _meter_workload() -> Tuple[DPMeter, int, int]:
    """Serve the standard mixed 4..48-token workload once (digital smoke
    model - the billed schedule is a pure function of the request stream) with
    a DPMeter attached, billing the FULL ``musicgen-medium`` matmul sites so
    the rollup reports deployment-scale energy on the real traffic pattern."""
    cfg = _mk_cfg(None)
    sites = per_token_matmul_shapes(configs.get(ARCH))
    meter = DPMeter(sites=sites)
    max_bucket = max(prefill_bucket(l, True, 10**9) for l in MIXED_LENS)
    cache_len = max_bucket + GEN + 8
    params = init_params(jax.random.PRNGKey(0), cfg)
    engine = Engine(cfg, params, BATCH, cache_len, max_chunk=GEN, meter=meter)
    drive_engine(engine, _mk_requests(cfg, MIXED_LENS, REQUESTS))
    generated = sum(len(r.out) for r in engine.finished)
    return meter, generated, len(engine.finished)


_ENERGY_CACHE: List[dict] = []


def energy_records() -> List[dict]:
    """J/token, J/request, EDP/token per substrate x design point on the
    metered serve workload, plus per-target frontier summaries and the
    QS-vs-QR crossover record (deterministic - no wall-clock timing).

    Single home: these records exist ONLY under the ``serve_energy`` suite
    (``run.py`` expands ``--only serve`` to include it, so the serve bench
    surface still reports energy).  Memoized per process.  NOTE the suite is
    committed in both ``BENCH_serve.json`` (via the expansion) and
    ``BENCH_energy.json`` - regenerate the two baselines together after any
    rollup change, or the regression gate will flag the stale one."""
    if _ENERGY_CACHE:
        return copy.deepcopy(_ENERGY_CACHE)
    meter, generated, n_requests = _meter_workload()
    meta = {"bench": "serve_energy", "arch": ARCH, "slots": BATCH,
            "requests": n_requests, "gen": GEN,
            "prompt_lens": MIXED_LENS, "bank_rows": ENERGY_N}
    records: List[dict] = []
    frontier: Dict[float, Dict[str, dict]] = {}
    for snr_db in (ENERGY_SNR_LOW, ENERGY_SNR_HIGH):
        per_kind: Dict[str, dict] = {}
        for kind in ("qs", "qr", "cm"):
            pt = optimize(n=ENERGY_N, snr_t_target_db=snr_db, kinds=(kind,))
            if pt is None:
                continue
            # bill through the executable substrate the design point
            # implies: the rollup reads the billed design (and any per-site
            # overrides) from the substrate object itself (schema v2.1:
            # every serve record names its substrate)
            rep = serve_energy_report(meter,
                                      substrate=substrate_for_design(pt),
                                      generated_tokens=generated,
                                      requests=n_requests)
            rec = {**meta, "snr_t_target_db": snr_db, "kind": kind,
                   **{k: v for k, v in rep.summary().items()
                      if k != "arch_kind"}}
            per_kind[kind] = rec
            records.append(rec)
        frontier[snr_db] = per_kind
        if per_kind:
            best_e = min(per_kind, key=lambda k: per_kind[k]["j_per_token"])
            best_edp = min(per_kind, key=lambda k: per_kind[k]["edp_per_token"])
            records.append({
                **meta, "bench": "serve_energy_summary",
                "substrate": "mixed",  # aggregates across substrates
                "snr_t_target_db": snr_db,
                "kinds_feasible": sorted(per_kind),
                "best_kind_energy": best_e,
                "best_kind_edp": best_edp,
                "j_per_token_best": per_kind[best_e]["j_per_token"],
                "edp_per_token_best": per_kind[best_edp]["edp_per_token"],
            })
    lo, hi = frontier[ENERGY_SNR_LOW], frontier[ENERGY_SNR_HIGH]
    records.append({
        **meta, "bench": "serve_energy_crossover",
        "substrate": "mixed",  # aggregates across substrates
        "snr_low_db": ENERGY_SNR_LOW, "snr_high_db": ENERGY_SNR_HIGH,
        # the crossover as it manifests in this calibration: QS serves the
        # low-SNR side of the frontier only (feasible at the low target,
        # absent at the high one); QR alone spans the high-SNR side
        "qs_feasible_low": "qs" in lo,
        "qs_feasible_high": "qs" in hi,
        "best_kind_high": min(hi, key=lambda k: hi[k]["j_per_token"]) if hi
        else None,
        "crossover": ("qs" in lo) and ("qs" not in hi)
        and bool(hi) and min(hi, key=lambda k: hi[k]["j_per_token"]) == "qr",
    })
    # per-site SNR_T map of the MPC-style override substrate vs the uniform
    # design point (deterministic closed forms; see benchmarks/layer_snr.py)
    from benchmarks.layer_snr import site_snr_records

    records.extend(site_snr_records(arch=ARCH, snr_t_db=ENERGY_SNR_LOW,
                                    n=ENERGY_N))
    _ENERGY_CACHE.extend(copy.deepcopy(records))
    return records


# ---------------------------------------------------------------------------
# prefix-sharing paged KV suite (shared-system-prompt traffic, warm vs cold)
# ---------------------------------------------------------------------------

PREFIX_LEN = 16  # shared system-prompt tokens: two full 8-token KV blocks
PREFIX_DUP = 4  # requests sharing each system prompt on average
PREFIX_REQUESTS = 12
PREFIX_SEED = 0
# digital + frozen imc_analytic: bit-identity across all three substrates
# (incl. the ~30x-slower bitserial path) is pinned by
# tests/test_prefix_cache.py; the bench keeps inside the CI budget
PREFIX_MODES = (None, "imc_analytic")


def _prefix_requests(cfg) -> List[Request]:
    """The committed shared-system-prompt draw: ``runtime.workload`` builds
    prompts, stop lengths and the per-class prefix pools from ONE seeded
    stream; arrival times are dropped (``drive_engine`` serves open loop) and
    rid order is kept, so the warm and cold engines see the identical
    schedule and greedy outputs compare token for token."""
    from repro.runtime.workload import WorkloadConfig, generate

    wcfg = WorkloadConfig(n_requests=PREFIX_REQUESTS, seed=PREFIX_SEED,
                          max_new=GEN, prefix_len=PREFIX_LEN,
                          prefix_dup=PREFIX_DUP)
    return [Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new,
                    stop_at=r.stop_at)
            for r in generate(wcfg, cfg.vocab_size)]


def _prefix_run(cfg, params, cache_len, enable):
    """One serve pass over the shared-prefix workload: fresh engine + meter
    (billing the full ``musicgen-medium`` sites), KV utilization sampled
    after every decode chunk; returns (engine, meter, kv_mean, out-by-rid)."""
    meter = DPMeter(sites=per_token_matmul_shapes(configs.get(ARCH)))
    engine = Engine(cfg, params, BATCH, cache_len, max_chunk=GEN,
                    meter=meter, prefix_cache=enable)
    sampler = _KVSampler()
    done = drive_engine(engine, _prefix_requests(cfg), sample=sampler)
    return engine, meter, sampler.mean, {r.rid: list(r.out) for r in done}


def prefix_records() -> List[dict]:
    """Prefix-sharing warm engine vs cold-cache engine on identical seeded
    shared-system-prompt traffic, per substrate.

    The acceptance invariants (gated in ``check_regression`` and pinned by
    ``test_bench_schema``): greedy outputs bit-identical to the cold run
    (``token_match``), a strictly positive hit rate, and a strictly positive
    billed-prefill-energy saving (``j_per_token_saved`` - the J/token the
    cache's skipped prefill dot-products would have cost at the committed
    low-SNR QR design point).  The prefix counters are structural (pure
    functions of the seeded schedule) and gate exactly."""
    from repro.core.substrate import calibrate_model

    pt = optimize(n=ENERGY_N, snr_t_target_db=ENERGY_SNR_LOW, kinds=("qr",))
    records: List[dict] = []
    for mode in PREFIX_MODES:
        cfg = _mk_cfg(mode)
        params = init_params(jax.random.PRNGKey(0), cfg)
        if mode:
            # freeze calibration (drift_records' rng(1) reference batch):
            # warm-vs-cold identity needs one fixed quantization map
            ref = np.random.default_rng(1).integers(0, cfg.vocab_size,
                                                    (2, 24))
            cfg = calibrate_model(cfg, params, [ref])
        lens = [len(r.prompt) for r in _prefix_requests(cfg)]
        cache_len = max(prefill_bucket(l, True, 10**9)
                        for l in lens) + GEN + 8
        cold, meter_c, kv_cold, toks_cold = _prefix_run(
            cfg, params, cache_len, enable=False)
        warm, meter_w, kv_warm, toks_warm = _prefix_run(
            cfg, params, cache_len, enable=True)
        stats = warm.prefix_stats()
        sub = substrate_for_design(pt)
        rep_w = serve_energy_report(
            meter_w, substrate=sub,
            generated_tokens=sum(len(t) for t in toks_warm.values()),
            requests=len(toks_warm))
        rep_c = serve_energy_report(
            meter_c, substrate=sub,
            generated_tokens=sum(len(t) for t in toks_cold.values()),
            requests=len(toks_cold))
        records.append({
            "bench": "serve_prefix", "arch": ARCH,
            "mode": mode or "digital", "substrate": mode or "digital",
            "config": "prefix_engine", "slots": BATCH,
            "requests": PREFIX_REQUESTS, "gen": GEN,
            "prefix_len": PREFIX_LEN, "prefix_dup": PREFIX_DUP,
            "workload_seed": PREFIX_SEED,
            "snr_t_target_db": ENERGY_SNR_LOW, "kind": "qr",
            "token_match": toks_warm == toks_cold,
            "prefix_lookups": stats["lookups"],
            "prefix_hits": stats["hits"],
            "hit_rate": stats["hit_rate"],
            "prefix_hit_tokens": stats["hit_tokens"],
            "saved_billed_tokens": stats["saved_billed_tokens"],
            "cow_copies": stats["cow_copies"],
            "prefix_evictions": stats["evictions"],
            "cached_blocks": stats["cached_blocks"],
            "prefill_calls": warm.prefill_calls,
            "prefill_rows": warm.prefill_rows,
            "prefill_rows_cold": cold.prefill_rows,
            "prefill_tokens": rep_w.prefill_tokens,
            "prefill_tokens_cold": rep_c.prefill_tokens,
            "kv_bytes_per_active_token": round(kv_warm, 1),
            "kv_bytes_per_active_token_cold": round(kv_cold, 1),
            "prefill_j": rep_w.prefill_j,
            "prefill_j_cold": rep_c.prefill_j,
            "j_per_token": rep_w.j_per_token,
            "j_per_token_cold": rep_c.j_per_token,
            "saved_prefill_j": rep_w.saved_prefill_j,
            "j_per_token_saved": rep_w.j_per_token_saved,
        })
    return records


# ---------------------------------------------------------------------------
# tensor-parallel sharded serve (multi-device scaling suite)
# ---------------------------------------------------------------------------

# the committed sharded scenario: the mixed 4..48 workload served by the
# single-device engine and by a (1, 4) tensor-parallel engine (musicgen's 4 KV
# heads head-shard 4 ways: one head per device), both in this process - it
# needs the mesh's devices (on the CPU:
# XLA_FLAGS=--xla_force_host_platform_device_count=8).  Structural fields
# (per-device KV bytes, greedy-token match) gate exactly; tok/s scaling gates
# on a generous absolute floor because host-simulated CPU "devices" share one
# physical socket (all-reduce overhead without any real parallel silicon).
SHARDED_MESH = "1x4"
# digital + frozen imc_analytic: equivalence across all three substrates
# (incl. the ~30x-slower bitserial path) is pinned by the slow lane in
# tests/test_serve_sharded.py; the bench keeps inside the CI budget
SHARDED_MODES = (None, "imc_analytic")


def _sharded_run(engine, cfg, n):
    engine.decode_calls = engine.decode_steps = 0
    engine.host_transfer_bytes = 0
    engine.prefill_calls = engine.prefill_rows = 0
    engine.finished = []
    t0 = time.perf_counter()
    out = serve(engine, _mk_requests(cfg, MIXED_LENS, n))
    dt = time.perf_counter() - t0
    tokens = sum(len(r.out) for r in out)
    return (tokens / dt if dt > 0 else float("nan"),
            {r.rid: list(r.out) for r in out})


def _best_sharded_run(engine, cfg, n):
    _sharded_run(engine, cfg, n)  # warmup: compiles excluded from timing
    return max((_sharded_run(engine, cfg, n) for _ in range(REPEATS)),
               key=lambda t: t[0])


def sharded_records() -> List[dict]:
    """The sharded-vs-single-device comparison, in this process: it needs
    the ``SHARDED_MESH`` devices (``make_serve_mesh`` raises otherwise)."""
    from repro.core.substrate import calibrate_model
    from repro.launch.mesh import make_serve_mesh, parse_mesh_shape

    mesh = make_serve_mesh(*parse_mesh_shape(SHARDED_MESH))
    n = len(MIXED_LENS)
    max_bucket = max(prefill_bucket(l, True, 10 ** 9) for l in MIXED_LENS)
    cache_len = max_bucket + GEN + 8
    records = []
    for mode in SHARDED_MODES:
        cfg = _mk_cfg(mode)
        params = init_params(jax.random.PRNGKey(0), cfg)
        if mode:
            # frozen calibration: batch-composition-invariant IMC forwards
            # (the precondition for sharded == single-device token identity)
            ref = np.random.default_rng(1).integers(
                0, cfg.vocab_size, (2, max(MIXED_LENS)))
            cfg = calibrate_model(cfg, params, [ref])
        single = Engine(cfg, params, BATCH, cache_len, max_chunk=GEN)
        tok_s_single, toks_single = _best_sharded_run(single, cfg, n)
        sharded = Engine(cfg, params, BATCH, cache_len, max_chunk=GEN,
                         mesh=mesh)
        tok_s_sharded, toks_sharded = _best_sharded_run(sharded, cfg, n)
        records.append({
            "bench": "serve_sharded", "arch": ARCH, "config": "tp_engine",
            "mode": mode or "digital", "substrate": mode or "digital",
            "decode_attn": sharded.cfg.decode_attn,
            "mesh_shape": SHARDED_MESH, "devices": len(jax.devices()),
            "slots": BATCH, "requests": n, "prompt_lens": MIXED_LENS[:n],
            "gen": GEN,
            "tok_s_single": round(tok_s_single, 1),
            "tok_s_sharded": round(tok_s_sharded, 1),
            "scaling_tok_s_ratio": round(tok_s_sharded / tok_s_single, 3),
            "kv_shard_ways": sharded.tp if sharded.kv_shard else 1,
            "kv_bytes_per_device": sharded.kv_pool_bytes_per_device(),
            "kv_bytes_total": sharded.kv_pool_bytes(),
            "token_match": toks_sharded == toks_single,
        })
    return records


def sharded_rows(records: List[dict]) -> List[Row]:
    rows: List[Row] = []
    for r in records:
        if r["bench"] != "serve_sharded":
            continue
        rows.append((
            f"serve_sharded/{r['substrate']}_mesh{r['mesh_shape']}",
            r["scaling_tok_s_ratio"],
            f"tok/s vs 1-device ({r['tok_s_single']}->{r['tok_s_sharded']}); "
            f"kv_B/dev={r['kv_bytes_per_device']} of {r['kv_bytes_total']} "
            f"({r['kv_shard_ways']}-way heads) "
            f"token_match={r['token_match']}",
        ))
    return rows


def energy_rows(records: List[dict]) -> List[Row]:
    rows: List[Row] = []
    for r in records:
        if r["bench"] == "serve_energy":
            rows.append((
                f"serve_energy/{r['kind']}_snr{int(r['snr_t_target_db'])}",
                r["j_per_token"],
                f"J/token; J/req={r['j_per_request']:.3e} "
                f"EDP/tok={r['edp_per_token']:.3e} "
                f"tok/s(compute)={r['tok_s_compute']:.3e} "
                f"b_adc={r['b_adc']} n_banks={r['n_banks']}",
            ))
        elif r["bench"] == "serve_energy_summary":
            rows.append((
                f"serve_energy/summary_snr{int(r['snr_t_target_db'])}",
                r["j_per_token_best"],
                f"best J/token ({r['best_kind_energy']}); "
                f"best EDP kind={r['best_kind_edp']} "
                f"feasible={'/'.join(r['kinds_feasible'])}",
            ))
        elif r["bench"] == "serve_energy_crossover":
            rows.append((
                "serve_energy/qs_qr_crossover",
                1.0 if r["crossover"] else 0.0,
                f"qs@low={r['qs_feasible_low']} qs@high={r['qs_feasible_high']} "
                f"best@high={r['best_kind_high']}",
            ))
        elif r["bench"] == "site_snr":
            rows.append((
                f"site_snr/{r['arch']}/{r['name']}",
                r["snr_t_override_db"],
                f"SNR_T dB w/ per-site override (uniform "
                f"{r['snr_t_uniform_db']} dB, B_ADC "
                f"{r['b_adc_uniform']}->{r['b_adc_override']})",
            ))
        elif r["bench"] == "site_snr_summary":
            rows.append((
                f"site_snr/{r['arch']}/summary",
                r["j_per_token_ratio"],
                f"J/token cost of boosting {r['sites_boosted']}/{r['sites']} "
                f"sites; min boosted SNR_T {r['snr_t_boosted_min_db']} dB "
                f"vs uniform {r['snr_t_uniform_db']} dB",
            ))
    return rows


def rows_from_records(records: List[dict]) -> List[Row]:
    rows: List[Row] = []
    energy = [r for r in records if r["bench"].startswith("serve_energy")]
    for r in records:
        if r["bench"].startswith("serve_energy"):
            continue
        tag = f"{r['mode']}_b{r['slots']}"
        if r["bench"] == "serve_summary":
            rows.append((
                f"serve/summary_{tag}",
                r["kv_reduction"],
                f"kv B/active-tok reduction "
                f"{r['kv_bytes_per_active_token_before']}->"
                f"{r['kv_bytes_per_active_token_after']}; "
                f"tok/s ratio {r['speedup_tok_s']} "
                f"prefill calls {r['prefill_calls_before']}->"
                f"{r['prefill_calls_after']}",
            ))
        elif r["bench"] == "serve_drift":
            rows.append((
                f"serve/drift_{tag}",
                r["recovery_gap_db_max"],
                f"dB worst post-swap gap to fresh-frozen; "
                f"detected={r['drift_detected']} in "
                f"{r['chunks_to_detect']} chunks "
                f"(bound {r['detection_bound_chunks']}) "
                f"swaps={r['swaps']} sites_drifted={r['sites_drifted']} "
                f"degradation={r['degradation_db_max']}dB",
            ))
        elif r["bench"] == "serve_slo":
            rows.append((
                f"serve/slo_{r['config']}_{tag}",
                r["goodput"],
                f"SLO-met req/step @{r['overload']}x {r['arrival']} "
                f"seed={r['workload_seed']}; met={r['slo_met']}/"
                f"{r['requests']} shed={r['shed']} "
                f"preempt={r['preempt_count']} "
                f"degrade={r['degrade_steps']} "
                f"ttft_p99={r['ttft_p99']} pool_util="
                f"{r['pool_utilization']} deaths={r['engine_deaths']}",
            ))
        elif r["bench"] == "serve_slo_summary":
            rows.append((
                f"serve/slo_summary_{tag}",
                r["goodput_ratio"],
                f"goodput ratio (deadline+lazy+degrade / fifo+reserve) "
                f"@{r['overload']}x overload; pool_util_gain="
                f"{r['pool_util_gain']} preempt={r['preempt_count']} "
                f"deaths={r['engine_deaths']} conserved={r['conserved']}",
            ))
        elif r["bench"] == "serve_prefix":
            rows.append((
                f"serve/prefix_{tag}",
                r["hit_rate"],
                f"prefix hit rate ({r['prefix_hits']}/{r['prefix_lookups']} "
                f"admissions); saved {r['saved_billed_tokens']} billed "
                f"prefill tokens = {r['j_per_token_saved']:.3e} J/token "
                f"({r['j_per_token_cold']:.3e}->{r['j_per_token']:.3e}) "
                f"cow={r['cow_copies']} evict={r['prefix_evictions']} "
                f"token_match={r['token_match']}",
            ))
        else:
            kv = r.get("kv_bytes_per_active_token")
            rows.append((
                f"serve/{r['config']}_{tag}",
                r["tok_s"],
                f"tok/s; ttft={r['ttft_ms']}ms "
                f"sync_B/tok={r['sync_bytes_per_token']} "
                + (f"kv_B/active_tok={kv}" if kv is not None else
                   f"jit_out_B/tick={r['jit_out_bytes_per_tick']}"),
            ))
    rows.extend(energy_rows(energy))
    return rows


def run() -> List[Row]:
    return rows_from_records(bench_records())
