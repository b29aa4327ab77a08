"""Device-resident continuous-batching serve engine with a PAGED KV cache:
batched bucketed prefill + fused decode, optionally executing every matmul
through the IMC simulation (the paper's technique in deployment position).
The execution substrate is a first-class ``repro.core.substrate.Substrate``
(``cfg.imc``); with a ``frozen`` calibration policy the IMC quantizer ranges
are compile-time constants, so batched engine output is bit-identical to
sequential single-request execution on every substrate (``--imc-policy
frozen``).

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-9b --smoke \
      --batch 4 --prompt-len 32 --gen 16 --imc-mode imc_analytic

Engine design (the decode hot loop never leaves the device):

  paged KV cache       global-attention K/V lives in a shared block pool
                       (num_blocks, block, Hkv, hd) indexed through a
                       device-resident per-slot block table (slots,
                       max_blocks).  A host-side free-list allocator
                       (``BlockAllocator``) hands each request exactly
                       ceil((prompt + max_new - 1) / block) blocks, so mixed
                       short/long traffic holds KV memory proportional to the
                       tokens it actually keeps, not slots x longest-request.
                       Physical block 0 is a reserved garbage block: block
                       tables point to it for unallocated logical blocks and
                       inactive rows' decode writes are routed to it (a
                       retired slot's stale table may reference blocks the
                       allocator already handed to another request).
                       Sliding-window rings (bounded at the window span) and
                       recurrent states (fixed size) stay contiguous.
  batched prefill      the FIFO prefix of pending requests sharing one
                       bucket is admitted as ONE (R, bucket) prefill call
                       (R padded to a power of two: one compile per
                       (R, bucket), dummy rows dropped via out-of-bounds
                       scatter), followed by ONE jitted multi-slot insert
                       that writes each row's prompt K/V into its allocated
                       blocks and its block-table row.  Prefix-only grouping
                       keeps strict arrival order (no short prompt overtakes
                       an earlier long one).  MoE patterns prefill one
                       request at a time (expert capacity is batch-coupled,
                       so batching would change routing vs the solo
                       reference).
  per-slot positions   the decode cache carries a (slots,) position vector,
                       so every slot sits at its own sequence depth.
  fused decode scan    decode runs T steps at a time inside ONE jitted call
                       (``jax.lax.scan`` over the step), with slot state
                       (last token, position, active mask) and greedy argmax
                       resident on device.  Exactly one (slots, T) int32 block
                       crosses to the host per chunk.  T is the largest power
                       of two that no active request overruns.
  bucketed prefill     prompts are right-padded to power-of-two length buckets
                       (one compile per bucket, not per length); causality
                       isolates the pad positions, logits are gathered at each
                       row's true last position.  Recurrent (ssm/rglru) and
                       MoE patterns use exact-length prefill instead: a
                       recurrent state would integrate the pad garbage, and
                       pad tokens would contend for expert capacity.

Greedy sampling.  Finished sequences free their slot (and blocks) for the
next request.

Robustness (drift + faults):

  shadow calibration   with a ``runtime.drift.DriftMonitor`` attached, every
                       Nth decode chunk / prefill group runs through a
                       shadow-traced variant of the same jitted function that
                       streams running-maxima stats to the monitor's recorder
                       (``core.substrate.shadow_recording`` - passive, outputs
                       bit-identical, still one (slots, T) transfer per chunk);
  atomic hot-swap      the Calibration pytree is a TRACED argument of every
                       decode/prefill jit, so the jit cache is keyed on its
                       treedef; ``swap_calibration`` installs a refreshed
                       calibration with the same site names between chunks as
                       a pure host-side pointer update - the compiled scan is
                       reused, and within any one chunk all rows quantize
                       against one consistent calibration;
  failure isolation    a request that cannot be admitted (oversized) or whose
                       prefill keeps failing retires with a per-request
                       ``error`` status instead of killing the engine; a
                       transient ``JaxRuntimeError`` on a decode chunk is
                       retried once (the ``runtime.fault`` retry idiom) and,
                       if it persists, fails only the requests in flight.

Overload resilience (scheduling + preemption contract):

  lazy paged blocks    under ``alloc_policy="lazy"`` (the default) admission
                       allocates only the blocks the prompt insert needs
                       (``ceil(len(prompt)/block)``); generation-tail blocks
                       are allocated ON the block-boundary crossing, right
                       before each decode chunk (``_ensure_blocks``), so the
                       early-stopping mix no longer pays worst-case
                       reservation.  ``alloc_policy="reserve"`` keeps the old
                       worst-case behaviour.  Feasibility (``_fits``) still
                       checks the worst case, so a solo request can always
                       finish once the pool drains.
  recompute-preempt    a mid-generation allocation failure preempts a VICTIM
                       (the latest-admitted active slot newer than the
                       grower; the grower itself if none is newer - the
                       oldest resident always progresses, so the scheme
                       cannot livelock): its blocks are freed and it joins
                       ``engine.preempted`` keeping its generated tokens.
                       Serve loops re-queue it; re-admission prefills
                       ``prompt + out`` (the resume prompt), whose final
                       argmax IS the next token decode would have produced -
                       under frozen calibration the resumed request is
                       bit-exact with its uninterrupted counterpart
                       (test-pinned on all three substrates).  Preemption
                       never kills the engine and never loses a request.
  scheduler policies   ``serve_slo`` drives the engine under a
                       ``launch.scheduler`` policy object (FIFO /
                       shortest-prompt-first / SLO-deadline with load
                       shedding); shed requests retire through
                       ``fail_request`` with ``error_kind="shed"`` (PR 6's
                       graceful per-request contract - never engine death).
                       Time is virtual (``runtime.workload.VirtualClock``,
                       decode-step units), so every SLO metric is a
                       deterministic function of the workload seed.
  frontier degradation a ``scheduler.PressureController`` watches queue
                       depth / pool occupancy and hot-swaps the substrate
                       one step down the EDAP frontier
                       (``Engine.swap_substrate``, jit caches keyed on
                       ``Substrate.trace_key`` - one compile per ladder
                       level, then pure pointer updates), stepping back up
                       when pressure clears.  While the queue is saturated
                       (``drift_pause_depth``), drift shadow sampling is
                       paused so the callback tax never lands at peak load.

Profiling.  The engine's host work runs in ``jax.profiler.TraceAnnotation``
spans (0.4-0.7 us each on a TPU v5e host when no profiler runs):
``engine.admit`` (one ``admit_pending`` call) holds ``engine.group`` (choosing
a group), ``engine.prefill`` (its arguments and the prefill dispatch; args
``rows``, ``bucket``, ``rids``), ``engine.insert`` and ``engine.first_token``
(the sync on its first tokens); ``engine.decode`` (one ``decode_chunk`` call)
holds ``engine.grow`` (block growth and its extend dispatch),
``engine.dispatch`` (arguments and the chunk dispatch; args ``steps``,
``active``, ``live_blocks``: the blocks the paged walk reads for the
active slots in the chunk, summed into ``kv_blocks_walked``), ``engine.sync``
(the token block's transfer) and ``engine.deliver`` (token bookkeeping and
retirement).  The device programs carry named scopes (``embed``, ``block``,
``attn``, ``mlp``, ``head``, ``sample``, ``paged_attention``, each matmul's
site, ``imc``) in their op names.
"""
from __future__ import annotations

import argparse
import dataclasses
import logging
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.core import substrate as substrate_lib
from repro.launch.mesh import axis_size, dp_axes
from repro.launch.sharding import (
    activation_rules,
    axis_rules,
    fallback_replicate,
    kv_head_partition,
    tree_param_specs,
    tree_shardings,
    validate_divisibility,
)
from repro.models import decode_step, init_paged_cache, init_params, prefill
from repro.runtime import drift as drift_lib
from repro.runtime import fault as fault_lib
from repro.runtime.compile_cache import use_compile_cache
from repro.runtime.prefix_cache import PrefixCache

log = logging.getLogger("repro.serve")

MIN_BUCKET = 8
DEFAULT_BLOCK = 8  # tokens per KV block; divides every pow2 bucket >= MIN_BUCKET


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (P,)
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    t_submit: Optional[float] = None
    t_first: Optional[float] = None  # first generated token on the host
    # per-request failure status: a request that cannot be served (oversized,
    # poisoned prefill, persistent device error mid-decode, shed under
    # overload) finishes with done=True, the reason in ``error`` and a typed
    # category in ``error_kind`` - failures never escape to the engine
    error: Optional[str] = None
    error_kind: Optional[str] = None  # "admission"|"prefill"|"decode"|"shed"
    # SLO workload metadata (runtime.workload): virtual arrival time,
    # per-class deadlines (relative to arrival / between tokens), tenant
    # class.  All None/default for plain offline serving.
    arrive_at: Optional[float] = None
    ttft_deadline: Optional[float] = None
    itl_deadline: Optional[float] = None
    rclass: str = "default"
    # true generation length (the EOS the engine cannot know at admission):
    # generation stops at min(max_new, stop_at).  Worst-case reservation
    # must still budget max_new blocks - that gap is the lazy-allocation win.
    stop_at: Optional[int] = None
    # recompute-preemption bookkeeping
    preemptions: int = 0
    # virtual completion time of each generated token (only stamped when the
    # engine runs under a VirtualClock; feeds p50/p99 inter-token latency)
    token_times: List[float] = dataclasses.field(default_factory=list)

    @property
    def ok(self) -> bool:
        return self.done and self.error is None

    @property
    def shed(self) -> bool:
        return self.error_kind == "shed"

    @property
    def effective_max(self) -> int:
        """Tokens this request will actually generate (EOS-capped)."""
        if self.stop_at is None:
            return self.max_new
        return min(self.max_new, self.stop_at)

    @property
    def full_prompt(self) -> np.ndarray:
        """The resume prompt: original prompt plus every generated token.
        Prefilling it reproduces the exact decode state - the final
        position's argmax is the next token the uninterrupted run would
        produce (bit-exact under frozen calibration)."""
        if not self.out:
            return self.prompt
        return np.concatenate(
            [np.asarray(self.prompt), np.asarray(self.out)]).astype(
                np.asarray(self.prompt).dtype)

    @property
    def ttft(self) -> Optional[float]:
        if self.t_submit is None or self.t_first is None:
            return None
        return self.t_first - self.t_submit


def needs_exact_prefill(cfg) -> bool:
    """Patterns that cannot take padded (bucketed) prefill: recurrent state
    integrates pad garbage; MoE pad tokens contend for expert capacity."""
    kinds = tuple(cfg.pattern) + tuple(cfg.tail_kinds)
    return any(k in ("ssm", "rglru") for k in kinds) or cfg.n_experts > 0


def prefill_bucket(length: int, bucketable: bool, cache_len: int) -> int:
    """Power-of-two prefill bucket for a prompt length (>= length, one jit
    compile per bucket); exact length when the pattern requires it."""
    if not bucketable:
        return length
    p = MIN_BUCKET
    while p < length:
        p *= 2
    return min(p, cache_len) if cache_len >= length else p


class BlockAllocator:
    """Refcounting free-list allocator over the physical KV block pool.

    Contract (pinned by the hypothesis property tests):
      - block 0 is reserved (the garbage block) and is never handed out;
      - ``alloc(n)`` returns n distinct blocks none of which is currently
        allocated elsewhere, or None (caller must not admit) - it never
        partially allocates;
      - ``free(blocks)`` returns blocks to the pool; freed blocks are
        immediately reusable;
      - ``free_count + sum(len(owned))`` is conserved at ``num_blocks - 1``.

    Prefix-sharing extension (same conservation law, refined):
      - ``alloc`` acquires each block at refcount 1; ``retain`` takes an
        extra reference (a second request linking a shared prefix block);
        ``free`` is a ref-RELEASE - the block only leaves ``used`` when its
        last reference drops, so preempting/retiring one sharer never pulls
        a block out from under its peers;
      - ``register_cached`` marks a block as owned by the prefix index:
        when its refcount hits zero it parks on an insertion-ordered IDLE
        list (still occupying pool memory, still serving future prefix
        hits) instead of returning to the free list;
      - ``evict`` reclaims one idle cached block (the engine picks WHICH -
        leaf-first LRU over the radix index) back onto the free list;
      - conservation: ``free_count + referenced + idle_cached`` is invariant
        at ``num_blocks - 1``; the free list never contains a block that is
        referenced or cached.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 1:
            raise ValueError("need at least the reserved garbage block")
        self.num_blocks = num_blocks
        # LIFO free list: recently freed (cache-warm) blocks are reused first
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._allocated: set = set()
        self._ref: Dict[int, int] = {}
        self._cached: set = set()
        # refcount-zero cached blocks, insertion-ordered = release-time LRU
        self._idle: Dict[int, None] = {}

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return len(self._allocated)

    @property
    def evictable_count(self) -> int:
        return len(self._idle)

    def refcount(self, b: int) -> int:
        return self._ref.get(b, 0)

    def is_evictable(self, b: int) -> bool:
        return b in self._idle

    def alloc(self, n: int) -> Optional[List[int]]:
        if n > len(self._free):
            return None
        blocks = [self._free.pop() for _ in range(n)]
        self._allocated.update(blocks)
        for b in blocks:
            self._ref[b] = 1
        return blocks

    def retain(self, blocks: List[int]):
        """Take one extra reference on each block (prefix-hit linking).
        A retained idle cached block leaves the eviction candidate set."""
        for b in blocks:
            if b not in self._allocated:
                raise ValueError(f"retain of unallocated block {b}")
            self._ref[b] = self._ref.get(b, 0) + 1
            self._idle.pop(b, None)

    def free(self, blocks: List[int]):
        """Release one reference per block.  At refcount zero a cached
        block goes idle (evictable, still resident); an uncached block
        returns to the free list."""
        for b in blocks:
            if b not in self._allocated or self._ref.get(b, 0) <= 0:
                raise ValueError(f"double free / foreign block {b}")
            self._ref[b] -= 1
            if self._ref[b] > 0:
                continue
            if b in self._cached:
                self._idle[b] = None
            else:
                del self._ref[b]
                self._allocated.remove(b)
                self._free.append(b)

    def register_cached(self, b: int):
        """Hand a block's zero-ref lifetime to the prefix index."""
        if b not in self._allocated:
            raise ValueError(f"cannot cache unallocated block {b}")
        self._cached.add(b)
        if self._ref.get(b, 0) == 0:
            self._idle[b] = None

    def evict(self, b: int):
        """Reclaim one idle cached block onto the free list (the caller
        must have dropped it from the prefix index first)."""
        if b not in self._idle:
            raise ValueError(
                f"block {b} is not evictable (referenced or uncached)")
        del self._idle[b]
        self._cached.remove(b)
        self._ref.pop(b, None)
        self._allocated.remove(b)
        self._free.append(b)


def _cfg_with_calibration(cfg, calib):
    """``cfg`` with its substrate's calibration replaced by ``calib`` (a
    possibly-traced Calibration pytree).  Runs INSIDE jitted traces: this is
    how the frozen quantizer ranges become runtime arguments of the decode
    scan / prefill instead of baked compile-time constants, which is what
    makes the hot-swap recompile-free."""
    if calib is None:
        return cfg
    sub = dataclasses.replace(substrate_lib.as_substrate(cfg.imc),
                              calibration=calib)
    return cfg.replace(imc=sub)


class Engine:
    """Fixed-slot continuous-batching engine: paged KV cache, batched
    bucketed prefill, fused decode scan.

    Host-side state is bookkeeping only (which request owns which slot and
    which physical blocks); everything the decode loop touches - block pools,
    block tables, per-slot positions, last tokens - lives on device between
    jitted calls.
    """

    def __init__(self, cfg, params, batch_slots: int, cache_len: int,
                 rng: Optional[jax.Array] = None, max_chunk: int = 8,
                 block_size: int = DEFAULT_BLOCK,
                 kv_blocks: Optional[int] = None, meter=None,
                 drift_monitor: Optional[drift_lib.DriftMonitor] = None,
                 failure_injector: Optional[Callable[[str, Any], None]] = None,
                 alloc_policy: str = "lazy", clock=None,
                 drift_pause_depth: Optional[int] = None, mesh=None,
                 prefix_cache: bool = False):
        # tensor-parallel serving: a (data=1, model=N) mesh shards the
        # weights (path-based param specs) and the paged KV pools (heads
        # over ``model``); None = the classic single-device engine
        self.mesh = mesh
        self.tp = axis_size(mesh, "model") if mesh is not None else 1
        if self.tp > 1 and cfg.decode_attn == "kernel":
            # per-shard Pallas paged-attention dispatch is out of scope: the
            # sharded engine serves through the gather reference path
            log.info("model-parallel mesh (%d-way): decode_attn='kernel' "
                     "falls back to the gather path under sharding", self.tp)
            cfg = cfg.replace(decode_attn="gather")
        self.cfg = cfg
        self.params = params
        # the first-class execution substrate every matmul routes through
        # (cfg.imc may be a bare IMCConfig - normalized here once)
        self.substrate = substrate_lib.as_substrate(cfg.imc)
        # optional launch.metering.DPMeter: billed-work accounting.  Both
        # hook points are O(1) host-side counter updates driven by values
        # the engine already holds, so the device contracts (fused scan,
        # one (slots, T) transfer per chunk) are untouched.  The meter is
        # stamped with the substrate that actually runs, so the energy
        # rollup bills the design points the substrate objects carry - no
        # side-channel flag plumbing.
        self.meter = meter
        if meter is not None and getattr(meter, "substrate", None) is None:
            meter.substrate = self.substrate
        # hot-swappable frozen calibration: passed as a TRACED argument to
        # every decode/prefill jit (None under dynamic/digital substrates)
        self._calib = (self.substrate.calibration
                       if self.substrate.policy == "frozen" else None)
        self.swap_count = 0
        # online drift monitoring (requires a frozen substrate: shadow stats
        # are compared against the frozen ranges)
        if drift_monitor is not None and self._calib is None:
            raise ValueError(
                "drift monitoring requires a frozen-policy substrate "
                "(there are no frozen ranges to compare shadow stats "
                "against)")
        self._drift = drift_monitor
        # test/chaos hook: called as failure_injector(phase, info) right
        # before the device call of a prefill ("prefill", rid tuple) or a
        # decode chunk ("decode", chunk index); raising simulates a device
        # error at exactly that point
        self.failure_injector = failure_injector
        self.batch_slots = batch_slots
        self.block = block_size
        self.max_blocks = -(-cache_len // block_size)
        # logical per-request capacity, rounded up to whole blocks
        self.cache_len = self.max_blocks * block_size
        self.max_chunk = max_chunk
        self.rng = rng
        self.bucketable = not needs_exact_prefill(cfg)
        # MoE expert capacity couples rows of a batch: batched prefill would
        # route differently than the solo reference, so keep R = 1 there
        self.batch_prefill = cfg.n_experts == 0
        kinds = tuple(cfg.pattern) + tuple(cfg.tail_kinds)
        self.has_paged = "attn" in kinds
        if kv_blocks is None:
            # full provisioning: admission can never stall on blocks
            kv_blocks = batch_slots * self.max_blocks + 1
        self.alloc = BlockAllocator(kv_blocks if self.has_paged else 1)

        if alloc_policy not in ("lazy", "reserve"):
            raise ValueError(f"unknown alloc_policy {alloc_policy!r}")
        self.alloc_policy = alloc_policy
        # prefix-sharing radix cache (host-side index; refcounts live in the
        # allocator).  Only sound when EVERY per-request KV byte lives in the
        # paged pool: a sliding-window ring or recurrent/MoE state cannot be
        # reconstructed by linking blocks, so those configs serve cold.
        self.prefix: Optional[PrefixCache] = None
        if prefix_cache:
            eligible = (self.has_paged and cfg.n_experts == 0
                        and all(k == "attn" for k in kinds))
            if eligible:
                self.prefix = PrefixCache(block_size)
            else:
                log.info("prefix cache disabled: pattern %s carries "
                         "non-paged per-request state", kinds)
        # prefix-sharing counters (miss = a cold admission with the cache on)
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0
        self.prefix_saved_tokens = 0
        self.cow_copies = 0
        self.prefix_evictions = 0
        # optional runtime.workload.VirtualClock: when present, admission /
        # decode advance it and stamp t_submit/t_first/token_times in virtual
        # decode-step units (deterministic SLO metrics); None = wall clock
        self.clock = clock
        # drift shadow sampling pauses while queue_depth exceeds this
        # (serve loops publish their queue length here each tick)
        self.drift_pause_depth = drift_pause_depth
        self.queue_depth = 0

        self.slots: List[Optional[Request]] = [None] * batch_slots
        self._slot_blocks: List[List[int]] = [[] for _ in range(batch_slots)]
        # host-side per-slot sequence depth (mirror of the device pos vector;
        # drives lazy block-boundary math without a device read)
        self._slot_pos: List[int] = [0] * batch_slots
        # admission sequence number per slot: the preemption victim order
        self._slot_seq: List[int] = [0] * batch_slots
        self._admit_seq = 0
        # recompute-preempted requests wait here for the serve loop to
        # re-queue them (they keep their generated tokens - the resume
        # prompt is prompt + out)
        self.preempted: List[Request] = []
        cache = init_paged_cache(cfg, batch_slots, self.cache_len,
                                 kv_blocks if self.has_paged else 1,
                                 block_size)
        cache.pop("pos")
        self.cache = cache  # blocks/tail only: positions are engine state
        self.pos = jnp.zeros((batch_slots,), jnp.int32)
        self.last_token = jnp.zeros((batch_slots,), jnp.int32)
        self.finished: List[Request] = []

        # sharded placement (no-op on the single-device engine): params get
        # their TP specs, KV pools get head-sharded iff Hkv divides the
        # model axis (else replicated - the partition helper refuses uneven
        # splits), and everything else replicates
        self._rules = None
        self._cache_shardings = None
        self._rep_sharding = None
        self.kv_shard = False
        if self.tp > 1:
            self._init_sharding()

        # perf counters (consumed by bench/run.py)
        self.decode_calls = 0
        self.decode_steps = 0
        self.host_transfer_bytes = 0
        self.prefill_calls = 0
        self.prefill_rows = 0
        # paged walk engagement: blocks the decode kernel walks for active
        # slots (each one's live blocks, through its tail block, at each
        # step; an idle slot also walks its stale table, uncounted) against
        # the blocks their tables hold (max_blocks per active slot and step)
        self.kv_blocks_walked = 0
        self.kv_blocks_tabled = 0
        # robustness counters
        self.failed_requests = 0
        self.decode_failures = 0
        self.shed_requests = 0
        self.preempt_count = 0
        self.substrate_swaps = 0
        # pool-utilization accounting (sampled once per decode chunk):
        # live tokens vs the token capacity of the blocks actually allocated
        self._util_token_sum = 0
        self._util_cap_sum = 0

        # jit caches keyed (..., shadow, substrate.trace_key): the shadow
        # variant of a function is traced under shadow_recording and carries
        # the observation callbacks; the calibration pytree is a traced
        # ARGUMENT of both, so a calibration hot-swap (same site names ->
        # same treedef) re-uses every entry, and a frontier-ladder substrate
        # swap (different trace_key) compiles once per level then re-uses -
        # no recompile storms on either axis
        self._prefill_fns: Dict[Tuple[int, int, bool, Any], Any] = {}
        self._decode_fns: Dict[Tuple[int, bool, Any], Any] = {}
        self._warm_fns: Dict[Tuple[int, Any], Any] = {}
        self._logits_fn = None
        if self.tp > 1:
            rep = self._rep_sharding
            self._insert_fn = self._with_rules(jax.jit(
                self._insert_impl,
                out_shardings=(self._cache_shardings, rep, rep)))
            self._extend_fn = self._with_rules(jax.jit(
                self._extend_impl, out_shardings=self._cache_shardings))
            self._cow_fn = self._with_rules(jax.jit(
                self._cow_impl, out_shardings=self._cache_shardings))
        else:
            self._insert_fn = jax.jit(self._insert_impl)
            self._extend_fn = jax.jit(self._extend_impl)
            self._cow_fn = jax.jit(self._cow_impl)
        self._block_bytes, self._fixed_kv_bytes = self._kv_accounting()
        if self.prefix is not None and self._fixed_kv_bytes > 0:
            # belt-and-braces: a contiguous ring/recurrent leaf means part of
            # the per-request state is NOT addressable through block tables
            log.info("prefix cache disabled: %d bytes of contiguous "
                     "per-request KV state", self._fixed_kv_bytes)
            self.prefix = None
        # per-device KV footprint: head-sharded pool/ring leaves split their
        # bytes over the model axis; the block tables and everything else
        # replicate (the allocator is whole per shard group)
        div = self.tp if self.kv_shard else 1
        self._block_bytes_per_device = self._block_bytes // div
        self._fixed_kv_bytes_per_device = self._fixed_kv_bytes // div
        if meter is not None and self.mesh is not None:
            meter.note_mesh(self.mesh_shape, self.mesh.devices.size,
                            self.kv_pool_bytes_per_device())

    # -- tensor-parallel placement --------------------------------------------
    def _init_sharding(self):
        mesh = self.mesh
        hkv = self.cfg.n_kv_heads
        self.kv_shard = self.has_paged and hkv % self.tp == 0
        if self.kv_shard:
            # contract: contiguous per-shard-group head ranges (no loss, no
            # overlap); raises - instead of padding - on uneven splits
            kv_head_partition(hkv, self.tp)
        elif self.has_paged:
            log.info("KV pools replicated: %d KV heads do not divide the "
                     "%d-way model axis", hkv, self.tp)
        rules = activation_rules(mesh)
        dp = dp_axes(mesh)
        rules["paged_kv_bshd"] = (
            P(dp, None, "model", None) if self.kv_shard else P())
        self._rules = rules
        self._rep_sharding = NamedSharding(mesh, P())

        specs = tree_param_specs(self.params)
        issues = validate_divisibility(self.params, specs, mesh)
        if issues:
            log.info("serve TP: replicating %d param tensor(s) whose "
                     "sharded dims do not divide the mesh", len(issues))
            specs = fallback_replicate(specs, {p for p, _, _ in issues})
        self.params = jax.device_put(self.params, tree_shardings(mesh, specs))

        def cache_spec(path, leaf):
            name = str(getattr(path[-1], "key", ""))
            if name in ("pk", "pv", "k", "v") and self.kv_shard:
                # (..., block/seq, Hkv, hd): heads ride the model axis
                entries = [None] * leaf.ndim
                entries[-2] = "model"
                return P(*entries)
            return P()  # block tables, recurrent states, positions

        cache_specs = jax.tree_util.tree_map_with_path(cache_spec, self.cache)
        self._cache_shardings = tree_shardings(mesh, cache_specs)
        self.cache = jax.device_put(self.cache, self._cache_shardings)
        self.pos = jax.device_put(self.pos, self._rep_sharding)
        self.last_token = jax.device_put(self.last_token, self._rep_sharding)

    def _with_rules(self, fn):
        """Bind the engine's logical-axis rules around a jitted callable so
        every ``ws``/``ws_attn`` annotation resolves at trace time (identity
        on the single-device engine)."""
        if self._rules is None:
            return fn
        mesh, rules = self.mesh, self._rules

        def call(*args, **kwargs):
            with axis_rules(mesh, rules):
                return fn(*args, **kwargs)

        return call

    @property
    def mesh_shape(self) -> Optional[str]:
        """The mesh as an ``RxC`` string ("1x4"), None when single-device."""
        if self.mesh is None:
            return None
        return f"{axis_size(self.mesh, 'data')}x{axis_size(self.mesh, 'model')}"

    # -- kv memory accounting --------------------------------------------------
    def _kv_accounting(self) -> Tuple[int, int]:
        """(bytes per physical block summed over paged layers, bytes of the
        always-allocated contiguous KV leaves: sliding-window rings)."""
        block_bytes = 0
        fixed = 0

        def walk(sub):
            nonlocal block_bytes, fixed
            if isinstance(sub, dict) and "pk" in sub:
                for leaf in (sub["pk"], sub["pv"]):
                    # (NB, bs, H, hd) or stacked (n_full, NB, bs, H, hd)
                    per_block = leaf.size // leaf.shape[-4] * leaf.dtype.itemsize
                    block_bytes += per_block
                return
            if isinstance(sub, dict):
                for key, v in sub.items():
                    if key in ("k", "v"):
                        fixed += v.size * v.dtype.itemsize
                    else:
                        walk(v)

        walk({"blocks": self.cache.get("blocks", {}),
              "tail": self.cache.get("tail", {})})
        return block_bytes, fixed

    def kv_bytes_in_use(self) -> int:
        """Bytes of KV memory currently backing live tokens: allocated blocks
        across every paged layer plus the fixed ring caches."""
        return self._fixed_kv_bytes + self.alloc.used_count * self._block_bytes

    def kv_pool_bytes(self) -> int:
        """Whole-pool KV capacity in bytes (a pure function of shapes)."""
        return (self._fixed_kv_bytes
                + self.alloc.num_blocks * self._block_bytes)

    def kv_pool_bytes_per_device(self) -> int:
        """Per-device whole-pool KV capacity: head-sharded pools carry
        ``1/model_axis`` of the bytes per device; replicated pools carry all
        of them.  Structural (shape-derived), so the bench gate pins it
        exactly."""
        return (self._fixed_kv_bytes_per_device
                + self.alloc.num_blocks * self._block_bytes_per_device)

    def kv_bytes_in_use_per_device(self) -> int:
        """Per-device bytes currently backing live tokens."""
        return (self._fixed_kv_bytes_per_device
                + self.alloc.used_count * self._block_bytes_per_device)

    def live_tokens(self) -> int:
        """Tokens currently resident in active slots' caches."""
        return sum(len(r.prompt) + len(r.out) for r in self.slots
                   if r is not None)

    def pool_utilization(self) -> float:
        """Chunk-averaged live tokens per allocated-block token capacity:
        the fraction of reserved KV memory actually backing live tokens
        (worst-case reservation scores low on early-stopping traffic; lazy
        allocation is the fix)."""
        if self._util_cap_sum == 0:
            return 0.0
        return self._util_token_sum / self._util_cap_sum

    # -- rng ------------------------------------------------------------------
    def _next_key(self):
        if self.rng is None:
            return None
        self.rng, key = jax.random.split(self.rng)
        return key

    # -- time -----------------------------------------------------------------
    def _now(self) -> float:
        return self.clock.now if self.clock is not None else time.perf_counter()

    # -- admission ------------------------------------------------------------
    @property
    def active(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    def _bucket(self, req: Request) -> int:
        return prefill_bucket(len(req.full_prompt), self.bucketable,
                              self.cache_len)

    def _total_positions(self, req: Request) -> int:
        """WORST-CASE K/V positions the request may write over its whole
        life: the original prompt plus a full ``max_new`` generation tail.
        Deliberately ignores ``stop_at`` (the EOS is unknowable at
        admission - budgeting on it would leak the oracle; early stopping
        is exactly what lazy allocation profits from).  Invariant under
        preemption: prompt + out + (max_new - out) - 1."""
        return len(req.prompt) + req.max_new - 1

    def _blocks_total(self, req: Request) -> int:
        """Worst-case block demand (feasibility: can this EVER finish?)."""
        if not self.has_paged:
            return 0
        return -(-self._total_positions(req) // self.block)

    def _blocks_needed(self, req: Request) -> int:
        """Blocks allocated AT ADMISSION.  Lazy: just the prompt-insert
        coverage (ceil(len(full_prompt)/block)); generation-tail blocks
        arrive later via ``_ensure_blocks``.  Reserve: the old worst case."""
        if not self.has_paged:
            return 0
        if self.alloc_policy == "reserve":
            return self._blocks_total(req)
        return -(-len(req.full_prompt) // self.block)

    def _fits(self, req: Request) -> bool:
        return (self._total_positions(req) <= self.cache_len
                and self._blocks_total(req) <= self.alloc.num_blocks - 1)

    def _admission_error(self, req: Request) -> Optional[str]:
        """Why ``req`` can NEVER be admitted (None if it can): the graceful
        replacement for the old hard ``ValueError`` - an oversized request
        retires with this as its per-request error status."""
        length = len(req.prompt)
        if self._total_positions(req) > self.cache_len:
            return (f"prompt ({length}) + max_new ({req.max_new}) exceeds "
                    f"cache_len ({self.cache_len})")
        if self._blocks_total(req) > self.alloc.num_blocks - 1:
            return (f"request {req.rid} needs {self._blocks_total(req)} KV "
                    f"blocks; pool has {self.alloc.num_blocks - 1}")
        return None

    def fail_request(self, req: Request, error: str,
                     kind: str = "admission"):
        """Retire an unadmitted request with a per-request error status
        (failure isolation: the engine and every other request keep going).
        ``kind`` types the failure ("admission" | "prefill" | "decode" |
        "shed" - the scheduler's load-shedding path)."""
        req.done = True
        req.error = error
        req.error_kind = kind
        self.finished.append(req)
        self.failed_requests += 1
        if kind == "shed":
            self.shed_requests += 1
            if self.meter is not None:
                self.meter.note_shed()
        if self.meter is not None:
            self.meter.note_request_failure()
        log.warning("request %d failed (%s): %s", req.rid, kind, error)

    def admit(self, req: Request) -> bool:
        """Single-request admission (compat shim over the batched path)."""
        pending = [req]
        return len(self.admit_pending(pending)) == 1

    def admit_pending(self, pending: List[Request]) -> List[Request]:
        """Admit as many pending requests as slots + KV blocks allow, one
        batched (R, bucket) prefill call per group.  A group is the FIFO
        PREFIX of the queue sharing the head's bucket: strict arrival order
        is preserved (grouping across later same-bucket requests would let
        short prompts overtake an earlier long one and inflate its TTFT).
        Removes admitted requests from ``pending`` and returns the ones that
        reached a slot; a head request that can never fit retires with an
        error status instead of blocking the queue."""
        with TraceAnnotation("engine.admit"):
            return self._admit_pending(pending)

    def _admit_pending(self, pending: List[Request]) -> List[Request]:
        admitted: List[Request] = []
        while pending:
            free_slots = [i for i, s in enumerate(self.slots) if s is None]
            if not free_slots:
                break
            err = self._admission_error(pending[0])
            if err is not None:
                self.fail_request(pending.pop(0), err)
                continue
            if self.prefix is not None:
                # prefix-hit heads admit SOLO through the warm path (linked
                # shared blocks + suffix-only prefill); strict FIFO order is
                # preserved because only the head is considered
                state = self._try_admit_prefix(pending[0], free_slots[0])
                if state == "admitted":
                    admitted.append(pending.pop(0))
                    continue
                if state == "defer":
                    break  # head waits for blocks/evictions to free up
                if state == "failed":
                    pending.pop(0)  # already retired via fail_request
                    continue
                # "miss": fall through to the cold batched path
            with TraceAnnotation("engine.group"):
                bucket = self._bucket(pending[0])
                group = self._choose_group(pending, bucket, len(free_slots))
            if not group:
                break  # head-of-line request waits for blocks to free
            ok = self._admit_group(group, free_slots[: len(group)], bucket)
            del pending[: len(group)]
            admitted.extend(ok)
        return admitted

    def _choose_group(self, pending: List[Request], bucket: int,
                      free: int) -> List[Request]:
        """The FIFO prefix of ``pending`` in ``bucket`` that the free slots
        and blocks hold."""
        group: List[Request] = []
        reserved = 0
        limit = free if self.batch_prefill else 1
        for r in pending:
            if len(group) >= limit or self._bucket(r) != bucket:
                break
            if not self._fits(r):
                # an oversized non-head request ends the prefix BEFORE
                # any allocation; it retires with an error status when
                # it reaches the head (nothing admitted behind it leaks)
                break
            need = self._blocks_needed(r)
            # idle cached prefix blocks count as capacity: _alloc_blocks
            # evicts them (LRU leaf-first) when the free list runs short
            if reserved + need > (self.alloc.free_count
                                  + self.alloc.evictable_count):
                break
            group.append(r)
            reserved += need
        return group

    def _admit_group(self, group: List[Request], slot_ids: List[int],
                     bucket: int) -> List[Request]:
        """Prefill + insert one admitted group; returns the requests that
        actually reached a slot.  A transient device error is retried once
        (the shared ``runtime.fault`` idiom); if the batched prefill still
        fails, its blocks are freed and each member retries SOLO, so a single
        poison request errors out alone instead of taking the group (or the
        engine) down with it."""
        rid_arg = " ".join(str(r.rid) for r in group)
        with TraceAnnotation("engine.prefill", rows=len(group), bucket=bucket,
                             rids=rid_arg):
            prefilled = self._prefill_group(group, slot_ids, bucket)
        if prefilled is None:
            if len(group) == 1:
                return []  # retired by _prefill_group
            # the batched prefill failed for good: each member retries solo
            ok: List[Request] = []
            for r, req in enumerate(group):
                ok.extend(self._admit_group([req], [slot_ids[r]],
                                            self._bucket(req)))
            return ok
        tok0, cache1, slot_vec, bt_rows, true_len, shadow = prefilled
        with TraceAnnotation("engine.insert", rids=rid_arg):
            self.cache, self.last_token, self.pos = self._insert_fn(
                self.cache, {k: v for k, v in cache1.items() if k != "pos"},
                jnp.asarray(slot_vec), jnp.asarray(bt_rows), tok0,
                jnp.asarray(true_len), self.last_token, self.pos,
            )
        self.prefill_calls += 1
        self.prefill_rows += len(group)
        if self.meter is not None:
            # bucket padding is billed work; pow2 pad rows are not
            self.meter.note_prefill(len(group), bucket,
                                    [len(r.prompt) for r in group])
            if shadow:
                self.meter.note_shadow_sample()
        with TraceAnnotation("engine.first_token", rids=rid_arg):
            # one sync per GROUP (TTFT for all rows)
            tok0_host = np.asarray(tok0)
        if self.clock is not None:
            # batched prefill cost: one bucket's worth of token-forwards
            # (rows run in parallel across the banks)
            self.clock.advance(bucket * self.clock.prefill_token_cost)
        t_first = self._now()
        for r, req in enumerate(group):
            sid = slot_vec[r]
            self.slots[sid] = req
            self._slot_pos[sid] = int(true_len[r])
            self._slot_seq[sid] = self._admit_seq
            self._admit_seq += 1
            if self.prefix is not None:
                # a cold admission under an enabled cache is a prefix MISS;
                # its full prompt blocks are indexed for future sharers
                self.prefix_lookups += 1
                if self.meter is not None:
                    self.meter.note_prefix_miss()
                self._register_prefix(req, sid)
            req.out.append(int(tok0_host[r]))
            if req.t_first is None:  # a resumed request keeps its real TTFT
                req.t_first = t_first
            if self.clock is not None:
                req.token_times.append(t_first)
            if len(req.out) >= req.effective_max:
                self._retire(sid)
        return list(group)

    def _prefill_group(self, group: List[Request], slot_ids: List[int],
                       bucket: int):
        """Allocate a group's blocks and dispatch its prefill.  Returns
        ``(tok0, cache1, slot_vec, bt_rows, true_len, shadow)``, or None
        when the prefill failed for good: its blocks are freed, and a lone
        request is retired."""
        now = self._now()
        r_real = len(group)
        r_pad = 1
        while r_pad < r_real:
            r_pad *= 2
        toks = np.zeros((r_pad, bucket), np.int32)
        true_len = np.ones((r_pad,), np.int32)
        # dummy rows scatter to slot index == batch_slots: out of bounds,
        # dropped by the insert's mode="drop" scatters
        slot_vec = np.full((r_pad,), self.batch_slots, np.int32)
        bt_rows = np.zeros((r_pad, self.max_blocks), np.int32)
        for r, req in enumerate(group):
            if req.t_submit is None:
                req.t_submit = now
            # the RESUME prompt: original prompt plus any tokens generated
            # before a preemption (empty out = plain admission, unchanged)
            pvec = req.full_prompt
            length = len(pvec)
            toks[r, :length] = pvec
            true_len[r] = length
            slot_vec[r] = slot_ids[r]
            blocks = self._alloc_blocks(self._blocks_needed(req))
            assert blocks is not None  # reserved in admit_pending
            self._slot_blocks[slot_ids[r]] = blocks
            bt_rows[r, : len(blocks)] = blocks
        shadow = (self._drift is not None and not self._drift_paused()
                  and self._drift.take_prefill_sample())
        pf_key = (r_pad, bucket, shadow, self.substrate.trace_key)
        pf = self._prefill_fns.get(pf_key)
        if pf is None:
            pf = self._prefill_fns[pf_key] = self._make_prefill()
        rids = tuple(r.rid for r in group)

        def run_pf():
            if self.failure_injector is not None:
                self.failure_injector("prefill", rids)
            if shadow:
                with substrate_lib.shadow_recording(self._drift.recorder):
                    return pf(self.params, jnp.asarray(toks),
                              jnp.asarray(true_len), self._next_key(),
                              self._calib)
            return pf(self.params, jnp.asarray(toks), jnp.asarray(true_len),
                      self._next_key(), self._calib)

        try:
            tok0, cache1 = fault_lib.call_with_retries(
                run_pf, 1, retryable=fault_lib.is_transient_device_error,
                describe=f"prefill group rids={list(rids)}", logger=log)
        except Exception as e:
            if not fault_lib.is_transient_device_error(e):
                raise  # programming bugs must surface, not retire requests
            for r in range(r_real):  # nothing was inserted: free the blocks
                sid = slot_ids[r]
                if self._slot_blocks[sid]:
                    self.alloc.free(self._slot_blocks[sid])
                    self._slot_blocks[sid] = []
            if r_real == 1:
                self.fail_request(
                    group[0], f"prefill failed after retry: {e!r}",
                    kind="prefill")
                return None
            log.warning("batched prefill of %d requests failed (%r); "
                        "re-admitting each solo to isolate the poison row",
                        r_real, e)
            return None
        return tok0, cache1, slot_vec, bt_rows, true_len, shadow

    def _make_prefill(self):
        cfg, bucketable = self.cfg, self.bucketable

        def pf(params, toks, true_len, key, calib):
            # cache_len == bucket: the insert redistributes rows into blocks,
            # so prefill never materializes the full-length contiguous cache.
            # calib is the (traced) hot-swappable frozen calibration; None
            # (an empty pytree) under dynamic/digital substrates.
            run_cfg = _cfg_with_calibration(cfg, calib)
            logits, cache1 = prefill(
                params, run_cfg, toks, cache_len=toks.shape[1], rng=key,
                true_len=true_len if bucketable else None,
            )
            tok0 = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return tok0, cache1

        return self._with_rules(jax.jit(pf))

    # -- multi-slot cache insert ----------------------------------------------
    def _insert_impl(self, cache, cache1, slot_vec, bt_rows, tok0, true_len,
                     last_token, pos):
        """One jitted scatter of a whole prefill group into the engine cache:
        paged layers write each row's prompt K/V blocks into the pool and its
        block-table row; contiguous leaves (rings, recurrent states) scatter
        along the slot axis.  Out-of-bounds slot ids (dummy pad rows) drop."""
        bs = self.block

        def put_paged(eng: Dict[str, Any], pref: Dict[str, Any], stacked: bool):
            seq_ax = 2 if stacked else 1
            out = dict(eng)
            bt = eng["bt"]
            if stacked:
                out["bt"] = bt.at[:, slot_vec].set(bt_rows, mode="drop")
            else:
                out["bt"] = bt.at[slot_vec].set(bt_rows, mode="drop")
            for pool_key, kv_key in (("pk", "k"), ("pv", "v")):
                pool, src = eng[pool_key], pref[kv_key]
                s = src.shape[seq_ax]
                s_pad = -(-s // bs) * bs
                pads = [(0, 0)] * src.ndim
                pads[seq_ax] = (0, s_pad - s)
                src = jnp.pad(src, pads).astype(pool.dtype)
                nbb = s_pad // bs
                # logical block j of row r -> physical block bt_rows[r, j]
                # (0 = garbage for blocks past the row's allocation: pad-only
                # bucket tails are discarded, never read)
                dest = bt_rows[:, :nbb].reshape(-1)
                if stacked:
                    nf, r = src.shape[0], src.shape[1]
                    src = src.reshape((nf, r * nbb, bs) + src.shape[3:])
                    out[pool_key] = pool.at[:, dest].set(src)
                else:
                    r = src.shape[0]
                    src = src.reshape((r * nbb, bs) + src.shape[2:])
                    out[pool_key] = pool.at[dest].set(src)
            return out

        def put_leaf(eng, pref, stacked: bool):
            if getattr(eng, "ndim", 0) == 0:
                return eng
            slot_ax = 1 if stacked else 0
            # right-pad short leaves (a prefill ring narrower than the
            # engine's span is identity-layout: bucket < window)
            pads = [(0, 0)] * pref.ndim
            for ax in range(pref.ndim):
                if ax != slot_ax:
                    pads[ax] = (0, eng.shape[ax] - pref.shape[ax])
            src = jnp.pad(pref, pads).astype(eng.dtype)
            if stacked:
                return eng.at[:, slot_vec].set(src, mode="drop")
            return eng.at[slot_vec].set(src, mode="drop")

        def walk(eng, pref, stacked: bool):
            if isinstance(eng, dict) and "pk" in eng:
                return put_paged(eng, pref, stacked)
            if isinstance(eng, dict):
                return {k: walk(v, pref[k], stacked) for k, v in eng.items()}
            return put_leaf(eng, pref, stacked)

        new_cache = {}
        for key, sub in cache.items():
            stacked = key == "blocks"
            new_cache[key] = walk(sub, cache1[key], stacked)
        return (
            new_cache,
            last_token.at[slot_vec].set(tok0, mode="drop"),
            pos.at[slot_vec].set(true_len, mode="drop"),
        )

    def _retire(self, i: int, error: Optional[str] = None,
                kind: str = "decode"):
        req = self.slots[i]
        req.done = True
        req.error = error
        self.slots[i] = None
        self._slot_pos[i] = 0
        self.finished.append(req)
        if error is not None:
            req.error_kind = kind
            self.failed_requests += 1
            if self.meter is not None:
                self.meter.note_request_failure()
            log.warning("request %d failed in slot %d: %s", req.rid, i, error)
        if self._slot_blocks[i]:
            # the stale device block table keeps pointing at these blocks;
            # that is safe because inactive rows write to the garbage block
            self.alloc.free(self._slot_blocks[i])
            self._slot_blocks[i] = []

    # -- lazy allocation + recompute-preemption --------------------------------
    def _preempt(self, i: int):
        """Evict slot ``i`` mid-generation: free its blocks and park the
        request (with its generated tokens) on ``self.preempted`` for the
        serve loop to re-queue.  Re-admission prefills prompt + out, which
        reproduces the decode state exactly - recompute-preemption is
        bit-exact under frozen calibration.  The stale device block table /
        last-token row is safe for the same reason retirement is: inactive
        rows write to the garbage block."""
        req = self.slots[i]
        self.slots[i] = None
        self._slot_pos[i] = 0
        req.preemptions += 1
        if self._slot_blocks[i]:
            self.alloc.free(self._slot_blocks[i])
            self._slot_blocks[i] = []
        self.preempted.append(req)
        self.preempt_count += 1
        if self.meter is not None:
            self.meter.note_preemption()
        log.info("preempted request %d from slot %d (%d tokens kept)",
                 req.rid, i, len(req.out))

    def _pick_victim(self, grower: int) -> Optional[int]:
        """Victim slot for a failed block grow: the LATEST-admitted active
        slot newer than the grower (None if the grower itself is newest).
        Never preempting an older resident means the oldest one always makes
        progress, so grow/preempt cycles terminate."""
        candidates = [i for i, s in enumerate(self.slots)
                      if s is not None and i != grower
                      and self._slot_seq[i] > self._slot_seq[grower]]
        if not candidates:
            return None
        return max(candidates, key=lambda i: self._slot_seq[i])

    def _ensure_blocks(self, n_steps: int):
        """Lazy allocation on block-boundary crossing: before a chunk of
        ``n_steps`` decode writes, every active slot must own blocks covering
        positions ``0 .. pos + n_steps - 1``.  Grows oldest-first; an
        allocation failure preempts victims (``_pick_victim``) until the grow
        fits or the grower itself is preempted.  New (slot, logical block,
        physical block) entries are scattered into the device block tables in
        ONE jitted call."""
        if not self.has_paged or self.alloc_policy != "lazy":
            return
        triples: List[Tuple[int, int, int]] = []
        order = sorted(
            (i for i, s in enumerate(self.slots) if s is not None),
            key=lambda i: self._slot_seq[i])
        for i in order:
            if self.slots[i] is None:
                continue  # preempted as a victim earlier in this pass
            need = -(-(self._slot_pos[i] + n_steps) // self.block)
            deficit = need - len(self._slot_blocks[i])
            if deficit <= 0:
                continue
            got = self._alloc_blocks(deficit)
            while got is None:
                victim = self._pick_victim(i)
                if victim is None:
                    # the grower is the newest resident: it yields (keeping
                    # its tokens) rather than evicting older work
                    self._preempt(i)
                    break
                self._preempt(victim)
                got = self._alloc_blocks(deficit)
            if got is None:
                continue
            have = len(self._slot_blocks[i])
            triples.extend((i, have + j, b) for j, b in enumerate(got))
            self._slot_blocks[i].extend(got)
        if not triples:
            return
        # pad to a power of two (dropped via slot == batch_slots) so the
        # jitted block-table extend compiles per size class, not per count
        n_pad = 1
        while n_pad < len(triples):
            n_pad *= 2
        slot_vec = np.full((n_pad,), self.batch_slots, np.int32)
        log_vec = np.zeros((n_pad,), np.int32)
        phys_vec = np.zeros((n_pad,), np.int32)
        for j, (s, l, p) in enumerate(triples):
            slot_vec[j], log_vec[j], phys_vec[j] = s, l, p
        self.cache = self._extend_fn(
            self.cache, jnp.asarray(slot_vec), jnp.asarray(log_vec),
            jnp.asarray(phys_vec))

    def _extend_impl(self, cache, slot_vec, log_vec, phys_vec):
        """Scatter freshly-allocated physical block ids into every paged
        layer group's block table at (slot, logical) - the device half of a
        lazy grow.  Out-of-bounds slot ids (pad entries) drop."""

        def walk(sub, stacked: bool):
            if isinstance(sub, dict) and "pk" in sub:
                out = dict(sub)
                bt = sub["bt"]
                if stacked:
                    src = jnp.broadcast_to(
                        phys_vec, (bt.shape[0],) + phys_vec.shape)
                    out["bt"] = bt.at[:, slot_vec, log_vec].set(
                        src, mode="drop")
                else:
                    out["bt"] = bt.at[slot_vec, log_vec].set(
                        phys_vec, mode="drop")
                return out
            if isinstance(sub, dict):
                return {k: walk(v, stacked) for k, v in sub.items()}
            return sub

        return {k: walk(v, k == "blocks") for k, v in cache.items()}

    # -- prefix sharing --------------------------------------------------------
    def _alloc_blocks(self, n: int) -> Optional[List[int]]:
        """``alloc`` with eviction pressure: when the free list runs short,
        reclaim idle cached prefix blocks (LRU, leaf-first over the radix
        index) until the allocation fits or nothing is evictable."""
        if n == 0:
            return []
        blocks = self.alloc.alloc(n)
        while blocks is None and self._evict_one():
            blocks = self.alloc.alloc(n)
        return blocks

    def _evict_one(self) -> bool:
        """Evict ONE idle cached block: drop the least-recently-used leaf of
        the radix index whose block holds no references.  Leaf-first keeps
        every remaining chain reachable; referenced leaves (a sharer is
        mid-flight) are skipped."""
        if self.prefix is None:
            return False
        for node in self.prefix.leaves_lru():
            if self.alloc.is_evictable(node.block):
                self.prefix.remove(node)
                self.alloc.evict(node.block)
                self.prefix_evictions += 1
                return True
        return False

    def _register_prefix(self, req: Request, sid: int):
        """Index the admitted request's full prompt blocks so later sharers
        can link them.  Only FULL blocks enter the index (they are immutable:
        every later write for this slot lands at position >= the prompt
        length); newly indexed blocks hand their zero-ref lifetime to the
        allocator's cached set."""
        n_full = len(req.full_prompt) // self.block
        chain = self._slot_blocks[sid][:n_full]
        if n_full == 0 or len(chain) < n_full:
            return
        for b in self.prefix.insert(req.full_prompt, chain):
            self.alloc.register_cached(b)

    def _try_admit_prefix(self, req: Request, slot: int) -> str:
        """Warm admission of a prefix-hit head request into ``slot``.

        Matches the longest chain of cached full blocks prefixing the
        request's (resume) prompt, retains + links those physical blocks
        into the slot's block table, and runs prefill ONLY over the uncached
        suffix as a teacher-forced decode scan (the same decode==prefill
        argmax equivalence the recompute-preemption contract pins, in
        reverse).  At least one token is always re-fed so the final
        position's logits exist; when the whole prompt is cached that
        re-feed writes INSIDE the last shared block, which triggers
        copy-on-write: a one-block jitted pool copy into a fresh private
        block that replaces the shared one in this slot's table only.

        Returns "miss" (no cached prefix - caller takes the cold path),
        "defer" (hit, but blocks are short - head waits), "failed"
        (persistent device error - request retired), or "admitted".
        """
        pvec = req.full_prompt
        nodes = self.prefix.match(pvec)
        if not nodes:
            return "miss"
        length = len(pvec)
        bs = self.block
        m = len(nodes)
        start = min(bs * m, length - 1)  # always re-feed >= 1 token
        t_true = length - start
        cow = (start // bs) < m  # re-feed write lands in a shared block
        keep = nodes[:-1] if cow else nodes
        total = (self._blocks_total(req) if self.alloc_policy == "reserve"
                 else -(-length // bs))
        fresh_n = total - len(keep)
        shared = [n.block for n in keep]
        matched = [n.block for n in nodes]
        # retain EVERY matched block (incl. a CoW source) BEFORE allocating:
        # eviction pressure inside _alloc_blocks must never reclaim a block
        # this admission is about to link or copy from
        self.alloc.retain(matched)
        fresh = self._alloc_blocks(fresh_n)
        if fresh is None:
            self.alloc.free(matched)
            return "defer"
        blocks = list(shared)
        if cow:
            blocks.append(fresh[0])
        blocks.extend(fresh[1:] if cow else fresh)
        if req.t_submit is None:
            req.t_submit = self._now()
        if cow:
            # private copy of the shared block's earlier positions; the
            # suffix scan then overwrites only position ``length - 1``.  The
            # source's extra reference drops once the copy is taken (it
            # stays indexed for other sharers).
            self.cache = self._cow_fn(self.cache,
                                      jnp.int32(nodes[-1].block),
                                      jnp.int32(fresh[0]))
            self.alloc.free([nodes[-1].block])
            self.cow_copies += 1
            if self.meter is not None:
                self.meter.note_cow_copy()
        bt_row = np.zeros((self.max_blocks,), np.int32)
        bt_row[: len(blocks)] = blocks
        t_pad = 1
        while t_pad < t_true:
            t_pad *= 2
        toks = np.zeros((self.batch_slots, t_pad), np.int32)
        toks[slot, :t_true] = pvec[start:]
        fn_key = (t_pad, self.substrate.trace_key)
        fn = self._warm_fns.get(fn_key)
        if fn is None:
            fn = self._warm_fns[fn_key] = self._make_warm(t_pad)

        def run_warm():
            if self.failure_injector is not None:
                self.failure_injector("prefill", (req.rid,))
            return fn(self.params, self.cache, jnp.asarray(bt_row),
                      jnp.int32(slot), jnp.asarray(toks),
                      jnp.int32(t_true), jnp.int32(start),
                      self.last_token, self.pos, self._next_key(),
                      self._calib)

        try:
            cache, last_token, pos, tok0 = fault_lib.call_with_retries(
                run_warm, 1, retryable=fault_lib.is_transient_device_error,
                describe=f"warm prefill rid={req.rid}", logger=log)
        except Exception as e:
            if not fault_lib.is_transient_device_error(e):
                raise
            # the pure warm fn never committed: device block tables are
            # untouched, so releasing the references fully unwinds
            self.alloc.free(blocks)
            self.fail_request(
                req, f"warm prefill failed after retry: {e!r}",
                kind="prefill")
            return "failed"
        self.cache, self.last_token, self.pos = cache, last_token, pos
        self._slot_blocks[slot] = blocks
        self.slots[slot] = req
        self._slot_pos[slot] = length
        self._slot_seq[slot] = self._admit_seq
        self._admit_seq += 1
        self.prefill_calls += 1
        self.prefill_rows += 1
        self.prefix_lookups += 1
        self.prefix_hits += 1
        self.prefix_hit_tokens += start
        cold_bucket = self._bucket(req)
        self.prefix_saved_tokens += max(0, cold_bucket - t_true)
        if self.meter is not None:
            self.meter.note_prefix_admission(t_true, cold_bucket, start)
        if self.clock is not None:
            self.clock.advance(t_true * self.clock.prefill_token_cost)
        # register BEFORE appending tok0: tok0's K/V is not in the cache yet
        # (it is written when fed back on the first decode step)
        self._register_prefix(req, slot)
        t_first = self._now()
        req.out.append(int(tok0))
        if req.t_first is None:
            req.t_first = t_first
        if self.clock is not None:
            req.token_times.append(t_first)
        if len(req.out) >= req.effective_max:
            self._retire(slot)
        log.info("prefix hit request %d: %d/%d tokens cached (%d blocks, "
                 "suffix %d%s)", req.rid, start, length, m, t_true,
                 ", CoW" if cow else "")
        return "admitted"

    def _make_warm(self, t_pad: int):
        """Suffix prefill as a teacher-forced fused decode scan: link the
        slot's block-table row, start from ``start_pos``, feed the suffix
        tokens one step at a time (inactive rows and pad steps write to the
        garbage block), and return the final true step's argmax - exactly
        the ``tok0`` a cold bucketed prefill of the full prompt produces."""
        cfg = self.cfg

        def warm(params, cache, bt_row, slot, toks, t_true, start_pos,
                 last_tok, pos, key, calib):
            run_cfg = _cfg_with_calibration(cfg, calib)

            def link(sub, stacked):
                if isinstance(sub, dict) and "pk" in sub:
                    out = dict(sub)
                    bt = sub["bt"]
                    if stacked:
                        src = jnp.broadcast_to(
                            bt_row, (bt.shape[0],) + bt_row.shape)
                        out["bt"] = bt.at[:, slot].set(src)
                    else:
                        out["bt"] = bt.at[slot].set(bt_row)
                    return out
                if isinstance(sub, dict):
                    return {k: link(v, stacked) for k, v in sub.items()}
                return sub

            cache = {k: link(v, k == "blocks") for k, v in cache.items()}
            pos = pos.at[slot].set(start_pos)
            row = jnp.arange(pos.shape[0]) == slot

            def step(carry, t):
                cache, pos, out = carry
                fed = toks[:, t]
                act = row & (t < t_true)
                k = None if key is None else jax.random.fold_in(key, t)
                logits, new_cache = decode_step(
                    params, run_cfg, fed, dict(cache, pos=pos), rng=k,
                    active=act,
                )
                nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                out = jnp.where(t < t_true, nxt[slot], out)
                new_pos = jnp.where(act, pos + 1, pos)
                new_cache.pop("pos")
                return (new_cache, new_pos, out), None

            (cache, pos, out), _ = jax.lax.scan(
                step, (cache, pos, last_tok[slot]), jnp.arange(t_pad))
            return cache, last_tok.at[slot].set(out), pos, out

        if self.tp > 1:
            rep = self._rep_sharding
            return self._with_rules(jax.jit(
                warm, out_shardings=(self._cache_shardings, rep, rep, rep)))
        return jax.jit(warm)

    def _cow_impl(self, cache, src, dst):
        """Copy one physical block's K/V across every paged layer group -
        the device half of copy-on-write.  One jitted call per CoW event;
        the block-table rewrite rides the warm scan's link step."""

        def walk(sub, stacked):
            if isinstance(sub, dict) and "pk" in sub:
                out = dict(sub)
                for pool_key in ("pk", "pv"):
                    pool = sub[pool_key]
                    if stacked:
                        out[pool_key] = pool.at[:, dst].set(pool[:, src])
                    else:
                        out[pool_key] = pool.at[dst].set(pool[src])
                return out
            if isinstance(sub, dict):
                return {k: walk(v, stacked) for k, v in sub.items()}
            return sub

        return {k: walk(v, k == "blocks") for k, v in cache.items()}

    def prefix_stats(self) -> Dict[str, Any]:
        """Host-side prefix-sharing scoreboard (bench/CLI surface)."""
        lookups = self.prefix_lookups
        return {
            "enabled": self.prefix is not None,
            "lookups": lookups,
            "hits": self.prefix_hits,
            "hit_rate": round(self.prefix_hits / lookups, 4) if lookups
            else 0.0,
            "hit_tokens": self.prefix_hit_tokens,
            "saved_billed_tokens": self.prefix_saved_tokens,
            "cow_copies": self.cow_copies,
            "evictions": self.prefix_evictions,
            "cached_blocks": len(self.prefix) if self.prefix else 0,
        }

    # -- online calibration ----------------------------------------------------
    def swap_calibration(self, calibration: substrate_lib.Calibration):
        """Atomically install a refreshed frozen calibration.

        Contract (documented in ``core.substrate``): call ONLY between
        chunks - the engine is synchronous, so any call site outside
        ``decode_chunk``/``_admit_group`` is a chunk boundary.  The refreshed
        calibration must carry the same site names as the frozen one (same
        pytree treedef; build it with ``runtime.drift.refreshed_calibration``)
        so every compiled decode/prefill executable is re-used - the swap is
        a host-side pointer update, never a recompile.
        """
        if self._calib is None:
            raise ValueError(
                "swap_calibration requires a frozen-policy substrate")
        if calibration.site_names() != self._calib.site_names():
            raise ValueError(
                "refreshed calibration must preserve the frozen site-name "
                "structure (same pytree treedef); merge with the frozen "
                "calibration first (runtime.drift.refreshed_calibration): "
                f"{calibration.site_names()} != {self._calib.site_names()}")
        old = self.substrate
        self.substrate = old.frozen(calibration)
        self.cfg = self.cfg.replace(imc=self.substrate)
        self._calib = calibration
        self.swap_count += 1
        if self.meter is not None:
            self.meter.note_swap()
            if self.meter.substrate is old:
                self.meter.substrate = self.substrate

    def swap_substrate(self, substrate, time_scale: float = 1.0):
        """Hot-swap the execution substrate (load-adaptive frontier
        degradation).  Call only between chunks - same atomicity contract as
        ``swap_calibration``.  The engine's live frozen calibration (if any)
        is re-attached to the incoming substrate, so site names - and with
        them the calibration treedef - are preserved; the prefill/decode jit
        caches are keyed on ``Substrate.trace_key``, so each distinct ladder
        level compiles once and every later move to it is a host-side
        pointer update.  ``time_scale`` is the new per-decode-step virtual
        cost (a degraded design point's frontier delay ratio < 1)."""
        sub = substrate_lib.as_substrate(substrate)
        if self._calib is not None:
            sub = sub.frozen(self._calib)
        old = self.substrate
        self.substrate = sub
        self.cfg = self.cfg.replace(imc=sub)
        self.substrate_swaps += 1
        if self.clock is not None:
            self.clock.time_scale = time_scale
        if self.meter is not None:
            self.meter.note_substrate_swap(sub)
            if self.meter.substrate is old:
                self.meter.substrate = sub

    def _drift_paused(self) -> bool:
        """Shadow sampling pauses while the serve loop reports a queue above
        the pressure threshold: the cadence phase freezes (``take_sample`` is
        simply not consulted) and resumes untouched when pressure clears, so
        the DriftMonitor callback tax never lands at peak load."""
        return (self.drift_pause_depth is not None
                and self.queue_depth > self.drift_pause_depth)

    def _maybe_check_drift(self):
        """After a shadow-sampled chunk: run the detector at the monitor's
        cadence and hot-swap the refreshed calibration on a drifted report
        (we are between chunks here, so the swap is atomic by construction)."""
        mon = self._drift
        report = mon.check(self._calib)
        if report is None:
            return
        if self.meter is not None:
            self.meter.note_drift_report(report.to_dict())
        log.info("drift check %d: %s", mon.checks, report.summary_line())
        if report.drifted and mon.cfg.auto_swap:
            self.swap_calibration(mon.refreshed(self._calib))
            mon.note_swap()
            log.info("hot-swapped refreshed calibration (swap %d) at sites "
                     "%s", self.swap_count, list(report.drifted_sites))

    # -- fused decode ----------------------------------------------------------
    def next_chunk(self) -> int:
        """Largest power-of-two scan length no active request overruns
        (EOS-capped: an early-stopping request bounds the chunk at its true
        remaining generation, not its worst-case cap)."""
        rem = [r.effective_max - len(r.out) for r in self.slots
               if r is not None]
        if not rem:
            return 0
        cap = min(min(rem), self.max_chunk)
        t = 1
        while t * 2 <= cap:
            t *= 2
        return t

    def _make_decode(self, n_steps: int):
        cfg = self.cfg

        def chunk(params, cache, last_tok, pos, active, key, calib):
            # calib: the hot-swappable frozen calibration, a traced pytree
            # argument - one consistent set of ranges for the WHOLE chunk
            run_cfg = _cfg_with_calibration(cfg, calib)

            def step(carry, t):
                cache, tok, pos = carry
                k = None if key is None else jax.random.fold_in(key, t)
                logits, new_cache = decode_step(
                    params, run_cfg, tok, dict(cache, pos=pos), rng=k,
                    active=active,
                )
                with jax.named_scope("sample"):
                    nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                    nxt = jnp.where(active, nxt, tok)
                new_pos = jnp.where(active, pos + 1, pos)
                new_cache.pop("pos")
                return (new_cache, nxt, new_pos), nxt

            (cache, tok, pos), toks = jax.lax.scan(
                step, (cache, last_tok, pos), jnp.arange(n_steps)
            )
            return cache, tok, pos, toks.T  # (slots, T)

        if self.tp > 1:
            # the (slots, T) token block is REPLICATED (every shard holds the
            # same argmax'd tokens), so the one-transfer-per-chunk contract
            # survives sharding: nothing else crosses to the host.  The cache
            # keeps its head-sharded placement across chunks.
            rep = self._rep_sharding
            return self._with_rules(jax.jit(
                chunk,
                out_shardings=(self._cache_shardings, rep, rep, rep)))
        return jax.jit(chunk)

    def compiled_decode_text(self, n_steps: int) -> str:
        """Optimized HLO of the fused decode chunk of ``n_steps`` steps at
        the engine's current shapes - the program ``decode_chunk`` runs -
        for checking which kernels the device program holds.  Single-device
        engines only (the sharded chunk is wrapped in its axis rules)."""
        if self.tp > 1:
            raise ValueError("compiled_decode_text needs a single-device "
                             "engine")
        fn = self._decode_fns.get((n_steps, False, self.substrate.trace_key))
        if fn is None:
            fn = self._make_decode(n_steps)
        active = jnp.zeros((self.batch_slots,), bool)
        return fn.lower(self.params, self.cache, self.last_token, self.pos,
                        active, self.rng, self._calib).compile().as_text()

    def prompt_logits(self, prompt) -> np.ndarray:
        """f32 logits at the last position of a lone prefill of ``prompt`` -
        the distribution the first generated token is the argmax of - under
        the engine's placement and axis rules (to compare engines, e.g. a
        sharded one against a single device)."""
        if self._logits_fn is None:
            cfg = self.cfg

            def last_logits(params, toks, calib):
                logits, _ = prefill(params, _cfg_with_calibration(cfg, calib),
                                    toks, cache_len=toks.shape[1])
                return logits[0, -1].astype(jnp.float32)

            self._logits_fn = self._with_rules(jax.jit(last_logits))
        toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
        return np.asarray(self._logits_fn(self.params, toks, self._calib))

    def decode_chunk(self, n_steps: Optional[int] = None) -> np.ndarray:
        """Run ``n_steps`` fused decode steps; returns the (slots, T) token
        block (the single device->host transfer of the chunk).

        A transient device error (``JaxRuntimeError``) is retried once via
        the shared ``runtime.fault`` idiom - the chunk function is pure, so
        the re-run is exact; if the error persists, only the requests in
        flight retire with an error status and the engine survives."""
        with TraceAnnotation("engine.decode"):
            return self._decode_chunk(n_steps)

    def _decode_chunk(self, n_steps: Optional[int]) -> np.ndarray:
        if n_steps is None:
            n_steps = self.next_chunk()
        if n_steps <= 0:
            return np.zeros((self.batch_slots, 0), np.int32)
        # lazy growth (may preempt: the active set below reflects it)
        with TraceAnnotation("engine.grow"):
            self._ensure_blocks(n_steps)
        if self.active == 0:
            return np.zeros((self.batch_slots, 0), np.int32)
        live_blocks = self._walked_blocks(n_steps)
        with TraceAnnotation("engine.dispatch", steps=n_steps,
                             active=self.active, live_blocks=live_blocks):
            shadow = (self._drift is not None and self.active > 0
                      and not self._drift_paused()
                      and self._drift.take_sample())
            fn_key = (n_steps, shadow, self.substrate.trace_key)
            fn = self._decode_fns.get(fn_key)
            if fn is None:
                fn = self._decode_fns[fn_key] = self._make_decode(n_steps)
            active_host = np.array([s is not None for s in self.slots])
            args = (self.params, self.cache, self.last_token, self.pos,
                    jnp.asarray(active_host), self._next_key(), self._calib)

            def run_chunk():
                if self.failure_injector is not None:
                    self.failure_injector("decode", self.decode_calls)
                if shadow:
                    with substrate_lib.shadow_recording(self._drift.recorder):
                        return fn(*args)
                return fn(*args)

            try:
                cache, last_token, pos, toks = fault_lib.call_with_retries(
                    run_chunk, 1,
                    retryable=fault_lib.is_transient_device_error,
                    describe=f"decode chunk {self.decode_calls}", logger=log)
            except Exception as e:
                if not fault_lib.is_transient_device_error(e):
                    raise  # programming bugs must surface, not retire requests
                # persistent device error: the chunk never committed (device
                # state is untouched - assignment below did not happen), so
                # fail exactly the requests that were in flight and keep
                # serving
                self.decode_failures += 1
                msg = f"decode chunk failed after retry: {e!r}"
                log.warning("%s; failing %d in-flight requests", msg,
                            self.active)
                for i, req in enumerate(self.slots):
                    if req is not None:
                        self._retire(i, error=msg)
                return np.zeros((self.batch_slots, 0), np.int32)
        self.cache, self.last_token, self.pos = cache, last_token, pos
        with TraceAnnotation("engine.sync"):
            block = np.asarray(toks)  # the one host transfer per chunk
        with TraceAnnotation("engine.deliver"):
            self._deliver(block, n_steps, int(active_host.sum()), shadow,
                          live_blocks)
        return block

    def _walked_blocks(self, n_steps: int) -> int:
        """Blocks the paged decode walk reads in a chunk of ``n_steps``,
        from the host positions: at each step, every active slot's blocks
        through its tail block (``kernels.paged_attention``'s live count)."""
        if not self.has_paged:
            return 0
        pos = np.array([p for p, r in zip(self._slot_pos, self.slots)
                        if r is not None], np.int64)
        steps = pos[:, None] + np.arange(n_steps)[None, :]
        return int(np.minimum(steps // self.block + 1, self.max_blocks).sum())

    def _deliver(self, block: np.ndarray, n_steps: int, n_active: int,
                 shadow: bool, live_blocks: int) -> None:
        """Account a finished chunk and hand its tokens to the requests,
        retiring those that are done."""
        if self.meter is not None:
            # active slots at chunk start each ran n_steps token-forwards
            self.meter.note_decode(n_active, n_steps)
            if shadow:
                self.meter.note_shadow_sample()
        self.decode_calls += 1
        self.decode_steps += n_steps
        self.host_transfer_bytes += block.nbytes
        self.kv_blocks_walked += live_blocks
        if self.has_paged:
            self.kv_blocks_tabled += n_active * n_steps * self.max_blocks
        # pool-utilization sample: tokens live in active caches vs the token
        # capacity of the blocks currently allocated (lazy vs reserve signal)
        if self.has_paged and self.alloc.used_count:
            self._util_token_sum += self.live_tokens()
            self._util_cap_sum += self.alloc.used_count * self.block
        dt = None
        if self.clock is not None:
            dt = self.clock.time_scale
            self.clock.advance(n_steps * dt)
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            self._slot_pos[i] += n_steps
            take = min(n_steps, req.effective_max - len(req.out))
            req.out.extend(int(t) for t in block[i, :take])
            if dt is not None:
                t_end = self.clock.now
                req.token_times.extend(
                    t_end - (take - 1 - j) * dt for j in range(take))
            if len(req.out) >= req.effective_max:
                self._retire(i)
        if shadow:
            self._maybe_check_drift()


def serve(engine: Engine, requests: List[Request]) -> List[Request]:
    """Drive the engine until every request finishes (successfully or with a
    per-request error status); returns them in completion order.

    Graceful degradation: a head-of-line request the idle engine can never
    admit (the old hard ``RuntimeError`` deadlock) retires with an error
    status and serving continues for everyone else."""
    pending = list(requests)
    done_mark = len(engine.finished)
    while pending or engine.active:
        admitted = engine.admit_pending(pending)
        for req in admitted:
            log.info("admitted request %d len=%d (active=%d)",
                     req.rid, len(req.prompt), engine.active)
        if pending and not engine.active and not admitted:
            # nothing is running, nothing could be admitted, and the queue
            # is non-empty: the head request is stuck (e.g. its block demand
            # exceeds what an idle pool can ever free).  Retire IT, not the
            # engine - everyone behind it gets served.
            engine.fail_request(
                pending.pop(0),
                "cannot be admitted into an idle engine (slots or KV block "
                "pool too small)")
            continue
        engine.decode_chunk()
        if engine.preempted:
            # recompute-preempted requests re-enter at the FRONT: they hold
            # generated tokens (partial work) and freeing their successor
            # blocks fastest means finishing them first
            pending[:0] = engine.preempted
            engine.preempted.clear()
        for r in engine.finished[done_mark:]:
            if r.error is None:
                log.info("finished request %d: %d tokens", r.rid, len(r.out))
        done_mark = len(engine.finished)
    return engine.finished


def serve_slo(engine: Engine, requests: List[Request], policy=None,
              controller=None) -> List[Request]:
    """Real-time SLO serve loop: requests ARRIVE at their ``arrive_at``
    virtual times, a ``launch.scheduler`` policy orders the queue and sheds
    hopeless work, preempted requests re-queue at the front, and an optional
    ``PressureController`` walks the EDAP frontier under load.

    Every submitted request leaves through ``engine.finished`` exactly once -
    completed, errored, or shed (request conservation, property-pinned).  The
    loop is duck-typed over the engine (attributes: ``clock``, ``queue_depth``,
    ``active``, ``preempted``, ``finished``; methods: ``admit_pending``,
    ``decode_chunk``, ``fail_request``), so model-free fakes can drive the
    scheduling invariants in tests."""
    from repro.launch.scheduler import FIFOPolicy
    from repro.runtime.workload import VirtualClock

    if policy is None:
        policy = FIFOPolicy()
    if engine.clock is None:
        engine.clock = VirtualClock()
    clock = engine.clock
    arrivals = sorted(requests, key=lambda r: (
        r.arrive_at if r.arrive_at is not None else 0.0, r.rid))
    queue: List[Request] = []
    while arrivals or queue or engine.active:
        while arrivals and (arrivals[0].arrive_at is None
                            or arrivals[0].arrive_at <= clock.now):
            queue.append(arrivals.pop(0))
        if not queue and not engine.active:
            # idle gap: jump to the next arrival instead of spinning
            clock.advance(max(0.0, arrivals[0].arrive_at - clock.now))
            continue
        engine.queue_depth = len(queue)
        if controller is not None:
            controller.update()
        for r in policy.shed(queue, clock.now):
            engine.fail_request(
                r, f"shed by {policy.name} policy at t={clock.now:.1f} "
                   f"(TTFT deadline unmeetable)", kind="shed")
        policy.order(queue, clock.now)
        admitted = engine.admit_pending(queue)
        engine.queue_depth = len(queue)
        if queue and not engine.active and not admitted:
            engine.fail_request(
                queue.pop(0),
                "cannot be admitted into an idle engine (slots or KV block "
                "pool too small)")
            continue
        engine.decode_chunk()
        if engine.preempted:
            queue[:0] = engine.preempted
            engine.preempted.clear()
    engine.queue_depth = 0
    return engine.finished


def main(argv=None, on_engine: Optional[Callable[[Engine], None]] = None):
    """Serve seeded traffic from the command line; returns the finished
    requests.  ``on_engine`` is called with the engine once it is built,
    before any request is served.  Raises ``RuntimeError`` when a request of
    the offline replay finishes with an error status."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(configs.ARCH_NAMES))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--prompt-lens", default=None,
                    help="comma list of prompt lengths cycled over the "
                         "requests (unequal-length admission); overrides "
                         "--prompt-len")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=8,
                    help="max fused decode steps per jitted scan call")
    ap.add_argument("--block", type=int, default=DEFAULT_BLOCK,
                    help="tokens per paged-KV block")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="physical KV pool size in blocks (default: full "
                         "provisioning, slots * max_blocks + 1)")
    ap.add_argument("--imc-mode", default=None,
                    choices=[None, "fakequant", "imc_analytic",
                             "imc_bitserial"])
    ap.add_argument("--imc-vwl", type=float, default=0.7)
    ap.add_argument("--imc-policy", default="dynamic",
                    choices=["dynamic", "frozen"],
                    help="substrate calibration policy: 'frozen' calibrates "
                         "quantizer ranges on a synthetic reference batch "
                         "before serving and disables the shared analog-"
                         "noise RNG, making IMC outputs batch-composition-"
                         "invariant (batched == sequential, bit-identical)")
    ap.add_argument("--recalibrate", action="store_true",
                    help="online calibration (requires --imc-policy frozen): "
                         "shadow-record a sample of live chunks, detect "
                         "range drift with the one-sided superset test, and "
                         "hot-swap a refreshed calibration at a chunk "
                         "boundary (no recompile, no pause)")
    ap.add_argument("--drift-sample-every", type=int, default=2,
                    help="shadow-record every Nth decode chunk / prefill "
                         "group (with --recalibrate)")
    ap.add_argument("--drift-check-every", type=int, default=2,
                    help="run the drift detector every Nth shadow sample")
    ap.add_argument("--inject-drift", default=None, metavar="SCALE@REQS",
                    help="drift-injection demo: serve the first REQS "
                         "requests, then scale the token embedding by SCALE "
                         "(an activation-scale shift at every downstream "
                         "site) and serve the rest; prints the drift report "
                         "and the post-swap SNR_T recovery table")
    ap.add_argument("--energy-report", action="store_true",
                    help="meter the served traffic and print J/token, "
                         "J/request and EDP/token at the min-energy QS/QR/CM "
                         "design points (512-row banks, two SNR_T targets); "
                         "sites are the FULL (non-smoke) model's matmuls, so "
                         "smoke runs still report deployment-scale energy")
    ap.add_argument("--energy-snr-db", default="14,26",
                    help="comma list of SNR_T targets for --energy-report")
    ap.add_argument("--workload", default="none",
                    choices=["none", "poisson", "bursty"],
                    help="SLO workload mode: generate seeded timed arrivals "
                         "(runtime.workload) and drive the engine through "
                         "the real-time serve_slo loop under --slo-policy "
                         "instead of replaying --prompt-lens offline")
    ap.add_argument("--workload-seed", type=int, default=0,
                    help="workload generator seed (every draw - arrivals, "
                         "lengths, classes - is reproducible from it)")
    ap.add_argument("--overload", type=float, default=2.0,
                    help="offered load as a multiple of engine capacity "
                         "(with --workload)")
    ap.add_argument("--slo-policy", default="fifo",
                    choices=["fifo", "sjf", "deadline"],
                    help="scheduler policy for the SLO loop: fifo, "
                         "shortest-prompt-first, or SLO-deadline admission "
                         "with load shedding")
    ap.add_argument("--alloc", default="lazy", choices=["lazy", "reserve"],
                    help="KV block allocation: lazy (allocate on block-"
                         "boundary crossing, preempt on pool exhaustion) or "
                         "reserve (worst-case at admission)")
    ap.add_argument("--degrade", action="store_true",
                    help="load-adaptive frontier degradation: under queue/"
                         "pool pressure, hot-swap the substrate one step "
                         "down the EDAP frontier (lower B_ADC) and back up "
                         "when pressure clears (requires --imc-mode "
                         "imc_analytic --imc-policy frozen)")
    ap.add_argument("--drift-pause-depth", type=int, default=None,
                    help="pause drift shadow sampling while the queue is "
                         "deeper than this (saturation guard)")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="prefix-sharing paged KV: a radix index over token "
                         "prefixes at block granularity links already-cached "
                         "blocks into new requests' block tables (refcounted "
                         "copy-on-write); admission prefills only the "
                         "uncached suffix.  Greedy tokens are identical to "
                         "a cold-cache run under frozen calibration")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="shared-system-prompt traffic: every request's "
                         "prompt starts with this many common tokens "
                         "(drawn once per run) followed by its unique "
                         "--prompt-len(s) tail; with --workload the shared "
                         "prefixes come from per-class seeded pools instead")
    ap.add_argument("--prefix-dup", type=int, default=4,
                    help="with --workload and --shared-prefix-len: requests "
                         "per distinct shared prefix within each request "
                         "class (the duplication factor)")
    ap.add_argument("--decode-attn", default="kernel",
                    choices=["kernel", "gather"],
                    help="paged decode attention: 'kernel' streams KV blocks "
                         "through the fused online-softmax paged-attention "
                         "kernel (default); 'gather' is the reference escape "
                         "hatch that materializes pool[bt] each step.  Baked "
                         "into the engine cfg at construction (static at "
                         "trace time), so it cannot thrash the jit caches")
    ap.add_argument("--mesh", default=None, metavar="RxC",
                    help="serve over a (data, model) device mesh, e.g. 1x8: "
                         "tensor-parallel weights + head-sharded paged KV "
                         "pools (replicated pools when Hkv does not divide "
                         "the model axis).  Needs R*C devices - on CPU set "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    use_compile_cache()

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    cfg = cfg.replace(decode_attn=args.decode_attn)
    rng = None
    base_pt = None
    if args.degrade:
        if not (args.imc_mode == "imc_analytic"
                and args.imc_policy == "frozen"):
            ap.error("--degrade requires --imc-mode imc_analytic "
                     "--imc-policy frozen (the frontier ladder re-freezes "
                     "each level against the live calibration)")
        from repro.core.design import optimize

        base_pt = optimize(n=512, snr_t_target_db=26.0, kinds=("qr",))
    if args.imc_mode:
        from repro.core.imc_linear import IMCConfig

        if base_pt is not None:
            # start at the committed frontier point: the PressureController's
            # ladder level 0 IS this substrate
            sub = substrate_lib.substrate_for_design(base_pt)
        else:
            sub = substrate_lib.as_substrate(
                IMCConfig(mode=args.imc_mode, bx=7, bw=7, v_wl=args.imc_vwl))
        cfg = cfg.replace(imc=sub)
        rng = jax.random.PRNGKey(7)

    if args.prompt_lens:
        lens = [int(x) for x in args.prompt_lens.split(",")]
    else:
        lens = [args.prompt_len]
    params = init_params(jax.random.PRNGKey(0), cfg)
    if args.imc_mode and args.imc_policy == "frozen":
        # freeze quantizer ranges on a synthetic reference batch: served
        # outputs become independent of how requests are batched together.
        # The engine-wide noise RNG must also go: its draws are shaped by
        # the batch (slots x step), so leaving it on would break the
        # batched == sequential bit-identity the frozen policy advertises.
        rng = None
        ref = np.random.default_rng(1).integers(
            0, cfg.vocab_size, (2, max(lens) if lens else 32))
        cfg = substrate_lib.calibrate_model(cfg, params, [ref])
        log.info("froze substrate calibration on a %s reference batch "
                 "(%d sites); analog-noise RNG disabled for "
                 "batch-invariance", ref.shape,
                 len(cfg.imc.calibration.site_names()))
    bucketable = not needs_exact_prefill(cfg)
    max_bucket = max(prefill_bucket(l + args.shared_prefix_len, bucketable,
                                    10**9) for l in lens)
    cache_len = max_bucket + args.gen + 8
    meter = None
    if args.energy_report:
        from repro.launch.metering import DPMeter

        meter = DPMeter(configs.get(args.arch))
    monitor = None
    if args.recalibrate:
        if not (args.imc_mode and args.imc_policy == "frozen"):
            ap.error("--recalibrate requires --imc-mode and "
                     "--imc-policy frozen")
        monitor = drift_lib.DriftMonitor(drift_lib.DriftConfig(
            sample_every=args.drift_sample_every,
            check_every=args.drift_check_every))
    frozen0 = cfg.imc.calibration if args.imc_policy == "frozen" and \
        args.imc_mode else None
    clock = None
    if args.workload != "none":
        from repro.runtime.workload import VirtualClock

        clock = VirtualClock()
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_serve_mesh, parse_mesh_shape

        try:
            mesh = make_serve_mesh(*parse_mesh_shape(args.mesh))
        except ValueError as e:
            ap.error(str(e))
        log.info("serving over a %s mesh (%d devices visible)", args.mesh,
                 len(jax.devices()))
    engine = Engine(cfg, params, args.batch, cache_len, rng=rng,
                    max_chunk=args.chunk, block_size=args.block,
                    kv_blocks=args.kv_blocks, meter=meter,
                    drift_monitor=monitor, alloc_policy=args.alloc,
                    clock=clock, drift_pause_depth=args.drift_pause_depth,
                    mesh=mesh, prefix_cache=args.prefix_cache)
    if on_engine is not None:
        on_engine(engine)

    if args.workload != "none":
        from repro.launch.metering import format_slo_summary, slo_summary
        from repro.launch.scheduler import PressureController, make_policy
        from repro.runtime import workload as workload_lib

        wcfg = workload_lib.make_overload_config(
            n_requests=args.requests, seed=args.workload_seed,
            overload=args.overload, slots=args.batch, max_new=args.gen,
            arrival=args.workload, prefix_len=args.shared_prefix_len,
            prefix_dup=args.prefix_dup)
        requests = workload_lib.generate(wcfg, cfg.vocab_size)
        policy = make_policy(args.slo_policy)
        controller = None
        if base_pt is not None:
            controller = PressureController(
                engine, substrate_lib.substrate_ladder(base_pt, steps=2))
        finished = serve_slo(engine, requests, policy=policy,
                             controller=controller)
        summary = slo_summary(finished, elapsed=engine.clock.now,
                              policy=policy.name,
                              prefix_hits=engine.prefix_hits,
                              cow_copies=engine.cow_copies)
        summary.update(
            preemptions=engine.preempt_count,
            shed=engine.shed_requests,
            pool_utilization=round(engine.pool_utilization(), 4),
            substrate_swaps=engine.substrate_swaps,
        )
        if controller is not None:
            summary.update(controller.counters())
        print(f"serve_slo [{args.workload} x{args.overload:g} overload, "
              f"policy={policy.name}, alloc={args.alloc}]:")
        print(format_slo_summary(summary))
        return finished

    rnp = np.random.default_rng(0)
    shared_prefix = (rnp.integers(0, cfg.vocab_size, args.shared_prefix_len)
                     if args.shared_prefix_len else None)
    requests = []
    for i in range(args.requests):
        tail = rnp.integers(0, cfg.vocab_size, lens[i % len(lens)])
        prompt = (np.concatenate([shared_prefix, tail])
                  if shared_prefix is not None else tail)
        requests.append(Request(rid=i, prompt=prompt, max_new=args.gen))
    t0 = time.perf_counter()
    if args.inject_drift:
        scale_s, _, after_s = args.inject_drift.partition("@")
        scale, after = float(scale_s), int(after_s or len(requests) // 2)
        serve(engine, requests[:after])

        # mid-workload scale shift on every mlp.wi weight: drifts w_max at
        # mlp.wi and the activation range feeding mlp.wo.  The shift must
        # live in the weights - the model is pre-norm, so an embedding-scale
        # shift would be normalized away before every matmul site
        def _scale_wi(p):
            if isinstance(p, dict):
                return {k: (v * scale if k == "wi" else _scale_wi(v))
                        for k, v in p.items()}
            return p

        engine.params = _scale_wi(engine.params)
        log.info("injected mlp.wi weight-scale drift x%.2f after %d "
                 "requests", scale, after)
        serve(engine, requests[after:])
        finished = engine.finished
    else:
        finished = serve(engine, requests)
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.out) for r in finished)
    tok_s = total_tokens / dt if dt > 0 else float("nan")
    ttfts = [r.ttft for r in finished if r.ttft is not None]
    ttft_ms = 1e3 * float(np.mean(ttfts)) if ttfts else float("nan")
    log.info(
        "served %d requests, %d tokens, %d fused chunks (%d steps), "
        "%d prefill calls (%d rows), %.1f tok/s, mean TTFT %.1f ms, "
        "%d host-transfer bytes, %d KV blocks in pool",
        len(finished), total_tokens, engine.decode_calls,
        engine.decode_steps, engine.prefill_calls, engine.prefill_rows,
        tok_s, ttft_ms, engine.host_transfer_bytes, engine.alloc.num_blocks,
    )
    failed = [r for r in finished if r.error is not None]
    if failed:
        raise RuntimeError(
            f"{len(failed)} request(s) finished with an error status: "
            + "; ".join(f"request {r.rid}: {r.error}" for r in failed))
    if args.prefix_cache:
        ps = engine.prefix_stats()
        print(f"prefix cache: hit_rate={ps['hit_rate']:.2f} "
              f"({ps['hits']}/{ps['lookups']} admissions), "
              f"prefill tokens skipped={ps['hit_tokens']}, "
              f"billed prefill tokens saved={ps['saved_billed_tokens']}, "
              f"cow_copies={ps['cow_copies']}, "
              f"evictions={ps['evictions']}, "
              f"cached_blocks={ps['cached_blocks']}")
    if monitor is not None:
        c = monitor.counters()
        print(f"online calibration: {c['shadow_samples']} shadow samples / "
              f"{c['chunks_seen']} chunks, {c['drift_checks']} checks, "
              f"{c['drift_events']} drift events, "
              f"{c['calibration_swaps']} hot-swaps "
              f"({engine.swap_count} applied)")
        if monitor.last_report is not None:
            print("last drift report: " + monitor.last_report.summary_line())
        if monitor.last_observed is not None and frozen0 is not None:
            rows = drift_lib.site_snr_table(
                frozen0, engine._calib, monitor.last_observed,
                bx=engine.substrate.imc.bx)
            print("per-site SNR_T (stale frozen vs post-swap vs "
                  "fresh-frozen reference):")
            print(drift_lib.format_snr_table(rows))
    if meter is not None:
        from repro.core.design import optimize
        from repro.launch.metering import format_report, serve_energy_report

        reports = []
        for snr_db in (float(s) for s in args.energy_snr_db.split(",")):
            for kind in ("qs", "qr", "cm"):
                pt = optimize(n=512, snr_t_target_db=snr_db, kinds=(kind,))
                if pt is None:
                    continue
                # bill through the substrate the design point implies: the
                # rollup reads its design from the substrate object itself
                reports.append(serve_energy_report(
                    meter, substrate=substrate_lib.substrate_for_design(pt),
                    generated_tokens=total_tokens, requests=len(finished)))
        print(f"serve-path energy (billed prefill tokens="
              f"{meter.prefill_billed_tokens} of which padding="
              f"{meter.prefill_pad_tokens}, decode tokens="
              f"{meter.decode_billed_tokens}):")
        print(format_report(reports))
        if meter.prefix_saved_billed_tokens:
            print(f"prefix-cache energy savings ("
                  f"{meter.prefix_saved_billed_tokens} billed prefill "
                  f"tokens avoided):")
            for r in reports:
                frac = r.saved_prefill_j / max(
                    r.total_j + r.saved_prefill_j, 1e-30)
                print(f"  {r.design.arch_kind:>4s} @ "
                      f"{r.design.snr_t_db:5.1f} dB: "
                      f"-{r.j_per_token_saved:.3e} J/token "
                      f"({100 * frac:.1f}% of the cold bill)")
    return finished


if __name__ == "__main__":
    main()
