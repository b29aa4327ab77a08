"""Fault-tolerant execution loop: step retry, straggler deadline, checkpoint
restart, preemption-safe save, and elastic re-mesh.

On a real multi-pod deployment the failure modes are: host crash (process
exits -> restart from checkpoint), device error (JaxRuntimeError -> retry the
step, then restart), straggler (step exceeds deadline -> raise, coordinator
reschedules), and preemption (SIGTERM -> synchronous final save).  On CPU we
exercise the same code paths with injected failures (tests/test_fault.py).

The loop is deliberately framework-level (pure-Python around a jit'd step):
that is what survives 1000-node reality - in-graph error handling does not.
"""
from __future__ import annotations

import dataclasses
import logging
import signal
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from repro.checkpoint import manager as ckpt

log = logging.getLogger("repro.fault")

#: exception types a retry is worth attempting for: device-side runtime
#: errors (OOM blips, transient fabric faults), not Python-level bugs
TRANSIENT_ERROR_TYPES = (jax.errors.JaxRuntimeError,)


def is_transient_device_error(e: BaseException) -> bool:
    """True for device-runtime errors worth a retry (``JaxRuntimeError`` and
    subclasses).  Python-level exceptions - shape errors, assertion failures,
    programming bugs - are NOT transient: retrying them only hides the bug."""
    return isinstance(e, TRANSIENT_ERROR_TYPES)


def call_with_retries(fn: Callable[[], Any], max_retries: int, *,
                      retryable: Optional[Callable[[BaseException], bool]] = None,
                      describe: str = "step",
                      logger: Optional[logging.Logger] = None):
    """THE retry idiom: run ``fn()``, re-running it up to ``max_retries``
    times when it raises an exception ``retryable`` accepts (default: any
    ``Exception``); the final failure propagates to the caller.

    Shared by the training loop (:class:`TrainLoopRunner`, which retries
    everything except :class:`StepTimeout`) and the serve engine
    (``launch.serve.Engine``, which retries only
    :func:`is_transient_device_error` and then fails just the affected
    requests) - one code path, so the two cannot drift apart.
    """
    lg = logger or log
    for attempt in range(max_retries + 1):
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 - device errors are dynamic
            if (retryable is not None and not retryable(e)) \
                    or attempt >= max_retries:
                raise
            lg.warning("%s attempt %d failed: %r; retrying", describe,
                       attempt, e)
    raise AssertionError("unreachable")


@dataclasses.dataclass
class FaultConfig:
    ckpt_dir: str = "/tmp/repro_ckpt"
    save_every: int = 50
    keep: int = 3
    max_step_retries: int = 2
    step_deadline_s: Optional[float] = None  # straggler mitigation
    max_restarts: int = 3
    async_save: bool = True


class StepTimeout(RuntimeError):
    pass


class TrainLoopRunner:
    """Runs `step_fn(state, batch) -> (state, metrics)` fault-tolerantly."""

    def __init__(
        self,
        step_fn: Callable,
        init_state_fn: Callable[[], Any],
        batch_fn: Callable[[int], Dict],
        cfg: FaultConfig,
        failure_injector: Optional[Callable[[int], None]] = None,
    ):
        self.step_fn = step_fn
        self.init_state_fn = init_state_fn
        self.batch_fn = batch_fn
        self.cfg = cfg
        self.failure_injector = failure_injector
        self.saver = ckpt.AsyncSaver()
        self._preempted = False

    # -- preemption handling -------------------------------------------------
    def install_preemption_handler(self):
        def _handler(signum, frame):
            log.warning("preemption signal received; will save and exit")
            self._preempted = True

        signal.signal(signal.SIGTERM, _handler)

    # -- state restore --------------------------------------------------------
    def _restore_or_init(self) -> Tuple[Any, int]:
        state = self.init_state_fn()
        last = ckpt.latest_step(self.cfg.ckpt_dir)
        if last is None:
            return state, 0
        shapes = jax.tree_util.tree_map(lambda x: x, state)
        restored, extra = ckpt.restore(self.cfg.ckpt_dir, last, shapes)
        log.info("restored checkpoint at step %d", last)
        return restored, int(extra.get("next_step", last))

    # -- one guarded step ------------------------------------------------------
    def _guarded_step(self, state, batch, step: int):
        deadline = self.cfg.step_deadline_s

        def attempt():
            t0 = time.monotonic()
            if self.failure_injector is not None:
                self.failure_injector(step)
            new_state, metrics = self.step_fn(state, batch)
            # block so stragglers/timeouts are observable
            jax.block_until_ready(
                jax.tree_util.tree_leaves(metrics)[0]
                if jax.tree_util.tree_leaves(metrics)
                else jax.tree_util.tree_leaves(new_state)[0]
            )
            dt = time.monotonic() - t0
            if deadline is not None and dt > deadline:
                raise StepTimeout(
                    f"step {step} took {dt:.1f}s > deadline {deadline}s"
                )
            return new_state, metrics

        # stragglers (StepTimeout) escalate to restart/reschedule, anything
        # else is retried in place - the shared serve/train retry idiom
        return call_with_retries(
            attempt, self.cfg.max_step_retries,
            retryable=lambda e: not isinstance(e, StepTimeout),
            describe=f"step {step}",
        )

    # -- the loop ---------------------------------------------------------------
    def run(self, total_steps: int) -> Tuple[Any, Dict]:
        restarts = 0
        history: Dict[str, list] = {"loss": [], "restarts": 0, "retried": 0}
        while True:
            try:
                state, step = self._restore_or_init()
                while step < total_steps and not self._preempted:
                    batch = self.batch_fn(step)
                    state, metrics = self._guarded_step(state, batch, step)
                    if "loss" in metrics:
                        history["loss"].append(float(metrics["loss"]))
                    step += 1
                    if step % self.cfg.save_every == 0 or step == total_steps:
                        extra = {"next_step": step}
                        # serialize wait -> cleanup -> save: cleanup removes
                        # stray .tmp dirs and must never run while an async
                        # save is mid-write (it would delete the in-flight
                        # .tmp; caught by test_train_driver_resume as a lost
                        # checkpoint)
                        self.saver.wait()
                        ckpt.cleanup(self.cfg.ckpt_dir, self.cfg.keep)
                        if self.cfg.async_save:
                            self.saver.save(self.cfg.ckpt_dir, step, state, extra)
                        else:
                            ckpt.save(self.cfg.ckpt_dir, step, state, extra)
                if self._preempted:
                    self.saver.wait()
                    ckpt.save(self.cfg.ckpt_dir, step, state, {"next_step": step})
                self.saver.wait()
                history["restarts"] = restarts
                return state, history
            except Exception as e:  # noqa: BLE001
                restarts += 1
                log.warning("run failed (%r); restart %d", e, restarts)
                self.saver.wait()
                if restarts > self.cfg.max_restarts:
                    raise


def elastic_remesh(model_axis: Optional[int] = None):
    """Rebuild a mesh from the devices that are currently alive.

    After losing hosts, callers rebuild the step functions against this mesh;
    checkpoint restore is sharding-agnostic (repro.checkpoint) and the data
    pipeline is counter-based (repro.data), so training resumes bit-identically
    modulo batch layout.
    """
    from repro.launch.mesh import make_host_mesh

    return make_host_mesh(model_axis)
