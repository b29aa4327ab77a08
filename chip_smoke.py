"""Chip smoke: serve musicgen-medium at its published width on a TPU.

    python chip_smoke.py               # one chip: phases digital, imc_analytic
    python chip_smoke.py --four-chips  # only: a 1x4 tensor-parallel engine
                                       # against one device, digital

Each phase drives ``repro.launch.serve.main`` - the command-line entry point -
with random weights from a fixed seed, checks that every request finished
without an error and produced its tokens, and prints one JSON line.  The last
line of the output is ``{"ok": true, "device": {...}}``.  The script refuses
any device but a TPU: it exits non-zero, serving nothing, when JAX finds none.
Everything runs in this one process, which holds the chip(s).

The phase functions take a preset, so a test can run them at the smoke size
on the CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from typing import List, Optional, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "musicgen-medium"

# first-step logits of the 1x4 engine against one device: the tensor-parallel
# all-reduce re-associates the bf16 row sums of every sharded matmul, so the
# two programs round differently in each of the 48 layers.  bf16 keeps 8
# mantissa bits (a rounding step of 2**-8 relative); the bound allows a few
# such steps of the largest logit, and a wrong shard layout or a dropped
# partial sum misses it by orders of magnitude.
LOGITS_RTOL = 0.03


@dataclasses.dataclass(frozen=True)
class Preset:
    smoke: bool
    batch: int
    requests: int
    prompt_lens: Tuple[int, ...]
    gen: int


# the published width: 8 slots, 16 requests over four prefill buckets
# (16, 64, 256, 512); 32 tokens each, so full 8-step decode chunks run
FULL = Preset(smoke=False, batch=8, requests=16,
              prompt_lens=(16, 64, 200, 500), gen=32)
# the 2-layer d=64 preset, for rehearsals and tests on the CPU
SMOKE = Preset(smoke=True, batch=4, requests=6, prompt_lens=(4, 12, 40, 5),
               gen=10)

SUBSTRATE_FLAGS = {
    "digital": [],
    "imc_analytic": ["--imc-mode", "imc_analytic", "--imc-policy", "frozen"],
}


class CompileClock:
    """Sums the backend-compile seconds JAX reports while it is entered
    (a persistent-cache hit reports its retrieval time instead)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0

    def _listen(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.seconds += duration

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listen)


def serve_argv(preset: Preset, substrate: str,
               mesh: Optional[str] = None) -> List[str]:
    argv = ["--arch", ARCH, "--batch", str(preset.batch),
            "--requests", str(preset.requests),
            "--prompt-lens", ",".join(str(n) for n in preset.prompt_lens),
            "--gen", str(preset.gen)]
    if preset.smoke:
        argv.append("--smoke")
    if mesh is not None:
        argv += ["--mesh", mesh]
    return argv + SUBSTRATE_FLAGS[substrate]


def run_serve(preset: Preset, substrate: str, mesh: Optional[str] = None):
    """One ``serve.main`` run; returns (engine, finished, timings).

    ``setup_s`` runs from the call to the built engine (weights,
    calibration); ``serve_s`` from the built engine to ``main`` returning,
    less the compile seconds inside it; ``compile_s`` counts both.  ``main``
    returns only after every token block has been copied to the host, so the
    device work is finished by then.
    """
    from repro.launch import serve

    built = {}
    with CompileClock() as clock:

        def on_engine(engine):
            built.update(engine=engine, t=time.perf_counter(),
                         compile_s=clock.seconds)

        t0 = time.perf_counter()
        finished = serve.main(serve_argv(preset, substrate, mesh),
                              on_engine=on_engine)
        t1 = time.perf_counter()
    timings = {
        "setup_s": built["t"] - t0,
        "compile_s": clock.seconds,
        "serve_s": t1 - built["t"] - (clock.seconds - built["compile_s"]),
    }
    return built["engine"], finished, timings


def check_finished(finished, preset: Preset) -> None:
    if len(finished) != preset.requests:
        raise AssertionError(
            f"{len(finished)} of {preset.requests} requests finished")
    for r in finished:
        if not r.ok:
            raise AssertionError(f"request {r.rid} failed: {r.error}")
        if len(r.out) != preset.gen:
            raise AssertionError(
                f"request {r.rid} produced {len(r.out)} of {preset.gen} "
                "tokens")


def peak_bytes_in_use() -> Optional[int]:
    import jax

    stats = jax.devices()[0].memory_stats()  # None on the CPU
    return None if stats is None else stats.get("peak_bytes_in_use")


def serve_phase(substrate: str, preset: Preset = FULL) -> dict:
    """Serve ``preset`` on one device through ``serve.main``; returns the
    phase's report line.  On a TPU the fused decode chunk must hold the
    Pallas paged-attention kernel (a ``tpu_custom_call``)."""
    import jax

    engine, finished, timings = run_serve(preset, substrate)
    check_finished(finished, preset)
    has_kernel = "tpu_custom_call" in engine.compiled_decode_text(
        engine.max_chunk)
    if engine.cfg.decode_attn == "gather":
        path = "gather"
    elif has_kernel:
        path = "pallas_kernel"
    else:
        path = "jax_block_walk"
    if (jax.devices()[0].platform == "tpu"
            and engine.cfg.decode_attn == "kernel" and not has_kernel):
        raise AssertionError(
            "decode_attn='kernel' on a TPU, but the compiled decode chunk "
            "holds no tpu_custom_call")
    return {
        "phase": substrate,
        "requests": len(finished),
        "tokens": sum(len(r.out) for r in finished),
        **timings,
        "peak_bytes_in_use": peak_bytes_in_use(),
        "decode_attn": path,
        "decode_chunk_has_tpu_custom_call": has_kernel,
    }


def four_chip_phase(preset: Preset = FULL) -> dict:
    """The same digital requests on a 1x4 tensor-parallel mesh and on one
    device: greedy tokens are compared, and every request's first-step
    logits must agree within ``LOGITS_RTOL`` of the largest logit."""
    engine, finished, timings = run_serve(preset, "digital", mesh="1x4")
    check_finished(finished, preset)
    path = engine.cfg.decode_attn  # the sharded engine serves through gather
    tp_tokens = {r.rid: list(r.out) for r in finished}
    tp_logits = {r.rid: engine.prompt_logits(r.prompt) for r in finished}
    del engine, finished
    gc.collect()

    engine, finished, timings_1 = run_serve(preset, "digital")
    check_finished(finished, preset)
    parted, worst = [], 0.0
    for r in finished:
        ref = engine.prompt_logits(r.prompt)
        err = float(abs(tp_logits[r.rid] - ref).max() / abs(ref).max())
        worst = max(worst, err)
        if list(r.out) != tp_tokens[r.rid]:
            parted.append(r.rid)
    if worst > LOGITS_RTOL:
        raise AssertionError(
            f"first-step logits of the 1x4 mesh differ from one device by "
            f"{worst} of the largest logit (bound {LOGITS_RTOL})")
    return {
        "phase": "four_chips",
        "mesh": "1x4",
        "requests": len(finished),
        "tokens": sum(len(r.out) for r in finished),
        "token_match": not parted,
        "requests_parted": parted,
        "first_step_logits_max_rel_err": worst,
        "logits_rtol": LOGITS_RTOL,
        "decode_attn_sharded": path,
        "compile_s_sharded": timings["compile_s"],
        "serve_s_sharded": timings["serve_s"],
        "compile_s_single": timings_1["compile_s"],
        "serve_s_single": timings_1["serve_s"],
        "peak_bytes_in_use": peak_bytes_in_use(),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 1x4 tensor-parallel phase and the "
                         "single-device run it is compared with")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 1
    from repro.runtime.compile_cache import use_compile_cache

    print(f"chip_smoke: compile cache {use_compile_cache()}", file=sys.stderr)
    if args.four_chips:
        if len(devices) < 4:
            print(f"chip_smoke: --four-chips needs 4 devices, found "
                  f"{len(devices)}", file=sys.stderr)
            return 1
        phases = [four_chip_phase]
    else:
        phases = [lambda: serve_phase("digital"),
                  lambda: serve_phase("imc_analytic")]
    for phase in phases:
        print("chip_smoke phase " + json.dumps(phase()), flush=True)
        gc.collect()  # the phase's engine sits in reference cycles
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
