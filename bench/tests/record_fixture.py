"""Record the scoped-trace fixture of ``test_scopes.py`` on a TPU.

    python3 bench/tests/record_fixture.py

A two-slot engine at the ``musicgen-medium`` smoke size, on the
``imc_analytic`` substrate and the Pallas paged kernel, runs three 4-step
decode calls inside the benchmark's spans (``bench_window``, and per call
``decode_chunk`` then a 5 ms ``wait``), every program compiled beforehand.
Writes to ``bench/tests/data/``: the profile (``tpu_scoped_decode.xplane.pb``),
the optimized HLO of the 4-step decode program it ran
(``tpu_scoped_decode.hlo.txt``) and the calls' steps
(``tpu_scoped_decode.json``).  Refuses any device but a TPU.
"""
import glob
import json
import os
import re
import shutil
import sys
import tempfile
import time

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))

STEPS, CALLS = 4, 3


def main() -> int:
    import jax
    import numpy as np

    # the programs' source files are named relative to the checkout, so the
    # fixture does not depend on where it was recorded
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      re.escape(ROOT + os.sep))
    if jax.devices()[0].platform != "tpu":
        print("record_fixture: needs a TPU", file=sys.stderr)
        return 2
    from repro import configs
    from repro.core import substrate as substrate_lib
    from repro.core.imc_linear import IMCConfig
    from repro.launch.serve import Engine, Request
    from repro.models import init_params

    cfg = configs.get_smoke("musicgen-medium").replace(decode_attn="kernel")
    params = init_params(jax.random.PRNGKey(0), cfg)
    cfg = cfg.replace(imc=substrate_lib.as_substrate(
        IMCConfig(mode="imc_analytic", bx=7, bw=7)))
    eng = Engine(cfg, params, 2, 128, rng=None, max_chunk=STEPS,
                 block_size=8)
    eng.admit_pending([Request(rid=i, prompt=np.arange(5 + 3 * i) % 50,
                               max_new=96) for i in range(2)])
    for _ in range(6):  # compile the chunk and every block-table extend
        eng.decode_chunk(STEPS)
    out = tempfile.mkdtemp()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench_window"):
        for _ in range(CALLS):
            with jax.profiler.TraceAnnotation("decode_chunk"):
                eng.decode_chunk(STEPS)
            with jax.profiler.TraceAnnotation("wait"):
                time.sleep(0.005)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(DATA, exist_ok=True)
    shutil.copy(path[0], os.path.join(DATA, "tpu_scoped_decode.xplane.pb"))
    with open(os.path.join(DATA, "tpu_scoped_decode.hlo.txt"), "w") as f:
        f.write(eng.compiled_decode_text(STEPS))
    with open(os.path.join(DATA, "tpu_scoped_decode.json"), "w") as f:
        json.dump({"chunks": [{"steps": STEPS} for _ in range(CALLS)]}, f)
    shutil.rmtree(out, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
