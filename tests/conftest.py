"""Test config: tests run on the single real CPU device (the 512-device
dry-run is exercised only via subprocesses in test_distributed/test_dryrun)."""
import os

# make sure no leaked XLA_FLAGS turn tests multi-device
os.environ.pop("XLA_FLAGS", None)

import jax
import numpy as np
import pytest

# tests compile afresh and write nothing into the checkout: entry points under
# test (serve.main) point JAX's persistent compilation cache at <repo>/.jax_cache
jax.config.update("jax_enable_compilation_cache", False)

try:
    from hypothesis import settings as _hyp_settings
except ImportError:  # pragma: no cover - dev extra always carries hypothesis
    _hyp_settings = None

if _hyp_settings is not None:
    # pinned deterministic CI profile: derandomized example generation (no
    # fresh-entropy flakes across the python matrix) and a fixed disabled
    # deadline (shared CI boxes blow any wall-clock deadline spuriously).
    # CI selects it via HYPOTHESIS_PROFILE=ci; local runs keep the default
    # randomized search (better bug-finding) minus the deadline.
    _hyp_settings.register_profile(
        "ci", derandomize=True, deadline=None, max_examples=100,
        print_blob=True)
    _hyp_settings.register_profile("dev", deadline=None)
    _hyp_settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "dev"))


@pytest.fixture(scope="module", autouse=True)
def _bounded_xla_executable_accumulation():
    """Drop compiled-executable caches at every module boundary.  A full
    tier-1 run compiles hundreds of engine scans into ONE process; past a
    few hundred live executables the CPU XLA client has been observed to
    segfault inside backend_compile (deterministically, on the next scan
    compile).  Per-module clearing bounds the live set; tests never share
    compiled functions across modules, so this only costs recompiles."""
    jax.clear_caches()
    yield


@pytest.fixture
def rng():
    return np.random.default_rng(0)
