"""Counter-based PRNG shared by the Pallas kernels and the jnp oracles.

The bit-serial and analytic IMC kernels generate their analog-noise draws
*inside* the kernel instead of streaming a pre-drawn HBM noise tensor.  The
draw for a given noise site is a pure function of ``(seed, counter fields)``:

  bit-serial  z[bank, plane, b, m] = N(seed; TAG_BITSERIAL, bank, plane, b, m)
  analytic    z[b, m]              = N(seed; TAG_ANALYTIC, b, m)

where the counter fields are *global* indices (not tile-local ones), so the
same value is produced regardless of how the kernel tiles B/M/K.  That makes
the fallback path reproducible by the pure-jnp oracles in ``ref.py``:
interpret-mode kernel output with a given seed matches the oracle output
with the same seed draw-for-draw (up to last-ulp FMA-contraction differences
between the two XLA graphs - the integer hash itself is exact).

On a real TPU the kernels instead use the hardware PRNG
(``pltpu.prng_seed`` / ``pltpu.prng_random_bits``) seeded per grid step -
faster, but only *statistically* equivalent to the oracle (same N(0,1)
marginals, different bits).  Tests therefore assert bit-exactness in
interpret mode and statistical (SNR-level) equivalence otherwise.

The hash is a splitmix32-style finalizer chained over the counter fields.
All arithmetic is uint32 with wraparound, which lowers to plain VPU integer
ops inside Pallas and to XLA integer ops in the oracles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# domain-separation tags (first counter field) so the two kernels never share
# a counter stream even under the same seed
TAG_BITSERIAL = 0x51
TAG_ANALYTIC = 0xA7

_GOLDEN = 0x9E3779B9  # 2^32 / phi; Weyl increment for field absorption


def _mix32(h):
    """splitmix32 finalizer: full avalanche on a uint32."""
    h = (h ^ (h >> 16)) * jnp.uint32(0x7FEB352D)
    h = (h ^ (h >> 15)) * jnp.uint32(0x846CA68B)
    return h ^ (h >> 16)


def hash_u32(seed, *fields):
    """Hash ``seed`` and integer counter ``fields`` to uint32 noise bits.

    Fields may be scalars or broadcastable integer arrays; the result has the
    broadcast shape.  Every field is absorbed with a Weyl-sequence offset and
    re-avalanched, so low-entropy counters (small ints, iotas) still produce
    independent-looking streams.
    """
    h = _mix32(jnp.asarray(seed).astype(jnp.uint32) ^ jnp.uint32(_GOLDEN))
    for f in fields:
        f = jnp.asarray(f).astype(jnp.uint32)
        h = _mix32(h ^ (f * jnp.uint32(_GOLDEN) + jnp.uint32(0x85EBCA6B)))
    return h


def uniform_from_bits(bits, open_zero: bool = False):
    """uint32 bits -> f32 uniform using the top 24 bits.

    ``open_zero=True`` maps to (0, 1] (safe under log); otherwise [0, 1).
    """
    # the 24-bit value is a non-negative int32: convert through int32, since
    # Mosaic has no uint32 -> f32 cast (the values are identical either way)
    u = jax.lax.bitcast_convert_type(bits >> jnp.uint32(8), jnp.int32)
    u = u.astype(jnp.float32)
    if open_zero:
        u = u + 1.0
    return u * jnp.float32(2.0**-24)


def normal_from_bits(bits_a, bits_b):
    """Two independent uint32 bit arrays -> standard-normal f32 (Box-Muller)."""
    u1 = uniform_from_bits(bits_a, open_zero=True)
    u2 = uniform_from_bits(bits_b)
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    return r * jnp.cos(jnp.float32(2.0 * 3.141592653589793) * u2)


def counter_normal(seed, *fields):
    """Standard-normal draw at the given counter site(s).

    Deterministic in ``(seed, fields)`` and tile-layout independent; this is
    the fallback noise generator used by the interpret/CPU kernel path and by
    the ``ref.py`` oracles (which makes the two bit-exact against each other).
    """
    return normal_from_bits(
        hash_u32(seed, *fields, 1), hash_u32(seed, *fields, 2)
    )
