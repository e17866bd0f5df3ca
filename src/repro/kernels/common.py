"""Shared in-kernel utilities: 32-bit mixing RNG and uniform generation.

TPU vector units have native uint32 arithmetic (full-width low product), so
all in-kernel pseudo-randomness is built from murmur3-style finalizers over
``uint32`` lanes -- no 64-bit emulation, no host round-trips.  These run both
inside Pallas kernel bodies and in plain jnp (the ref oracles use the same
functions so kernel-vs-ref comparisons are bit-exact).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_M1 = 0x85EBCA6B
_M2 = 0xC2B2AE35
_GOLDEN = 0x9E3779B9

# ---------------------------------------------------------------------------
# The u32 salt-stream registry: every independent hash draw any kernel makes
# gets a named ``*_STREAM`` constant HERE (device side) with an identically
# named, identically valued host twin in ``repro.core`` (u32.py for ICWS,
# linear.py for CS/JL, sampling.py for TS/PS -- those packages stay
# numpy-only and never import this module).  Stream IDs must be globally
# unique: two draws sharing an ID share their randomness, which silently
# correlates sketches that the estimators assume independent.  Uniqueness,
# host/device mirroring, and literal-free call sites are machine-checked by
# ``python -m repro.analysis`` (rules SR001-SR006); the generated STREAMS.md
# at the repo root is the human-readable registry table.
# ---------------------------------------------------------------------------

# ICWS (weighted MinHash): per-(sample, key) variates r ~ Gamma(2,1) from
# two uniforms, c ~ Gamma(2,1) from two more, beta ~ U(0,1), plus the
# (key, level) fingerprint salt.
ICWS_R1_STREAM = 1
ICWS_R2_STREAM = 2
ICWS_C1_STREAM = 3
ICWS_C2_STREAM = 4
ICWS_BETA_STREAM = 5
ICWS_FP_STREAM = 9
# linear-sketch kernels: CountSketch buckets/signs (shared between the dense
# gradient-compression kernel and the sparse corpus-ingest kernel so
# position- and key-sketched vectors interoperate) and JL signs.
CS_BUCKET_STREAM = 21
CS_SIGN_STREAM = 22
JL_SIGN_STREAM = 31
# coordinated sample hash h(key) of the TS/PS sampling sketches (one draw
# per key, shared across vectors -- repro.core.sampling mirrors this)
SAMPLE_HASH_STREAM = 41
# DMH (densified one-permutation weighted MinHash, arXiv:1602.08393 /
# 1703.04664): one bin draw per key, ICWS-style variates drawn at
# sample-index t = bin (so within-bin ranks follow the exact weighted
# MinHash law), a (key, level)-salted fingerprint per bin, and the
# 2-universal reseeded probe stream of optimal densification (one draw per
# (empty bin, attempt) pair -- repro.core.dmh mirrors all of these).
DMH_BIN_STREAM = 51
DMH_R1_STREAM = 52
DMH_R2_STREAM = 53
DMH_C1_STREAM = 54
DMH_C2_STREAM = 55
DMH_BETA_STREAM = 56
DMH_FP_STREAM = 57
DMH_DENSIFY_STREAM = 58


def densify_probes(m: int) -> int:
    """Probe budget of the DMH densification epilogue (lane-multiple).
    Mirrored bit for bit by ``repro.core.dmh.densify_probes`` -- the host
    oracle and the kernel must probe identically or borrowed fingerprints
    stop colliding across the host/device boundary."""
    return min(1024, 128 * -(-4 * int(m) // 128))


def streams() -> dict:
    """The enumerated stream registry: ``{name: id}`` for every ``*_STREAM``
    constant above (runtime view of what ``repro.analysis`` reads from the
    AST)."""
    return {k: v for k, v in sorted(globals().items())
            if k.endswith("_STREAM") and isinstance(v, int)}


def mix32(x: jnp.ndarray) -> jnp.ndarray:
    """Murmur3 fmix32: high-quality 32-bit mixer (bijective)."""
    z = x.astype(jnp.uint32)
    z = z ^ (z >> jnp.uint32(16))
    z = z * jnp.uint32(_M1)
    z = z ^ (z >> jnp.uint32(13))
    z = z * jnp.uint32(_M2)
    z = z ^ (z >> jnp.uint32(16))
    return z


def hash_u32(key: jnp.ndarray, salt: jnp.ndarray) -> jnp.ndarray:
    """Mix key with a salt (two rounds; inputs broadcast)."""
    k = key.astype(jnp.uint32)
    s = jnp.asarray(salt).astype(jnp.uint32)
    return mix32(mix32(k + s * jnp.uint32(_GOLDEN))
                 ^ (s * jnp.uint32(_M2) + jnp.uint32(0x27D4EB2F)))


def uniform01(key: jnp.ndarray, salt) -> jnp.ndarray:
    """Strictly-interior uniform (0,1) f32 from a 32-bit hash.

    Uses the top 24 bits => values in [2^-25, 1 - 2^-25]; logs are safe.
    """
    bits = hash_u32(key, salt) >> jnp.uint32(8)          # 24 random bits
    # through int32: exact for 24 bits, and the TPU compiler has no direct
    # uint32 -> float32 conversion
    return (bits.astype(jnp.int32).astype(jnp.float32) * jnp.float32(2 ** -24)
            + jnp.float32(2 ** -25))


def salt_for(seed: int, stream: int, t: jnp.ndarray) -> jnp.ndarray:
    """Combine (seed, stream, sample-index t) into a salt array."""
    base = jnp.uint32(seed & 0xFFFFFFFF) * jnp.uint32(0x9E3779B1) \
        + jnp.uint32(stream) * jnp.uint32(0x517CC1B7)
    return base + t.astype(jnp.uint32) * jnp.uint32(0x2545F491)
