"""bf16-halfword codec: the packed corpus value layout (one bf16 per sample).

The packed :class:`repro.data.store.CorpusStore` layout stores every f32
*value* component (ICWS sampled values, TS/PS sampled values, linear table
cells) as a bf16 plane of the same shape, sample for sample:

    packed[..., k] = bf16(x[..., k])

``bf16(x)`` here is *truncation* -- the top 16 bits of the f32 encoding
(sign, 8-bit exponent, 7 mantissa bits).  Truncation, not round-to-nearest,
is deliberate: it makes the decode exact (``unpack(pack(x)) ==
pack-domain(x)`` bit for bit) and the codec idempotent
(``pack(unpack(w)) == w`` for every halfword), which is what the
packed-path bitwise-identity contract is stated against.  Zero encodes to
the zero halfword, so zero-filled spare rows stay inert through the codec
with no sentinel machinery.

Integer components (31-bit ICWS fingerprints, TS/PS sample keys) are NOT
narrowed: they are exact-match state -- a single flipped bit changes
collision/join semantics -- and 31 bits do not compress below one i32 lane
without changing results.  The byte savings come entirely from the value
lanes (f32 -> bf16 halves the dominant component), which the estimate
kernels decode tile by tile in VMEM with a plain ``bf16 -> f32`` convert
(:func:`unpack_halfwords_f32`, exact).  A packed tile covers the same
samples in the same lane order as its unpacked twin, so packed and
unpacked launches share one block layout and one reduction order; the
packed values never expand in HBM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def pack_halfwords_f32(x: jnp.ndarray) -> jnp.ndarray:
    """``[...]`` f32 -> ``[...]`` bf16, each value truncated to its top 16
    bits (plain jnp, so it also runs on the device after a sketch launch)."""
    bits = jax.lax.bitcast_convert_type(jnp.asarray(x, jnp.float32),
                                        jnp.uint32) >> 16
    return jax.lax.bitcast_convert_type(bits.astype(jnp.uint16),
                                        jnp.bfloat16)


def unpack_halfwords_f32(w: jnp.ndarray) -> jnp.ndarray:
    """``[...]`` bf16 -> ``[...]`` f32, the exact codec inverse: each
    halfword expands to the f32 whose top 16 bits it holds (low 16 mantissa
    bits zero).  Used both host-side and as the in-kernel tile decode of
    the packed estimate kernels."""
    return jnp.asarray(w, jnp.bfloat16).astype(jnp.float32)
