"""Host-vs-kernel ICWS contract: one RNG, interoperable fingerprints.

The host sketcher (:class:`repro.core.ICWS`) and the Pallas kernel
(:mod:`repro.kernels.icws_sketch`) must draw the same variates and emit the
same fingerprints, or mixed (host-sketched vs device-sketched) corpora
silently estimate zero.  These tests pin:

  * the numpy u32 RNG twins against the jnp originals, bit for bit;
  * host ``ICWS.sketch`` against the device kernel on the same vectors
    (fingerprints agree except where libm/XLA transcendentals differ in the
    last ulp AND that flips a floor/argmin -- bounded well under 1%);
  * the estimator on mixed host/device sketch pairs against the pure host
    estimate, within f32 tolerance.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ICWS, SparseVec
from repro.core import u32
from repro.core.icws import _stack
from repro.kernels import common as kcommon
from repro.kernels import ops


# ---------------------------------------------------------------------------
# numpy twins of the in-kernel u32 RNG: bit-exact
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed,stream", [(0, 1), (7, 5), (12345, 9),
                                         (2**31 - 1, 2)])
def test_u32_twins_bit_exact(seed, stream):
    rng = np.random.default_rng(seed + stream)
    keys = rng.integers(0, 2**32, size=257, dtype=np.uint64).astype(np.uint32)
    t = np.arange(64, dtype=np.int64)

    salt_np = u32.salt_for(seed, stream, t)
    salt_j = np.asarray(kcommon.salt_for(seed, stream, jnp.asarray(t)))
    assert np.array_equal(salt_np, salt_j.astype(np.uint32))

    h_np = u32.hash_u32(keys[None, :], salt_np[:, None])
    h_j = np.asarray(kcommon.hash_u32(jnp.asarray(keys)[None, :],
                                      jnp.asarray(salt_np)[:, None]))
    assert np.array_equal(h_np, h_j.astype(np.uint32))

    u_np = u32.uniform01(keys[None, :], salt_np[:, None])
    u_j = np.asarray(kcommon.uniform01(jnp.asarray(keys)[None, :],
                                       jnp.asarray(salt_np)[:, None]))
    assert np.array_equal(u_np, u_j)
    assert u_np.dtype == np.float32
    assert (u_np > 0).all() and (u_np < 1).all()

    m_np = u32.mix32(keys)
    m_j = np.asarray(kcommon.mix32(jnp.asarray(keys)))
    assert np.array_equal(m_np, m_j.astype(np.uint32))


# ---------------------------------------------------------------------------
# host sketch vs device kernel on identical vectors
# ---------------------------------------------------------------------------
def _host_and_device_sketch(rng, n, density, m, seed):
    x = rng.normal(size=n) * (rng.random(n) < density)
    if not x.any():
        x[0] = 1.0
    v = SparseVec.from_dense(x)
    host = ICWS(m=m, seed=seed).sketch(v)

    z32 = (v.values / v.norm()).astype(np.float32)
    w = jnp.asarray((z32 * z32)[None, :])
    keys = jnp.asarray(v.indices.astype(np.int32)[None, :])
    vals = jnp.asarray(z32[None, :])
    fp, val, _, _ = ops.icws_sketch(w, keys, vals, m=m, seed=seed)
    return v, host, (np.asarray(fp)[0], np.asarray(val)[0], v.norm())


@pytest.mark.parametrize("n,density,m,seed", [(64, 1.0, 128, 0),
                                              (300, 0.5, 256, 7),
                                              (1000, 0.2, 512, 3),
                                              (50, 0.9, 64, 11)])
def test_host_device_fingerprints_compatible(n, density, m, seed):
    rng = np.random.default_rng(n + m + seed)
    _, host, (fp_dev, val_dev, _) = _host_and_device_sketch(
        rng, n, density, m, seed)
    agree = np.mean(host.fingerprints == fp_dev)
    assert agree > 0.99, f"fingerprint agreement {agree:.4f}"
    # values at agreeing samples match to f32 rounding
    same = host.fingerprints == fp_dev
    np.testing.assert_allclose(host.values[same], val_dev[same],
                               rtol=1e-5, atol=1e-6)
    assert host.fingerprints.dtype == np.int32
    assert (host.fingerprints >= -1).all()          # 31-bit fp or empty


class ICWSSketchLike:
    """Adapter: raw device arrays quacking like an ICWSSketch for stacking."""

    def __init__(self, fp, val, norm):
        self.fingerprints = np.asarray(fp)
        self.values = np.asarray(val, np.float64)
        self.norm = float(norm)


@pytest.mark.parametrize("seed", [0, 5])
def test_mixed_host_device_estimate_matches_host(seed):
    """Device estimates of (host-sketched A, device-sketched B) pairs must
    agree with the all-host estimator: one sketch per path, same contract."""
    rng = np.random.default_rng(40 + seed)
    n, m = 400, 1024
    pairs = []
    for _ in range(3):
        _, host_a, _ = _host_and_device_sketch(rng, n, 0.5, m, seed)
        _, host_b, dev_b = _host_and_device_sketch(rng, n, 0.5, m, seed)
        pairs.append((host_a, dev_b, host_b))

    icws = ICWS(m=m, seed=seed)
    A = _stack([p[0] for p in pairs])
    B_host = _stack([p[2] for p in pairs])
    host_host = icws.estimate_batch(A, B_host)

    # mixed: host-sketched A vs device-sketched B via the host estimator
    B_dev = _stack([ICWSSketchLike(*p[1]) for p in pairs])
    mixed = icws.estimate_batch(A, B_dev)
    scale = np.maximum(np.abs(host_host), 1.0)
    np.testing.assert_allclose(mixed / scale, host_host / scale, atol=0.05)

    # and via the device estimate kernel on the same mixed arrays: A's
    # sketches as the queries, B's as the corpus, pairs on the diagonal
    est = ops.icws_estimate_fields(
        jnp.asarray(A.fingerprints, jnp.int32)[None],
        jnp.asarray(A.values, jnp.float32)[None],
        jnp.asarray(A.norm, jnp.float32)[None],
        jnp.asarray(B_dev.fingerprints, jnp.int32)[None],
        jnp.asarray(B_dev.values, jnp.float32)[None],
        jnp.asarray(B_dev.norm, jnp.float32)[None], qmap=(0,), cmap=(0,))
    dev = np.diagonal(np.asarray(est[0]))
    np.testing.assert_allclose(dev / scale, mixed / scale, atol=1e-4)


def test_host_empty_sketch_matches_kernel_sentinels():
    icws = ICWS(m=32, seed=0)
    s = icws.sketch(SparseVec.from_dense(np.zeros(8)))
    assert (s.fingerprints == -1).all()
    assert s.fingerprints.dtype == np.int32
    assert (s.values == 0).all() and s.norm == 0.0
    fp, val, _, _ = ops.icws_sketch(jnp.zeros((1, 128)),
                                    jnp.zeros((1, 128), jnp.int32),
                                    jnp.zeros((1, 128)), m=32, seed=0)
    assert (np.asarray(fp)[0] == s.fingerprints).all()
