"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle.

Sweeps shapes (aligned and ragged vs the block sizes) and dtypes, asserting
allclose against ref.py, plus statistical checks that the device ICWS path
obeys the weighted-Jaccard collision law end to end.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.countsketch import countsketch_pallas
from repro.kernels.estimate import estimate_fields_pallas
from repro.kernels.icws_sketch import icws_sketch_pallas


def _sparse_batch(rng, B, N, density=0.6, dtype=jnp.float32):
    """Padded sparse batch: (w, keys, vals) with zero-padding."""
    vals = rng.normal(size=(B, N)).astype(np.float32)
    mask = rng.random((B, N)) < density
    vals = vals * mask
    norms = np.linalg.norm(vals, axis=1, keepdims=True)
    norms[norms == 0] = 1.0
    valsn = vals / norms
    w = valsn ** 2
    keys = rng.integers(0, 2**31 - 1, size=(B, N)).astype(np.int32)
    return (jnp.asarray(w, dtype), jnp.asarray(keys),
            jnp.asarray(valsn, dtype))


# ---------------------------------------------------------------------------
# ICWS sketch kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,N,m", [(1, 256, 128), (3, 300, 64), (2, 1000, 200),
                                   (4, 64, 128), (2, 513, 257)])
@pytest.mark.slow
def test_icws_kernel_matches_ref(B, N, m):
    rng = np.random.default_rng(B * 1000 + N + m)
    w, keys, vals = _sparse_batch(rng, B, N)
    fp_k, val_k, amin_k, key_k = icws_sketch_pallas(w, keys, vals, m=m, seed=7,
                                                    interpret=True)
    fp_r, val_r, amin_r, key_r = ref.icws_sketch_ref(w, keys, vals, m=m, seed=7)
    assert np.array_equal(np.asarray(fp_k), np.asarray(fp_r))
    assert np.array_equal(np.asarray(key_k), np.asarray(key_r))
    np.testing.assert_allclose(np.asarray(val_k), np.asarray(val_r), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(amin_k), np.asarray(amin_r), rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_icws_kernel_dtypes(dtype):
    rng = np.random.default_rng(0)
    w, keys, vals = _sparse_batch(rng, 2, 256, dtype=dtype)
    fp_k, val_k, _, _ = icws_sketch_pallas(w, keys, vals, m=64, seed=1,
                                           interpret=True)
    fp_r, val_r, _, _ = ref.icws_sketch_ref(w.astype(jnp.float32), keys,
                                            vals.astype(jnp.float32),
                                            m=64, seed=1)
    # bf16 inputs are upcast inside; fingerprints must agree except where the
    # bf16 rounding moved an argmin (rare) -- demand 95% agreement for bf16.
    agree = np.mean(np.asarray(fp_k) == np.asarray(fp_r))
    assert agree > (0.999 if dtype == jnp.float32 else 0.95)


def test_icws_kernel_empty_rows():
    w = jnp.zeros((2, 128))
    keys = jnp.zeros((2, 128), jnp.int32)
    vals = jnp.zeros((2, 128))
    fp, val, amin, key = icws_sketch_pallas(w, keys, vals, m=32, seed=0,
                                            interpret=True)
    assert np.all(np.asarray(fp) == -1)
    assert np.all(np.asarray(val) == 0.0)
    assert np.all(np.asarray(key) == 0)


@pytest.mark.slow
def test_icws_kernel_block_size_invariance():
    """Different tilings must give identical results (tie semantics included)."""
    rng = np.random.default_rng(42)
    w, keys, vals = _sparse_batch(rng, 2, 512)
    outs = []
    for bm, bn in [(64, 128), (128, 256), (128, 512)]:
        outs.append(icws_sketch_pallas(w, keys, vals, m=128, seed=3,
                                       bm=bm, bn=bn, interpret=True))
    for o in outs[1:]:
        assert np.array_equal(np.asarray(o[0]), np.asarray(outs[0][0]))
        assert np.array_equal(np.asarray(o[3]), np.asarray(outs[0][3]))
        np.testing.assert_allclose(np.asarray(o[1]), np.asarray(outs[0][1]),
                                   rtol=1e-6)


@pytest.mark.slow
def test_icws_device_collision_law():
    """End-to-end: device sketches obey the weighted-Jaccard collision law."""
    rng = np.random.default_rng(5)
    n = 256
    a = rng.normal(size=n) * (rng.random(n) < 0.5)
    b = rng.normal(size=n) * (rng.random(n) < 0.5)
    keys = np.arange(n, dtype=np.int32)

    def prep(x):
        nz = x != 0
        xn = x / np.linalg.norm(x)
        w = np.where(nz, xn ** 2, 0.0)
        return (jnp.asarray(w[None, :], jnp.float32), jnp.asarray(keys[None, :]),
                jnp.asarray(np.where(nz, xn, 0.0)[None, :], jnp.float32))

    m = 4096
    fpa, _, _, _ = icws_sketch_pallas(*prep(a), m=m, seed=11, interpret=True)
    fpb, _, _, _ = icws_sketch_pallas(*prep(b), m=m, seed=11, interpret=True)
    rate = np.mean(np.asarray(fpa) == np.asarray(fpb))
    wa = (a / np.linalg.norm(a)) ** 2
    wb = (b / np.linalg.norm(b)) ** 2
    jbar = np.minimum(wa, wb).sum() / np.maximum(wa, wb).sum()
    assert abs(rate - jbar) < 4.0 / np.sqrt(m) + 0.01


# ---------------------------------------------------------------------------
# CountSketch kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("T,width,reps", [(1024, 128, 5), (1000, 100, 3),
                                          (4096, 512, 5), (64, 256, 2),
                                          (2048, 130, 5)])
def test_countsketch_kernel_matches_ref(T, width, reps):
    rng = np.random.default_rng(T + width)
    x = jnp.asarray(rng.normal(size=T), jnp.float32)
    tab_k = countsketch_pallas(x, width=width, reps=reps, seed=9, interpret=True)
    tab_r = ref.countsketch_ref(x, width=width, reps=reps, seed=9)
    np.testing.assert_allclose(np.asarray(tab_k), np.asarray(tab_r),
                               rtol=1e-5, atol=1e-5)


def test_countsketch_offset_consistency():
    """Sketching a long vector in two chunks with offsets == one shot."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=2048), jnp.float32)
    full = countsketch_pallas(x, width=256, seed=4, interpret=True)
    lo = countsketch_pallas(x[:1024], width=256, seed=4, offset=0, interpret=True)
    hi = countsketch_pallas(x[1024:], width=256, seed=4, offset=1024,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(lo + hi), np.asarray(full),
                               rtol=1e-5, atol=1e-5)


def test_countsketch_linearity_and_decode():
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.normal(size=1024), jnp.float32)
    b = jnp.asarray(rng.normal(size=1024), jnp.float32)
    sa = countsketch_pallas(a, width=512, seed=3, interpret=True)
    sb = countsketch_pallas(b, width=512, seed=3, interpret=True)
    sab = countsketch_pallas(a + b, width=512, seed=3, interpret=True)
    np.testing.assert_allclose(np.asarray(sa + sb), np.asarray(sab),
                               rtol=1e-4, atol=1e-4)
    dec = ops.countsketch_decode(sa, jnp.arange(1024), seed=3)
    err = np.mean((np.asarray(dec) - np.asarray(a)) ** 2)
    assert err < np.mean(np.asarray(a) ** 2)


# ---------------------------------------------------------------------------
# Estimator kernel (one field pair: sketch A_i against sketch B_j)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("P,m", [(8, 128), (5, 100), (16, 512), (1, 64),
                                 (9, 130)])
def test_estimate_kernel_matches_ref(P, m):
    """P sketches against P sketches; the pairs (A_i, B_i) are the diagonal."""
    rng = np.random.default_rng(P * 31 + m)
    fpa = rng.integers(0, 50, size=(1, P, m)).astype(np.int32)
    fpb = rng.integers(0, 50, size=(1, P, m)).astype(np.int32)
    va = rng.normal(size=(1, P, m)).astype(np.float32)
    vb = rng.normal(size=(1, P, m)).astype(np.float32)
    args = tuple(jnp.asarray(x) for x in (fpa, va, fpb, vb))
    cnt_k, sw_k = estimate_fields_pallas(*args, qmap=(0,), cmap=(0,),
                                         interpret=True)
    cnt_r, sw_r = ref.estimate_fields_ref(*args, qmap=(0,), cmap=(0,))
    assert cnt_k.shape == (1, P, P)
    np.testing.assert_allclose(np.asarray(cnt_k), np.asarray(cnt_r))
    # absolute bound on the scale of the terms: the kernel's importance
    # term rounds apart from the oracle's ratio (see test_corpus.py)
    sw_r = np.asarray(sw_r)
    scale = max(1.0, float(np.max(np.abs(sw_r))))
    np.testing.assert_allclose(np.asarray(sw_k), sw_r, rtol=1e-4,
                               atol=1e-6 * scale)


@pytest.mark.slow
def test_full_device_estimate_accuracy():
    """Device pipeline (sketch kernel + estimate kernel) estimates <a, b>."""
    rng = np.random.default_rng(8)
    n, m = 512, 2048
    a = rng.normal(size=n) * (rng.random(n) < 0.4)
    b = rng.normal(size=n) * (rng.random(n) < 0.4)
    keys = np.arange(n, dtype=np.int32)

    def prep(x):
        xn = x / np.linalg.norm(x)
        return (jnp.asarray(xn[None] ** 2, jnp.float32),
                jnp.asarray(keys[None]), jnp.asarray(xn[None], jnp.float32))

    fpa, va, _, _ = icws_sketch_pallas(*prep(a), m=m, seed=13, interpret=True)
    fpb, vb, _, _ = icws_sketch_pallas(*prep(b), m=m, seed=13, interpret=True)
    na = jnp.asarray([[np.linalg.norm(a)]], jnp.float32)
    nb = jnp.asarray([[np.linalg.norm(b)]], jnp.float32)
    est = float(ops.icws_estimate_fields(fpa[None], va[None], na,
                                         fpb[None], vb[None], nb,
                                         qmap=(0,), cmap=(0,))[0, 0, 0])
    true = float(np.dot(a, b))
    bound = 4.0 / np.sqrt(m) * np.linalg.norm(a) * np.linalg.norm(b)
    assert abs(est - true) < bound
