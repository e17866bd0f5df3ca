"""AOT compiles of every served kernel for a described TPU v5e.

Interpret mode runs a Pallas kernel body as plain jnp, so it cannot show
what the chip's compiler refuses: blocks that break the (8, 128) tiling
rule, casts Mosaic has no lowering for, or more VMEM than a kernel may
scope.  These tests compile the launches the serving path makes on a TPU
-- the public ``repro.kernels.ops`` wrappers, with the blocks they pick
there -- for one chip of a described ``v5e:2x2`` topology, at the widths
``chip_smoke.py`` serves (ICWS m=256, a 16-query micro-batch, a 2^18-row
corpus; the other families storage-matched to that m).  Nothing runs: a
pass means the chip's compiler accepted the program, not that it is fast
or right.

The topology is described inside a module fixture and never at import,
so every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.data.dataset_search import (CANDIDATE_PLANES, CANDIDATES, CFIELD,
                                       QFIELD)
from repro.data.families import make_family, wmh_storage
from repro.kernels import ops

M = 256                   # the service's default ICWS width
Q = 16                    # search_batch micro-batch
N_QUERY = 1024            # padded nonzeros of a query field row
P_LAKE = 2 ** 18          # ICWS corpus rows (store capacity)
P_SMALL = 2 ** 14         # the reduced lakes of the other families
INGEST_B, INGEST_N = 512, 4096   # one ingest batch of long tables
F = 3                     # key_indicator, values, values_sq


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def on_tpu(monkeypatch):
    """Aim the ops dispatch at the described chip: compiled kernels, TPU
    blocks, no autotune cache entries (none are keyed to tpu).  The
    persistent compilation cache is off: a TPU executable written here
    could not be read back without a chip."""
    monkeypatch.setattr(ops, "_backend", lambda: "tpu")
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, args, sharding):
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _fam(name):
    return make_family(name, storage=wmh_storage(M))


def _sketch_case(kernel, n, pack_vals, b=3 * Q):
    f = getattr(ops, kernel)
    args = [((b, n), jnp.float32), ((b, n), jnp.int32),
            ((b, n), jnp.float32)]
    return (lambda w, k, v: f(w, k, v, m=M, seed=0, pack_vals=pack_vals),
            args)


def _icws_fields_case(packed, p, n_q=Q):
    q = [((F, n_q, M), jnp.int32), ((F, n_q, M), jnp.float32),
         ((F, n_q), jnp.float32)]
    dtype = jnp.bfloat16 if packed else jnp.float32
    c = [((F, p, M), jnp.int32), ((F, p, M), dtype), ((F, p), jnp.float32)]
    return (lambda *a: ops.icws_estimate_fields(*a, qmap=QFIELD,
                                                cmap=CFIELD), q + c)


def _linear_fields_case(name, packed):
    fam = _fam(name)
    R, W = fam.reps, fam.width
    q = [((F, Q, R, W), jnp.float32)]
    dtype = jnp.bfloat16 if packed else jnp.float32
    c = [((F, P_SMALL, R, W), dtype)]
    return (lambda *a: ops.linear_estimate_fields(*a, qmap=QFIELD,
                                                  cmap=CFIELD), q + c)


def _sample_fields_case(name, packed, n_q=Q):
    S = _fam(name).slots
    q = [((F, n_q, S), jnp.int32), ((F, n_q, S), jnp.float32),
         ((F, n_q), jnp.float32)]
    dtype = jnp.bfloat16 if packed else jnp.float32
    c = [((F, P_SMALL, S), jnp.int32), ((F, P_SMALL, S), dtype),
         ((F, P_SMALL), jnp.float32)]
    return (lambda *a: ops.sample_estimate_fields(*a, qmap=QFIELD,
                                                  cmap=CFIELD), q + c)


def _jl_case():
    m = _fam("jl").m
    return (lambda k, v: ops.jl_sketch(k, v, m=m, seed=0),
            [((3 * Q, N_QUERY), jnp.int32), ((3 * Q, N_QUERY), jnp.float32)])


def _cs_case():
    fam = _fam("cs")
    return (lambda k, v: ops.countsketch_sparse(k, v, width=fam.width,
                                                reps=fam.reps, seed=0),
            [((3 * Q, N_QUERY), jnp.int32), ((3 * Q, N_QUERY), jnp.float32)])


def _cs_dense_case():
    return (lambda x: ops.countsketch(x, width=4096, reps=5, seed=0),
            [((2 ** 20,), jnp.float32)])


CASES = {
    "icws_sketch": lambda: _sketch_case("icws_sketch", N_QUERY, False),
    "icws_sketch_pack_vals": lambda: _sketch_case("icws_sketch", N_QUERY,
                                                  True),
    "icws_sketch_ingest": lambda: _sketch_case("icws_sketch", INGEST_N, True,
                                               b=INGEST_B),
    # DMH replicates every key 4x at m=256 before the launch
    "dmh_sketch": lambda: _sketch_case("dmh_sketch", 4 * N_QUERY, False),
    "dmh_sketch_pack_vals": lambda: _sketch_case("dmh_sketch", 4 * N_QUERY,
                                                 True),
    "jl_sketch": _jl_case,
    "countsketch_sparse": _cs_case,
    "countsketch_dense": _cs_dense_case,
    "icws_estimate_fields": lambda: _icws_fields_case(False, P_LAKE),
    "icws_estimate_fields_packed": lambda: _icws_fields_case(True, P_LAKE),
    # the other query counts the scan sees: one (the sequential path) and
    # a batch that pads to the 16-query block
    "icws_estimate_fields_q1": lambda: _icws_fields_case(False, P_LAKE, 1),
    "icws_estimate_fields_q9": lambda: _icws_fields_case(False, P_LAKE, 9),
    "icws_estimate_fields_packed_q1": lambda: _icws_fields_case(True, P_LAKE,
                                                                1),
    "icws_estimate_fields_packed_q9": lambda: _icws_fields_case(True, P_LAKE,
                                                                9),
    "cs_estimate_fields": lambda: _linear_fields_case("cs", False),
    "cs_estimate_fields_packed": lambda: _linear_fields_case("cs", True),
    "jl_estimate_fields": lambda: _linear_fields_case("jl", False),
    "jl_estimate_fields_packed": lambda: _linear_fields_case("jl", True),
    "ts_estimate_fields": lambda: _sample_fields_case("ts", False),
    "ps_estimate_fields_packed": lambda: _sample_fields_case("ps", True),
    # a whole 64-query batch in one launch: queries ride the grid in
    # blocks of 8, so the kernel and its VMEM do not grow with Q
    "ts_estimate_fields_q64": lambda: _sample_fields_case("ts", False,
                                                          n_q=64),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_served_kernel_compiles_for_v5e(case, one_chip, on_tpu):
    fn, args = CASES[case]()
    _compile(fn, args, one_chip)


def test_ranking_top_k_compiles_for_v5e(one_chip, on_tpu):
    """The candidate ranking of a micro-batch over the 2^18-row lake (an
    XLA program, no Pallas kernel)."""
    shape = jax.ShapeDtypeStruct((Q, P_LAKE), jnp.float32, sharding=one_chip)
    jax.jit(lambda s: ops.top_k(s, 128)).lower(shape).compile()


@pytest.mark.parametrize("chips", [1, 4])
def test_candidate_gather_compiles_for_v5e(chips, topo, on_tpu):
    """The candidates' join and sum gathered from the estimate planes of
    the 2^18-row lake, on one chip and with the planes split over the
    four chips of a v5e host (an XLA program, no Pallas kernel)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(topo.devices[:chips], ("data",))
    est = jax.ShapeDtypeStruct((6, Q, P_LAKE), jnp.float32,
                               sharding=NamedSharding(mesh, P(None, None,
                                                              "data")))
    idx = jax.ShapeDtypeStruct((Q, CANDIDATES), jnp.int32,
                               sharding=NamedSharding(mesh, P()))
    compiled = jax.jit(lambda e, i: ops.take_candidates(
        e, i, planes=CANDIDATE_PLANES)).lower(est, idx).compile()
    assert [o.shape for o in compiled.out_info] == [(Q, CANDIDATES)] * 2


def test_sharded_scan_compiles_for_v5e_2x2(topo, on_tpu):
    """The corpus scan sharded over the four chips of a v5e host: queries
    replicated, the packed 2^18-row store split along its row dim, the
    per-shard top-k merged -- one program across the mesh."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(topo.devices, ("data",))
    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P(None, "data"))
    q = [((F, Q, M), jnp.int32, rep), ((F, Q, M), jnp.float32, rep),
         ((F, Q), jnp.float32, rep)]
    c = [((F, P_LAKE, M), jnp.int32, rows),
         ((F, P_LAKE, M), jnp.bfloat16, rows), ((F, P_LAKE), jnp.float32, rows)]

    def scan(*a):
        est = ops.estimate_fields_sharded(
            _fam("icws").estimate_fields, a[:3], a[3:], qmap=QFIELD,
            cmap=CFIELD, mesh=mesh, axis="data")
        return ops.sharded_top_k(est[0], 10, mesh=mesh, axis="data")

    shapes = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d, sh in q + c]
    compiled = jax.jit(scan).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
