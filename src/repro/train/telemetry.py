"""Sketch-based training telemetry: gradient agreement without moving gradients.

Estimating the pairwise cosine similarity of per-replica gradients normally
costs a full gradient gather (GBs).  With the paper's inner-product sketches
it costs ``O(m)`` per replica: each replica sketches its flattened gradient,
an all-gather moves only the m-sized sketches, and any monitor (host or
device) estimates all R^2 pairwise inner products from them.

Gradients of embedding / MoE-expert rows are *sparse with low overlap across
data shards* (each shard touches its own tokens' rows) -- precisely the
regime where Theorem 2 beats linear sketching, so the default sketcher here
is the device ICWS (weighted MinHash) path; a JL option is provided for
dense gradients.

Used for divergence detection (a replica whose gradient stops correlating
with the fleet signals data corruption or hardware fault -- see repro.ft)
and for diagnosing straggler-induced staleness in async settings.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    m: int = 256                  # sketch size (per replica)
    seed: int = 23
    method: str = "icws"          # icws (weighted minhash) | jl


def sketch_gradient(flat_grad: jnp.ndarray, cfg: TelemetryConfig):
    """[T] gradient -> sketch pytree (device path, batched-kernel friendly)."""
    if cfg.method == "jl":
        # hash-based +-1 projection, m rows (the JL sign stream of the
        # kernel registry, so these projections interoperate with
        # device-JL-sketched vectors)
        from repro.kernels.common import JL_SIGN_STREAM, hash_u32, salt_for
        t = jnp.arange(cfg.m, dtype=jnp.int32)
        idx = jnp.arange(flat_grad.shape[0], dtype=jnp.uint32)
        sign = jnp.where(
            (hash_u32(idx[None, :], salt_for(cfg.seed, JL_SIGN_STREAM, t)[:, None])
             & jnp.uint32(1)) == 0, 1.0, -1.0)
        proj = (sign @ flat_grad) / jnp.sqrt(cfg.m)
        return {"proj": proj}
    norm = jnp.linalg.norm(flat_grad)
    safe = jnp.maximum(norm, 1e-30)
    zn = flat_grad / safe
    w = (zn * zn)[None, :]
    keys = jnp.arange(flat_grad.shape[0], dtype=jnp.int32)[None, :]
    fp, val, _, _ = kops.icws_sketch(w, keys, zn[None, :], m=cfg.m,
                                     seed=cfg.seed)
    return {"fp": fp[0], "val": val[0], "norm": norm}


def estimate_pairwise(sketches, cfg: TelemetryConfig) -> jnp.ndarray:
    """Stacked sketches (leaves with leading replica dim R) -> [R, R] inner
    product estimates."""
    if cfg.method == "jl":
        proj = sketches["proj"]                       # [R, m]
        return proj @ proj.T
    # the R sketches are both the queries and the corpus of one launch
    fp, val, norm = (sketches[k][None] for k in ("fp", "val", "norm"))
    return kops.icws_estimate_fields(fp, val, norm, fp, val, norm,
                                     qmap=(0,), cmap=(0,))[0]


def gradient_agreement(flat_grad: jnp.ndarray, axis_name: str,
                       cfg: TelemetryConfig) -> jnp.ndarray:
    """Inside shard_map over the data axis: [R, R] cosine-similarity estimate.

    Only m-sized sketches cross the network (all_gather), never gradients.
    """
    sk = sketch_gradient(flat_grad, cfg)
    gathered = jax.tree.map(
        lambda x: jax.lax.all_gather(x, axis_name), sk)
    est = estimate_pairwise(gathered, cfg)
    if cfg.method == "jl":
        return est
    norms = gathered["norm"]
    denom = jnp.outer(norms, norms)
    return est / jnp.maximum(denom, 1e-30)
