"""Pallas TPU kernels for the sketching hot-spots + pure-jnp oracles.

Kernels (each = pallas_call + explicit BlockSpec VMEM tiling):
  * icws_sketch  -- batched weighted-MinHash (ICWS) sketching
  * countsketch  -- MXU-formulated CountSketch (dense gradients + padded
                    sparse batches for the CS serving family)
  * jl_sketch    -- MXU-formulated JL/AMS projection of padded sparse batches
  * estimate     -- fused multi-field Algorithm-5 estimator partials +
                    per-rep MXU dot estimation for the linear families
  * sample_estimate -- unaligned key-match contraction for the sampling
                    families (Threshold/Priority Sampling rows)

``ops`` holds the jit'd wrappers; ``ref`` the oracles used for validation.
"""
from . import ops, ref
from .countsketch import countsketch_pallas, countsketch_sparse_pallas
from .estimate import estimate_fields_pallas, linear_estimate_fields_pallas
from .icws_sketch import icws_sketch_pallas
from .jl_sketch import jl_sketch_pallas
from .sample_estimate import (sample_estimate_fields_pallas,
                              sample_inclusion_probs)

__all__ = ["ops", "ref", "icws_sketch_pallas", "countsketch_pallas",
           "countsketch_sparse_pallas", "jl_sketch_pallas",
           "estimate_fields_pallas", "linear_estimate_fields_pallas",
           "sample_estimate_fields_pallas", "sample_inclusion_probs"]
