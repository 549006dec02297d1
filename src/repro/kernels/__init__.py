"""Pallas TPU kernels for the perf-critical compute layers.

  maxplus_matmul  — (max,+) semiring matmul for Max-Plus MCM analysis (VPU)
  maxplus_bellman — device-resident ELLPACK max-plus Bellman-Ford
                    lambda-search (the exact "csr-jit" mcr_batch backend,
                    float64, multi-lambda probing; plain jnp, no Pallas)
  lif_crossbar    — fused crossbar matvec (MXU) + LIF neuron update (VPU)
  flash_attention — block-wise online-softmax attention (MXU+VPU)
  mamba_scan      — chunked selective-state-space scan (VPU)

Each kernel has a pure-jnp oracle in ``ref.py``; ``ops.py`` holds the jit'd
public wrappers (padding, interpret-mode dispatch on CPU).
``maxplus_bellman.py`` carries its own jnp fallbacks and is imported
lazily by :mod:`repro.core.maxplus` (keeps core importable without jax).
"""

from . import ops, ref

__all__ = ["ops", "ref"]
