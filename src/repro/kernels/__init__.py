"""Pallas TPU kernels for the paper's compute hot-spots.

simplex_proj.py  fused Duchi simplex projection (paper §4.3): bitonic sort
                 network + Hillis-Steele scan down the slot axis of
                 transposed [L, n] slabs, VMEM-tiled over sources.
dual_oracle.py   one-pass fusion of the rest of the oracle: projection of
                 -(A^T lam + c)/gamma plus per-grid-step partial A x
                 histograms (one-hot accumulation on the VPU against the
                 [m, J] dual shape) and (c'x, ||x||^2) partials, so one
                 launch per bucket yields g, grad and x.
gather.py        the gather of A^T lam as an exact one-hot contraction on
                 the MXU against a VMEM-resident byte table of the duals
                 (Mosaic gathers only within one vector register).
ops.py           jit'd wrappers: the gather's dispatch (kernel on TPU for
                 J <= 16384 per family, else XLA's gather), slab
                 transposes, block sizing, bucket dispatch, >8192-width
                 fallback, interpret/TPU switch.
ref.py           pure-jnp oracles (the kernel tests' ground truth and the
                 off-TPU execution path of the fused dual oracle).

Validated with interpret=True on CPU; compiled for TPU v5e by
tests/test_tpu_compile.py.
"""
from repro.kernels.ops import (
    fused_dual_oracle,
    fused_dual_primal,
    fused_project_simplex,
)

__all__ = ["fused_dual_oracle", "fused_dual_primal", "fused_project_simplex"]
