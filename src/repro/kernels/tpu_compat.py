"""The one import site of the Pallas TPU compiler-params dataclass, the
one shared backend-detection policy, and the one float32 contract
precision.

jax renamed ``pltpu.TPUCompilerParams`` to ``pltpu.CompilerParams``; kernels
import ``CompilerParams`` from here (``repro.lint``'s ``jax-api-drift`` rule
enforces it) so the next rename is absorbed in one place.
"""
from __future__ import annotations

import jax
import jax.experimental.pallas.tpu as pltpu

CompilerParams = pltpu.CompilerParams

# Simple CNAPs subtracts nearly equal float32 quantities twice: the class
# covariance E[x x^T] - mu mu^T, and the Mahalanobis form (q - mu)^T S (q - mu)
# of large, nearby features.  On TPU the default matmul precision rounds dot
# operands to bfloat16, and that error then cancels into an indefinite
# covariance (a NaN Cholesky factor) or a wrong distance.  The contractions
# that feed those two differences (the class statistics and the Mahalanobis
# kernel) run at this precision; it changes nothing on CPU.
F32_CONTRACT = jax.lax.Precision.HIGHEST


def interpret_mode() -> bool:
    """True when Pallas kernels should run in interpret mode (any non-TPU
    backend).  The single policy shared by ops.py's wrappers and
    dispatch.py's backend resolution — keep them from drifting."""
    return jax.default_backend() != "tpu"
