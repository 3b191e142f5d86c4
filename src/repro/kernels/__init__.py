"""Pallas TPU kernels for the paper's fused per-layer clipping hot path,
plus the backend engine that makes them load-bearing.

  ghost_norm.py    per-example grad norms² (full + per-shard blocked)
  clip_reduce.py   fused clip-scale-accumulate Σ_i c_i A_iᵀ G_i, and the
                   book-keeping epilogue's per stack slice
                   (`scale_contract`, over residuals cached by core.bk)
  fused_clip.py    norms² + clip + reduce in ONE pass over A, G
  ref.py           pure-jnp oracles (the allclose ground truth)
  ops.py           thin jitted wrappers that hit one kernel directly
  backend.py       xla | pallas | auto engine registry + scoped config
  autotune.py      the sweep's measured (op, shape-bucket) table behind auto

`repro.core.dp_layers` resolves every ghost op through `backend.active()`;
import `backend` and use `backend.scoped("pallas")` (or
`DPConfig(backend=...)`) to route training through the kernels.
"""
from repro.kernels import backend  # noqa: F401

__all__ = ["backend"]
