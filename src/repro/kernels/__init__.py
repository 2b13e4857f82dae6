"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel ships four layers:
  <name>.py    pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py       jit'd public wrappers (interpret=True off-TPU)
  ref.py       pure-jnp oracles (the allclose ground truth in tests)
  dispatch.py  backend policy (naive | ref | pallas | auto) + custom_vjp
               wrappers for the episodic hot path's aggregation sites —
               the layer train/serve code actually calls

Kernels: flash_attention (causal/window/softcap online-softmax),
mahalanobis (Simple CNAPs head), segment_pool / class_second_moment
(LITE's aggregation sites as one-hot MXU matmuls — weight-aware, so
padded TaskBatch lanes drop out natively), ssd_scan (Mamba-2
intra-chunk), gmm (per-expert grouped GEMM for the MoE dispatch),
int8_matmul (blocked int8 x f32 matmul with per-block scale applied
in-kernel and fp32 accumulation — the weight-stationary serving path's
native site for blockwise-quantized frozen weights; FORWARD-ONLY by
contract, no custom_vjp: serving never differentiates through it),
conv_pool (the backbone's image block, conv3x3 + ReLU + 2x2 max-pool in
one pass, and its weight and bias gradient in another; the backbone
calls them through a custom_vjp, which keeps the XLA composite's VJP for
an image that carries a tangent).
"""
