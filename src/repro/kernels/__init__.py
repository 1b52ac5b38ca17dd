"""Pallas TPU kernels for the QAT hot spots (+ jnp oracles in ref.py).

  fake_quant   — tiled quantize-dequantize (per-tensor & per-row-group)
  quant_matmul — fused q(X) @ q(W) with per-column-group weight scales,
                 plus the int8-coded serving variant
  bin_stats    — fused per-bin count/sum/sumsq (OBR Eq. 10 + oscillation)

Written against BlockSpec VMEM tiling for TPU; validated on CPU via
interpret=True (platform.resolve_interpret switches automatically).
"""
from repro.kernels import ops, ref  # noqa: F401
