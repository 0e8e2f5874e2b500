"""Pallas TPU kernels for the RPIQ compute hot-spots.

  - hessian_accum  — H = X^T X calibration Gram accumulation (paper eq. 9)
  - w4a16_matmul   — int4-grouped dequant matmul (quantized serving path)
  - quant_pack     — fused quantize-to-grid + nibble pack (stage-2 projection
                     and deployment packing)
  - gptq_block     — the stage-1 GPTQ lazy-block sweep fused into ONE
                     ``pallas_call``: grid (members, Cout tiles, lazy
                     blocks), the working row tile stays VMEM-resident
                     for the whole sweep while the member's Cholesky
                     factor streams in one ``(blocksize, Cin)`` slab per
                     lazy block, replacing the O(Cin)
                     ``fori_loop``-of-``dynamic_slice`` XLA ops per sweep
                     with a single kernel dispatch.  Dispatch contract
                     (``ops.gptq_block``): ``impl="pallas"|"xla"`` force
                     a backend; ``"auto"`` uses pallas on TPU when the
                     row tile's residency
                     ``4·Cin·(4·block_out + 3·blocksize)`` bytes + 1 MiB
                     fits the budget (128-row tiles up to Cin = 3072),
                     else XLA; rows are padded to the ``block_out`` tile
                     and sliced back.

``ops`` is the dispatch layer (pallas on TPU / interpret-validated on CPU /
XLA fallback); ``ref`` holds the pure-jnp/NumPy oracles used by the
allclose tests.
"""
from repro.kernels import ops, ref  # noqa: F401
