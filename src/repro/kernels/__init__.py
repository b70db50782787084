"""Pallas TPU kernels for AutoQ's deployment hot spots.

quant_matmul   -- fused int8-dequant (per-output-channel scale) + MXU matmul
packed_matmul  -- fused sub-byte unpack (int4 nibble / int2 crumb along K)
                  + dequant + MXU matmul: 1/2 or 1/4 the weight HBM bytes
packed_mixed_matmul -- bucketed dispatch over a PackedWeight (a searched
                  mixed-QBN policy's serving contraction)
binary_matmul  -- bit-plane (binarized) matmul, alpha-weighted sign planes
fake_quant     -- per-channel quantize-dequantize (QAT forward)
flash_attention / paged_prefill_attention -- the attention subsystem
                  (attention.py, docs/attention.md): tiled flash forward,
                  and block-table paged attention for q-tiles of k tokens
                  per sequence -- chunked prefill and (k = 1, via the
                  paged_decode_attention wrapper) decode are one kernel
                  (int8 pages dequantize in VMEM)

pack.py holds the bit-packing format + the PackedWeight pytree container
(see docs/packed_layout.md); ops.py exposes the jit'd public wrappers
(padding + pallas/ref dispatch); ref.py holds the pure-jnp oracles every
kernel is allclose-tested against (for attention the oracle is
models/layers.attention_ref).  Kernels run interpreted on the CPU
backend and compiled on TPU (backend.py decides when a kernel is traced).
"""
from repro.kernels.attention import (flash_attention, paged_decode_attention,
                                     paged_prefill_attention)
from repro.kernels.ops import (binary_matmul, fake_quant_channels,
                               packed_matmul, packed_mixed_matmul,
                               quant_matmul)
from repro.kernels.pack import PackedWeight, pack_sub8, unpack_sub8

__all__ = ["binary_matmul", "fake_quant_channels", "flash_attention",
           "packed_matmul", "packed_mixed_matmul", "paged_decode_attention",
           "paged_prefill_attention", "quant_matmul", "PackedWeight",
           "pack_sub8", "unpack_sub8"]
