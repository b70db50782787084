"""Fused dequantize(int8, per-channel scale) -> MXU matmul Pallas kernel.

The deployment form of AutoQ-quantized weights on TPU (DESIGN.md section 3):
weights live in HBM as int8 (int4-packed channels are unpacked at load by the
caller) with one f32 scale per output channel; the kernel streams (bk, bn)
weight tiles into VMEM, runs the MXU in f32 accumulation, and applies the
per-channel scale once at the final K step -- so dequantization costs no HBM
round-trip and the weight-side HBM traffic is 1 byte/element instead of 2.

Tiling: grid (M/bm, N/bn, K/bk); K innermost so the f32 accumulator tile
stays resident in VMEM scratch.  Block shapes default to MXU-aligned 128s
(the allclose tests sweep other shapes, incl. non-aligned edges via padding
in ops.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import interpret_mode


def _kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)          # int8 -> f32 inside VMEM
    acc_ref[...] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _done():
        scale = s_ref[...].astype(jnp.float32)  # (1, bn) per-channel
        o_ref[...] = (acc_ref[...] * scale).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def quant_matmul_pallas(x: jnp.ndarray, qw: jnp.ndarray, scale: jnp.ndarray,
                        *, bm: int = 128, bn: int = 128, bk: int = 128,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """x: (M, K); qw: (K, N) int8; scale: (N,) f32.  M, K, N must be
    multiples of the block shape (ops.py pads)."""
    M, K = x.shape
    N = qw.shape[1]
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (M, K, N, bm, bn, bk)
    k_steps = K // bk
    return pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps),
        grid=(M // bm, N // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret_mode(interpret),
        name="quant_matmul_pallas",
    )(x, qw, scale.reshape(1, N))
