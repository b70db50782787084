"""Per-channel quantize-dequantize (QAT forward) Pallas kernel.

Elementwise per-channel fake quantization with precomputed scales/levels
(the per-channel amax reduction is a cheap one-pass jnp op outside; fusing it
would force a two-phase kernel for no HBM saving).  Used on the QAT
fine-tuning path where the same weight tile is fake-quantized every step.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import interpret_mode
from repro.quant.linear_quant import FULL_BITS


def _kernel(x_ref, s_ref, lv_ref, b_ref, o_ref, *, full_bits: float):
    x = x_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)           # (1, bn)
    lv = lv_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    q = jnp.clip(jnp.round(x / s), -lv, lv) * s
    out = jnp.where(b <= 0.5, 0.0, jnp.where(b >= full_bits, x, q))
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "interpret", "full_bits"))
def fake_quant_pallas(x: jnp.ndarray, scale: jnp.ndarray, levels: jnp.ndarray,
                      bits: jnp.ndarray, *, bm: int = 256, bn: int = 128,
                      interpret: Optional[bool] = None,
                      full_bits: float = FULL_BITS) -> jnp.ndarray:
    """x: (M, N); scale/levels/bits: (N,) per-channel.  ``full_bits`` is the
    pass-through threshold, threaded from quant.linear_quant.FULL_BITS so the
    kernel and the reference quantizer cannot silently diverge."""
    M, N = x.shape
    assert M % bm == 0 and N % bn == 0, (M, N, bm, bn)
    return pl.pallas_call(
        functools.partial(_kernel, full_bits=float(full_bits)),
        grid=(M // bm, N // bn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret_mode(interpret),
        name="fake_quant_pallas",
    )(x, scale.reshape(1, N), levels.reshape(1, N), bits.reshape(1, N))
