"""Fused sub-byte-unpack -> dequantize -> MXU matmul Pallas kernel.

The deployment form of AutoQ channels searched to QBN <= 4: weights live in
HBM bit-packed along K (kernels/pack.py format -- int4 nibbles or int2
crumbs, 2 or 4 values per byte), with one f32 scale per output channel.  The
kernel streams (bk/f, bn) *packed* tiles into VMEM -- so weight-side HBM
traffic is 1/f byte per element, half (int4) or a quarter (int2) of the int8
path in kernels/quant_matmul.py -- unpacks with shift/mask on the VPU,
accumulates the MXU matmul in f32, and applies per-channel scales once at the
final K step.

Unpack-in-kernel: byte field i of packed row r is original K row r*f+i
(little-endian within the byte).  Extraction is ``(b >> store_bits*i) & mask``
followed by a two's-complement sign extension; the f field planes are
interleaved back into K order with a stack+reshape, which lowers to cheap
VREG shuffles on TPU (and is exact in interpret mode on CPU).  A follow-on
for native-int4 MXU dtypes is tracked in ROADMAP.md.

Tiling matches quant_matmul: grid (M/bm, N/bn, K/bk), K innermost so the f32
accumulator tile stays resident in VMEM scratch; ``bk`` must be a multiple of
``f`` so packed tiles stay byte-aligned.

Invariants:

* **Scale placement**: per-output-channel scales are applied exactly once,
  at the *final* K step, to the completed f32 accumulator -- never per
  K-tile.  Folding scales into partial products would change the rounding
  of the accumulation and break bit-parity with the jnp reference
  (``ref.quant_matmul_ref`` scales the full integer-ish product too).
* **Unpack order matches pack.py's K-axis order**: field plane ``i`` of
  packed row ``r`` is original K row ``r*f + i``; the stack+reshape
  interleave restores exact K order before the MXU dot, so the kernel
  contracts the same (K, N) matrix the host packed.
* **Accumulation dtype**: the MXU matmul accumulates in f32
  (``preferred_element_type``) regardless of the output dtype; the cast
  happens after scaling at the final K step.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import interpret_mode
from repro.kernels.pack import SUB8_FACTORS, extract_fields


def _kernel(x_ref, pw_ref, s_ref, o_ref, acc_ref, *, k_steps: int,
            store_bits: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    f = SUB8_FACTORS[store_bits]
    x = x_ref[...].astype(jnp.float32)
    pw = pw_ref[...].astype(jnp.int32)            # (bk/f, bn) packed bytes
    w = jnp.stack(extract_fields(pw, store_bits), axis=1)   # (bk/f, f, bn)
    w = w.reshape(pw.shape[0] * f, pw.shape[1]).astype(jnp.float32)
    acc_ref[...] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _done():
        scale = s_ref[...].astype(jnp.float32)    # (1, bn) per-channel
        o_ref[...] = (acc_ref[...] * scale).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("store_bits", "bm", "bn", "bk",
                                    "interpret"))
def packed_matmul_pallas(x: jnp.ndarray, pw: jnp.ndarray, scale: jnp.ndarray,
                         *, store_bits: int, bm: int = 128, bn: int = 128,
                         bk: int = 128,
                         interpret: Optional[bool] = None) -> jnp.ndarray:
    """x: (M, K); pw: (K/f, N) int8 packed (f = 8/store_bits); scale: (N,).

    M, K, N must be multiples of the block shape (ops.py pads; zero pad bytes
    unpack to zero weights, so padding is exact)."""
    f = SUB8_FACTORS[store_bits]
    M, K = x.shape
    Kp, N = pw.shape
    assert Kp * f == K, (Kp, f, K)
    assert bk % f == 0, (bk, f)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (M, K, N, bm, bn, bk)
    k_steps = K // bk
    return pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps, store_bits=store_bits),
        grid=(M // bm, N // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // f, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret_mode(interpret),
        name="packed_matmul_pallas",
    )(x, pw, scale.reshape(1, N))
