"""Bit-plane ("binarized") matmul Pallas kernel.

TPU adaptation of the paper's XNOR-popcount binary convolution (DESIGN.md
sections 3 and 7): W ~= sum_m alpha_m B_m with B_m in {-1,+1} stored 1
bit/plane in HBM (the caller keeps planes as int8 for the MXU; packed-bit
storage is modeled in the roofline).  The kernel accumulates
sum_m alpha_m[n] * (x @ B_m) over K tiles with the planes loop unrolled
in-kernel, so each (bk, bn) weight tile of every plane is read exactly once.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import interpret_mode


def _kernel(x_ref, b_ref, a_ref, o_ref, acc_ref, *, k_steps: int,
            n_planes: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    for p in range(n_planes):                    # static unroll (<= 8 planes)
        bp = b_ref[p].astype(jnp.float32)        # (bk, bn) sign tile
        ap = a_ref[0, p].astype(jnp.float32)     # (bn,) per-channel alpha
        acc_ref[...] += jax.lax.dot(
            x, bp, preferred_element_type=jnp.float32) * ap[None, :]

    @pl.when(k == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def binary_matmul_pallas(x: jnp.ndarray, planes: jnp.ndarray,
                         alpha: jnp.ndarray, *, bm: int = 128, bn: int = 128,
                         bk: int = 128, interpret: Optional[bool] = None
                         ) -> jnp.ndarray:
    """x: (M, K); planes: (P, K, N) int8 {-1,+1}; alpha: (P, N) f32."""
    M, K = x.shape
    P, _, N = planes.shape
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (M, K, N, bm, bn, bk)
    k_steps = K // bk
    return pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps, n_planes=P),
        grid=(M // bm, N // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((P, bk, bn), lambda i, j, k: (0, k, j)),
            pl.BlockSpec((1, P, bn), lambda i, j, k: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret_mode(interpret),
        name="binary_matmul_pallas",
    )(x, planes, alpha.reshape(1, P, N))
