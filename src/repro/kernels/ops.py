"""Public jit'd wrappers around the Pallas kernels.

Handle non-aligned shapes by padding to the block grid, dispatch between the
Pallas kernel (interpreted on CPU, compiled on TPU: kernels/backend.py) and
the pure-jnp reference, and expose a single `use_pallas` switch the
serving/QAT paths use.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import pack, ref
from repro.kernels.binary_matmul import binary_matmul_pallas
from repro.kernels.fake_quant import fake_quant_pallas
from repro.kernels.packed_matmul import packed_matmul_pallas
from repro.kernels.quant_matmul import quant_matmul_pallas
from repro.quant.linear_quant import FULL_BITS

def _pad_to(x, m, axis):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def quant_matmul(x, qw, scale, *, bm=128, bn=128, bk=128, use_pallas=True):
    """y = x @ (qw * scale[None, :]).  x (M,K) f32/bf16; qw (K,N) int8."""
    if not use_pallas:
        return ref.quant_matmul_ref(x, qw, scale)
    M, K = x.shape
    N = qw.shape[1]
    xp = _pad_to(_pad_to(x, bm, 0), bk, 1)
    wp = _pad_to(_pad_to(qw, bk, 0), bn, 1)
    sp = _pad_to(scale, bn, 0)
    y = quant_matmul_pallas(xp, wp, sp, bm=bm, bn=bn, bk=bk)
    return y[:M, :N]


def packed_matmul(x, pw, scale, *, store_bits, bm=128, bn=128, bk=128,
                  use_pallas=True):
    """y = x @ (unpack(pw) * scale[None, :]) for sub-byte packed weights.

    x: (M, K) f32/bf16; pw: (ceil(K/f), N) int8 bit-packed along K
    (kernels.pack format, f = 8/store_bits); scale: (N,) f32.  Weight-side
    HBM traffic is 1/f byte per element versus 1 for quant_matmul."""
    f = pack.SUB8_FACTORS[store_bits]
    M, K = x.shape
    Kp, N = pw.shape
    assert Kp == -(-K // f), (K, Kp, f)
    if not use_pallas:
        return ref.packed_matmul_ref(x, pw, scale, store_bits)
    assert bk % f == 0, (bk, f)
    # logical K after byte-alignment pad, then after block pad
    k_log = Kp * f + ((-Kp * f) % bk)
    xp = jnp.pad(x, (((0, (-M) % bm), (0, k_log - K))))
    wp = _pad_to(_pad_to(pw, bk // f, 0), bn, 1)
    sp = _pad_to(scale, bn, 0)
    y = packed_matmul_pallas(xp, wp, sp, store_bits=store_bits, bm=bm, bn=bn,
                             bk=bk)
    return y[:M, :N]


def packed_mixed_matmul(x, w: "pack.PackedWeight", *, use_pallas=True):
    """y = x @ dequant(w) for a bucketed PackedWeight (2-d weights).

    Dispatches each storage bucket to its kernel -- int2/int4 to
    packed_matmul, int8 to quant_matmul, bf16 passthrough to a plain matmul,
    pruned channels to implicit zeros -- and scatters the per-bucket outputs
    back into policy channel order.  This is the serving contraction a
    searched mixed-QBN policy compiles to."""
    M, K = x.shape
    assert K == w.k, (K, w.k)
    out = jnp.zeros((M, w.n), jnp.float32)
    for (name, idx), part in zip(w.buckets, w.parts):
        if name == "pruned":
            continue
        if name == "full":
            y = x.astype(jnp.float32) @ part[0].astype(jnp.float32)
        elif name == "int8":
            y = quant_matmul(x, part[0], part[1].reshape(-1),
                             use_pallas=use_pallas)
        else:
            y = packed_matmul(x, part[0], part[1].reshape(-1),
                              store_bits=pack.STORE_BITS[name],
                              use_pallas=use_pallas)
        out = out.at[:, jnp.asarray(idx)].set(y.astype(jnp.float32))
    return out.astype(x.dtype)


def binary_matmul(x, planes, alpha, *, bm=128, bn=128, bk=128,
                  use_pallas=True):
    """y = sum_p alpha[p] * (x @ planes[p]).  planes (P,K,N) int8 signs."""
    if not use_pallas:
        return ref.binary_matmul_ref(x, planes, alpha)
    M, K = x.shape
    P, _, N = planes.shape
    xp = _pad_to(_pad_to(x, bm, 0), bk, 1)
    pp = _pad_to(_pad_to(planes, bk, 1), bn, 2)
    ap = _pad_to(alpha, bn, 1)
    y = binary_matmul_pallas(xp, pp, ap, bm=bm, bn=bn, bk=bk)
    return y[:M, :N]


def fake_quant_channels(x, scale, levels, bits, *, bm=256, bn=128,
                        use_pallas=True, full_bits=FULL_BITS):
    """Per-channel quantize-dequantize of x (M, N) with (N,) channel params.

    ``full_bits`` (default quant.linear_quant.FULL_BITS) is the single
    pass-through threshold shared by the kernel and the jnp reference."""
    if not use_pallas:
        return ref.fake_quant_ref(x, scale, levels, bits, full_bits=full_bits)
    M, N = x.shape
    xp = _pad_to(_pad_to(x, bm, 0), bn, 1)
    pad1 = lambda v: _pad_to(v, bn, 0)
    # padded channels: scale/levels 1 avoids div-by-zero; bits 0 prunes them
    sp = jnp.where(pad1(scale) == 0, 1.0, pad1(scale)) if N % bn else scale
    lp = jnp.where(pad1(levels) == 0, 1.0, pad1(levels)) if N % bn else levels
    bp = pad1(bits)
    y = fake_quant_pallas(xp, sp, lp, bp, bm=bm, bn=bn, full_bits=full_bits)
    return y[:M, :N]
