"""Linear (uniform, symmetric) quantization with per-channel bit-widths.

The paper's quantizer: a weight output channel with QBN ``b`` is mapped onto the
integer grid {-(2^(b-1)-1), ..., 2^(b-1)-1} with a per-channel scale
``s = amax / (2^(b-1)-1)``.  ``b = 0`` prunes the channel, ``b >= FULL_BITS``
is a pass-through (full precision).  All functions are jit-safe and accept
*vector* bit-widths so a single call fake-quantizes a tensor whose channels
carry different QBNs -- the kernel-wise regime AutoQ searches over.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Bit-widths at or above this behave as full precision (f32 mantissa is 24
# bits; >=24-bit fixed point is indistinguishable for our purposes).
FULL_BITS = 24


def _levels(bits: jnp.ndarray) -> jnp.ndarray:
    """Number of positive quantization levels for signed symmetric quant."""
    bits = jnp.asarray(bits, jnp.float32)
    return jnp.maximum(2.0 ** (bits - 1.0) - 1.0, 1.0)


def fake_quant(x: jnp.ndarray, bits, axis: int | None = None) -> jnp.ndarray:
    """Quantize-dequantize ``x`` at ``bits`` (scalar or per-channel vector).

    Args:
      x: tensor to quantize.
      bits: scalar, or vector of shape ``x.shape[axis]`` with per-channel QBNs.
      axis: channel axis for per-channel scales (None -> per-tensor).
    """
    x = jnp.asarray(x)
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    if axis is None:
        amax = jnp.max(jnp.abs(xf))
        b = jnp.asarray(bits, jnp.float32)
    else:
        axis = axis % xf.ndim
        red = tuple(d for d in range(xf.ndim) if d != axis)
        amax = jnp.max(jnp.abs(xf), axis=red, keepdims=True)
        b = jnp.asarray(bits, jnp.float32)
        if b.ndim > 0:  # per-channel vector -> broadcastable shape
            shape = [1] * xf.ndim
            shape[axis] = xf.shape[axis]
            b = b.reshape(shape)
    lv = _levels(b)
    scale = jnp.where(amax > 0, amax / lv, 1.0)
    q = jnp.clip(jnp.round(xf / scale), -lv, lv) * scale
    out = jnp.where(b <= 0.5, 0.0, jnp.where(b >= FULL_BITS, xf, q))
    return out.astype(dtype)


def fake_quant_per_channel(w: jnp.ndarray, bits_per_channel, axis: int = -1):
    """Per-output-channel fake quantization (the paper's weight quantizer)."""
    return fake_quant(w, bits_per_channel, axis=axis)


def fake_quant_per_token(x: jnp.ndarray, bits) -> jnp.ndarray:
    """Row-wise (per-token) fake quantization: one dynamic scale per
    leading-index row, amax over the last (feature) axis.

    This is the serving-side activation quantizer: each token's activation
    is scaled by its own amax, so the result for a token is independent of
    whatever else shares the batch.  (A per-tensor scale would couple
    continuous-batching decode lanes: admitting a new request would change
    every other in-flight sequence's quantization grid.)  ``bits`` is a
    scalar; <= 0.5 prunes, >= FULL_BITS passes through, matching
    :func:`fake_quant`.
    """
    x = jnp.asarray(x)
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    b = jnp.asarray(bits, jnp.float32)
    lv = _levels(b)
    scale = jnp.where(amax > 0, amax / lv, 1.0)
    q = jnp.clip(jnp.round(xf / scale), -lv, lv) * scale
    out = jnp.where(b <= 0.5, 0.0, jnp.where(b >= FULL_BITS, xf, q))
    return out.astype(dtype)


@jax.custom_vjp
def ste_fake_quant(x: jnp.ndarray, bits: jnp.ndarray, axis: int):
    """Fake quant with a straight-through gradient estimator (QAT forward)."""
    return fake_quant(x, bits, axis=axis)


def _ste_fwd(x, bits, axis):
    return fake_quant(x, bits, axis=axis), None


def _ste_bwd(_, g):
    return (g, None, None)


ste_fake_quant.defvjp(_ste_fwd, _ste_bwd)


def quant_pack_int8(w: jnp.ndarray, bits, axis: int = -1):
    """Quantize to a *stored* int8 representation + per-channel f32 scales.

    This is the deployment path (what the Pallas ``quant_matmul`` kernel
    consumes): channels with QBN in [1, 8] round to int8 on their own grid,
    QBN 0 stores zeros, QBN > 8 falls back to the bf16 path at a higher layer
    (the packer clamps to 8 and the caller tracks the overflow set).

    Returns (q_int8, scale, eff_bits) with ``scale`` shaped like the channel
    axis and broadcastable against ``q``.
    """
    w = jnp.asarray(w, jnp.float32)
    axis = axis % w.ndim
    red = tuple(d for d in range(w.ndim) if d != axis)
    amax = jnp.max(jnp.abs(w), axis=red, keepdims=True)
    b = jnp.asarray(bits, jnp.float32)
    if b.ndim > 0:
        shape = [1] * w.ndim
        shape[axis] = w.shape[axis]
        b = b.reshape(shape)
    b = jnp.clip(b, 0.0, 8.0)
    lv = _levels(b)
    scale = jnp.where(amax > 0, amax / lv, 1.0)
    q = jnp.clip(jnp.round(w / scale), -lv, lv)
    q = jnp.where(b <= 0.5, 0.0, q)
    return q.astype(jnp.int8), scale.astype(jnp.float32), b


def dequant_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`quant_pack_int8` (reference; kernel fuses this)."""
    return q.astype(jnp.float32) * scale


def _take_cols(w: jnp.ndarray, idx) -> jnp.ndarray:
    """``w[..., idx]`` for an ascending channel list, as static slices of
    its consecutive runs: policies assign QBNs per contiguous channel group,
    so the runs are few, and a last-axis gather runs one index at a time
    on TPU."""
    idx = np.asarray(idx)
    cuts = np.flatnonzero(np.diff(idx) != 1) + 1
    pieces = [w[..., int(r[0]):int(r[-1]) + 1]
              for r in np.split(idx, cuts)]
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, -1)


def quant_pack_sub8(w: jnp.ndarray, bits, axis: int = -1):
    """Quantize to the *bucketed sub-byte* stored layout + per-channel scales.

    The deployment path that realizes searched sub-byte QBNs as actual HBM
    bytes (kernels/pack.py holds the container; kernels/ops.py the matmuls):
    each output channel is routed by its QBN into a storage bucket --

        b <= 0   pruned     no storage (reconstructs as zeros)
        b <= 2   int2       crumb-packed along K, 4 values/byte
        b <= 4   int4       nibble-packed along K, 2 values/byte
        b <= 8   int8       1 byte/value (same grid as quant_pack_int8)
        b >  8   full       bf16 passthrough (2 bytes/value)

    Channels quantize on their *own* grid (levels = 2^(b-1)-1, scale =
    amax/levels, amax reduced over all non-channel dims -- identical to
    fake_quant, so the packed store round-trips to the fake-quant numerics
    for b <= 8).  Because storage width >= QBN within each bucket, every
    quantized value fits its bucket's field exactly.

    w: (..., K, N) with output channels **last** (axis must be the last
    axis); bits: scalar or (N,) per-channel QBNs.  Bucket membership is
    static (numpy), so this is a load-time transform, not a jit-traceable
    op.  Returns a :class:`repro.kernels.pack.PackedWeight`.
    """
    # lazy import: kernels.fake_quant imports FULL_BITS from this module
    from repro.kernels.pack import (PackedWeight, STORE_BITS, bucket_of_bits,
                                    pack_sub8)
    w = jnp.asarray(w)
    assert w.ndim >= 2, w.shape
    assert axis % w.ndim == w.ndim - 1, \
        "packed layout requires output channels on the last axis"
    n, k = w.shape[-1], w.shape[-2]
    # |w| max is exact in w's own dtype; only one bucket's columns are ever
    # widened to f32 at a time (a published-width unembed is GBs in f32)
    amax = jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1))).astype(
        jnp.float32)                                                # (n,)
    b = np.rint(np.broadcast_to(
        np.asarray(bits, np.float32), (n,))).astype(np.int64)
    members: dict = {}
    for c in range(n):
        members.setdefault(bucket_of_bits(b[c]), []).append(c)
    parts, buckets = [], []
    for name in ("pruned", "int2", "int4", "int8", "full"):
        idx = members.get(name)
        if not idx:
            continue
        buckets.append((name, tuple(idx)))
        if name == "pruned":
            # zero-width sentinel keeps the leading (stack) dims observable
            # even when every channel is pruned, and scans like any child
            parts.append((jnp.zeros(w.shape[:-2] + (k, 0), jnp.int8),))
            continue
        idx_a = jnp.asarray(idx)
        cols = _take_cols(w, idx).astype(jnp.float32)
        if name == "full":
            parts.append((cols.astype(jnp.bfloat16),))
            continue
        lv = _levels(jnp.asarray(b[idx], jnp.float32))             # (nb,)
        am = amax[idx_a]
        sc = jnp.where(am > 0, am / lv, 1.0)
        q = jnp.clip(jnp.round(cols / sc), -lv, lv).astype(jnp.int32)
        data = q.astype(jnp.int8) if name == "int8" else \
            pack_sub8(q, STORE_BITS[name], axis=-2)
        # scale broadcast over leading (stack) dims so every child of the
        # pytree scans with the weight it belongs to
        scale = jnp.broadcast_to(sc, w.shape[:-2] + (len(idx),))
        parts.append((data, scale.astype(jnp.float32)))
    return PackedWeight(parts=tuple(parts), k=k, n=n, buckets=tuple(buckets),
                        out_dtype=str(w.dtype))
