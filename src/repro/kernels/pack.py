"""Sub-byte weight packing: the executable form of searched QBN policies.

AutoQ lands most weight channels at 2--5 bits, but an int8 store spends a
full byte per element regardless -- the weight-side HBM traffic the roofline
reward optimizes for is then ~2x larger than the policy warrants.  This
module packs quantized channels with QBN <= 4 into nibble (int4, 2
values/byte) or crumb (int2, 4 values/byte) buffers along the contraction
(K) axis, so HBM bytes track the searched bit-width.

Packing format (little-endian within the byte, along K):

    packed[r] = sum_i (q[r*f + i] & mask) << (store_bits * i),   f = 8/store_bits

i.e. byte r of a channel holds original K positions ``r*f .. r*f+f-1``, the
lowest-order field first.  K is zero-padded to a multiple of ``f`` (zero
bytes unpack to zero weights, so matmuls over the pad are exact no-ops).
The channel (N) axis is untouched: per-channel scales and per-channel-group
QBNs from a :class:`~repro.quant.policy.QuantPolicy` map 1:1 onto packed
columns.

:class:`PackedWeight` is the bucketed whole-tensor layout
(``quant.linear_quant.quant_pack_sub8`` builds it): channels are routed by
QBN into ``pruned`` (no storage) / ``int2`` / ``int4`` / ``int8`` / ``full``
(bf16 passthrough) buckets.  It is a registered pytree whose array children
all keep any leading stack dims, so it rides through ``jax.jit`` and
``lax.scan`` (the LM's stacked-block layout) unchanged.

Invariants every consumer may rely on (and none may weaken):

* **K-axis packing order**: packing always runs along the contraction
  axis, little-endian within the byte -- byte ``r`` of a channel holds
  original K rows ``r*f .. r*f+f-1``, lowest-order field first.  The N
  (output-channel) axis is never packed, so per-channel scales and bucket
  membership map 1:1 onto packed columns.
* **Zero padding is exact**: K pads to a multiple of ``f`` with zero
  bytes, which unpack to zero weights -- contractions over the pad are
  no-ops, so callers (ops.py, the Pallas grids) may over-tile freely.
* **Fields are two's-complement in ``store_bits``**: :func:`extract_fields`
  is the one definition of the read side, shared by the host unpack and
  the in-VMEM kernel unpack, so the format cannot drift between them.
* **Grid identity with fake-quant**: each channel quantizes on its own
  ``levels = 2^(b-1)-1`` grid, identical to ``quant.linear_quant``'s
  fake-quant -- dequantizing a ``b <= 8`` bucket reproduces the
  search-time numerics bit-exactly (serving-parity tests pin this).

See docs/packed_layout.md for the full format description.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# storage width -> values per byte
SUB8_FACTORS = {2: 4, 4: 2}


def bucket_of_bits(bits: float) -> str:
    """Storage bucket for one channel's QBN: the bucketed sub-byte layout.

    <=0 pruned (no storage), <=2 crumb-packed, <=4 nibble-packed, <=8 plain
    int8, >8 bf16 passthrough."""
    b = round(float(bits))
    if b <= 0:
        return "pruned"
    if b <= 2:
        return "int2"
    if b <= 4:
        return "int4"
    if b <= 8:
        return "int8"
    return "full"


STORE_BITS = {"int2": 2, "int4": 4, "int8": 8}


def pack_sub8(q: jnp.ndarray, store_bits: int, axis: int = -2) -> jnp.ndarray:
    """Pack integer values (fitting signed ``store_bits``) into int8 bytes.

    q: integer array; values must lie in [-2^(store_bits-1), 2^(store_bits-1)-1].
    Returns int8 with ``axis`` shrunk to ceil(K / (8/store_bits)).
    """
    f = SUB8_FACTORS[store_bits]
    mask = (1 << store_bits) - 1
    q = jnp.asarray(q)
    axis = axis % q.ndim
    K = q.shape[axis]
    pad = (-K) % f
    if pad:
        widths = [(0, 0)] * q.ndim
        widths[axis] = (0, pad)
        q = jnp.pad(q, widths)
    qm = jnp.moveaxis(q, axis, 0).astype(jnp.int32) & mask
    Kp = qm.shape[0] // f
    qm = qm.reshape((Kp, f) + qm.shape[1:])
    packed = jnp.zeros((Kp,) + qm.shape[2:], jnp.int32)
    for i in range(f):
        packed = packed | (qm[:, i] << (store_bits * i))
    # reinterpret the byte pattern as signed before narrowing (int32->int8
    # conversion of values > 127 is not portable across backends)
    packed = packed - ((packed >> 7) << 8)
    return jnp.moveaxis(packed.astype(jnp.int8), 0, axis)


def extract_fields(pm: jnp.ndarray, store_bits: int) -> list:
    """Sign-extended field planes of packed bytes (int32 bit patterns).

    The single definition of the byte layout's read side -- shared by
    :func:`unpack_sub8` and the in-VMEM unpack in packed_matmul's kernel,
    so the format cannot drift between host packing and kernel unpacking.
    Returns ``f`` arrays shaped like ``pm``; plane ``i`` holds original K
    position ``r*f + i`` for packed row ``r``."""
    mask = (1 << store_bits) - 1
    out = []
    for i in range(SUB8_FACTORS[store_bits]):
        m = (pm >> (store_bits * i)) & mask
        out.append(m - ((m >> (store_bits - 1)) << store_bits))
    return out


def unpack_sub8(packed: jnp.ndarray, store_bits: int, k: int,
                axis: int = -2) -> jnp.ndarray:
    """Inverse of :func:`pack_sub8`: int8 bytes -> int8 values, ``axis``
    restored to length ``k`` (the pre-padding K)."""
    f = SUB8_FACTORS[store_bits]
    packed = jnp.asarray(packed)
    axis = axis % packed.ndim
    pm = jnp.moveaxis(packed, axis, 0).astype(jnp.int32)
    v = jnp.stack(extract_fields(pm, store_bits), axis=1)   # (Kp, f, ...)
    v = v.reshape((pm.shape[0] * f,) + pm.shape[1:])[:k]
    return jnp.moveaxis(v.astype(jnp.int8), 0, axis)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedWeight:
    """Bucketed sub-byte weight store for one (..., K, N) matmul weight.

    parts[i] mirrors buckets[i]:
      pruned -> (sentinel (..., K, 0) int8)  (channels reconstruct as zero;
               the zero-width array carries the leading stack dims so an
               all-pruned weight still dequantizes to the right shape)
      int2   -> (packed (..., ceil(K/4), nb) int8, scale (..., nb) f32)
      int4   -> (packed (..., ceil(K/2), nb) int8, scale (..., nb) f32)
      int8   -> (q      (..., K, nb)      int8, scale (..., nb) f32)
      full   -> (w      (..., K, nb)      bf16)

    Static aux: ``k``/``n`` (logical contraction length / channel count),
    ``buckets`` = ((name, channel-index tuple), ...), ``out_dtype``.  All
    array children keep leading stack dims, so a stacked (R, K, N) weight
    scans exactly like a plain array (``lax.scan`` slices the children; the
    aux -- per-channel bucket membership -- is R-invariant by construction:
    scales reduce over the stack dim like the fake-quant path).
    """
    parts: Tuple[Tuple[Any, ...], ...]
    k: int
    n: int
    buckets: Tuple[Tuple[str, Tuple[int, ...]], ...]
    out_dtype: str = "float32"

    def tree_flatten(self):
        return self.parts, (self.k, self.n, self.buckets, self.out_dtype)

    @classmethod
    def tree_unflatten(cls, aux, children):
        k, n, buckets, out_dtype = aux
        return cls(parts=tuple(children), k=k, n=n, buckets=buckets,
                   out_dtype=out_dtype)

    # ------------------------------------------------------------- dequant
    def dequant(self) -> jnp.ndarray:
        """Reconstruct the dequantized (..., K, N) weight (jit-safe).

        Channels are reassembled in policy order from static slices of the
        per-bucket columns and one concatenate: a policy assigns QBNs per
        contiguous channel group, so the slices are few, and XLA copies
        them (a column scatter runs one index at a time on TPU)."""
        out_dt = jnp.dtype(self.out_dtype)
        lead = self.parts[0][0].shape[:-2]
        cols = []
        for (name, idx), part in zip(self.buckets, self.parts):
            if name == "pruned":
                c = jnp.zeros(lead + (self.k, len(idx)), out_dt)
            elif name == "full":
                c = part[0].astype(out_dt)
            else:
                data, scale = part
                if name != "int8":
                    data = unpack_sub8(data, STORE_BITS[name], self.k,
                                       axis=-2)
                c = (data.astype(jnp.float32) *
                     scale.astype(jnp.float32)[..., None, :]).astype(out_dt)
            cols.append(c)
        pieces = [cols[b][..., off:off + size]
                  for b, off, size in self._runs()]
        return pieces[0] if len(pieces) == 1 else \
            jnp.concatenate(pieces, axis=-1)

    def _runs(self):
        """Maximal runs of consecutive channels stored consecutively in one
        bucket: (bucket position, offset in bucket, length), channel order."""
        src = np.empty((self.n, 2), np.int64)
        for b, (_, idx) in enumerate(self.buckets):
            src[list(idx)] = np.stack([np.full(len(idx), b),
                                       np.arange(len(idx))], axis=1)
        starts = np.flatnonzero(
            (np.diff(src[:, 0]) != 0) | (np.diff(src[:, 1]) != 1)) + 1
        bounds = [0, *starts.tolist(), self.n]
        return [(int(src[s, 0]), int(src[s, 1]), e - s)
                for s, e in zip(bounds[:-1], bounds[1:])]

    # ----------------------------------------------------------- accounting
    def bucket_nbytes(self) -> dict:
        """Stored bytes per bucket (packed buffers + scales)."""
        out = {}
        for (name, _), part in zip(self.buckets, self.parts):
            out[name] = int(sum(a.size * a.dtype.itemsize for a in part))
        return out

    def hbm_bytes(self) -> int:
        """Total weight-side HBM bytes of this store."""
        return int(sum(self.bucket_nbytes().values()))
