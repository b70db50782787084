"""Pallas attention subsystem: fused flash prefill + block-table paged decode.

Two kernels cover the serving hot path (models/layers.py owns the
``impl="pallas"|"ref"`` dispatch; the jnp chunked-flash path there is the
oracle both kernels are property-tested against):

* :func:`flash_attention` -- tiled flash-attention forward for prefill (and
  dense-cache decode, ``Sq == 1``).  Grid ``(B, nq, nk)`` with the KV axis
  innermost: the f32 accumulator, running max ``m`` and normalizer ``l``
  live in VMEM scratch across the KV tiles of one q tile (online softmax),
  so no (Sq, Skv) score matrix ever exists.  GQA is folded into the tile:
  each KV head's ``G = Hq/Hkv`` query heads are rows of one ``(bq*G, D)``
  matrix, so each K/V tile is loaded once per KV head, not once per query
  head.  Causal, sliding-window and softcap masking run on the score tile
  in VMEM.

* :func:`paged_prefill_attention` -- block-table-aware attention over the
  paged KV pool (serve/paged_kv.py layout) for q tiles of ``k`` tokens per
  sequence: the chunked-prefill workhorse, and (at ``k == 1``, via the
  :func:`paged_decode_attention` wrapper) the decode step.  Each sequence
  walks only its live pages, ``[first, last]`` of its block table
  (:func:`live_pages`): ``last`` holds the row's highest real position
  (causality hides every key past it) and, for sliding-window blocks,
  ``first`` is the oldest block inside the window of its lowest.  A grid
  step is a compute block of ``ppb = 128 // page_size`` pages, one lane
  width of slots: the block table rides in as a scalar-prefetch operand,
  and ``ppb`` BlockSpecs per pool resolve ``bt[seq, first + i*ppb + j]``
  *before* each step, so the pipeline DMAs exactly those physical pages
  HBM->VMEM while the step before computes -- there is no dense gather and
  no (B, nb*page_size) intermediate.  The step concatenates the pages into
  one ``(ppb*page_size, D)`` K / V tile and one score tile per head.
  Slots past the last live page are masked; steps past it repeat its
  indices, so they fetch nothing and compute nothing, and an idle row
  walks no page at all.  Causal masking runs against each q row's own
  position, so a chunk's rows attend earlier chunks' pages plus their own
  chunk's already-written slots (chunk offsets need no extra state).

Block layout (what the TPU compiler accepts: the last two dims of every
block are whole array dims or multiples of (8, 128)).  Both kernels run
head-major: the wrappers fold q to ``(B, Hkv, rows, D)`` with ``rows =
tokens*G`` (row ``t*G + g`` is query head ``h*G + g`` of token ``t``) and
expand q positions to one ``(rows, 1)`` column per row, so no kernel
reshapes or repeats anything.  A block carries every KV head -- q ``(1, Hkv,
rows, D)``, a KV tile ``(1, Hkv, bk, D)``, a pool page ``(1, Hkv, ps, D)``
of the head-major pool ``(P, Hkv, ps, D)`` -- and the kernel loops the heads
with static indices.  KV positions arrive as ``(1, bk)`` rows (``(B, 1,
Skv)`` / ``(P, 1, ps)`` arrays).  The paged kernel takes each pool as
``ppb`` operands, one BlockSpec per page of the compute block, because
Mosaic's DMA cannot slice one 16-slot position or scale row out of a
pool: it lays such rows out 128 lanes wide.  The layout is the same under
the CPU interpreter and on TPU: interpret-mode tests trace what the chip
compiles.

VMEM: the paged kernel's q / out blocks and f32 scratch grow with the q
tile's rows (``k * Hq/Hkv``), so the wrapper sets ``vmem_limit_bytes``
from the call's shapes (:func:`_paged_vmem_bytes`) rather than lean on
the compiler's default scoped limit.

int8 KV pages (``kv_bits=8`` pool): the kernel streams the int8 page plus
its per-(head, slot) scale page ``(1, Hkv, ps)`` into VMEM and applies the
scales on-chip -- to the scores for K (``q.(kq*s) = (q.kq)*s``) and to the
probabilities for V (``p.(vq*s) = (p*s).vq``), where a slot's scale is a
row broadcast -- so KV HBM traffic stays 1 byte/element and f32 only ever
exists on-chip.

Numerics shared by both kernels (matching the jnp oracle step for step):
scores, softmax statistics and accumulation are f32 regardless of input
dtype; masked slots contribute exact zeros (``exp(-inf - m_safe) == 0``);
position ``POS_SENTINEL`` (int32 max) is unconditionally unattendable; an
all-masked row normalizes by ``max(l, 1e-30)`` to exact zeros.  Results
match the oracle to f32 rounding: the online-softmax rescale, matmul
accumulation order and (int8) scale placement each differ from the
oracle's single-shot softmax by a few ulps.

Where a kernel runs is decided at trace time (kernels/backend.py):
interpreted on the CPU backend, compiled on TPU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import interpret_mode

NEG_INF = float("-inf")
POS_SENTINEL = np.iinfo(np.int32).max
_LANES = 128                 # TPU vector lane count (last-dim tile unit)
_SUBLANES = 8                # f32 sublane count (second-minor tile unit)


def _pad_axis(x, mult, axis, value=0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _fold_heads(x, Hkv):
    """(B, S, Hkv*G, D) -> (B, Hkv, S*G, D): row ``s*G + g`` of head h."""
    B, S, Hq, D = x.shape
    G = Hq // Hkv
    return x.reshape(B, S, Hkv, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, S * G, D)


def _unfold_heads(x, S, G):
    """Inverse of :func:`_fold_heads` for the first ``S*G`` rows."""
    B, Hkv, _, D = x.shape
    return x[:, :, :S * G].reshape(B, Hkv, S, G, D).transpose(
        0, 2, 1, 3, 4).reshape(B, S, Hkv * G, D)


def _row_positions(q_pos, G, rows):
    """(B, S) token positions -> (B, rows, 1) per-row column, sentinel
    padded (padded rows are sliced off by the wrappers)."""
    qp = jnp.repeat(q_pos.astype(jnp.int32), G, axis=1)
    return _pad_axis(qp, rows, 1, value=POS_SENTINEL)[..., None]


def _tile_mask(qp, kp, *, causal, window):
    """Attendability of a (rows, bk) score tile.  qp (rows, 1) / kp (1, bk).

    The sentinel test makes padded / scrubbed / trash slots unattendable even
    for idle decode lanes whose own q_pos is the sentinel (the oracle leaves
    those lanes attending trash; their outputs are ignored either way).
    """
    mask = kp != POS_SENTINEL
    if causal:
        mask &= kp <= qp
    if window is not None:
        mask &= kp > qp - window
    return mask


def _attend_heads(q_ref, k_at, v_at, mask, acc_ref, m_ref, l_ref, *,
                  scale, cap, k_scale=None, v_scale=None):
    """One online-softmax accumulation step for every KV head of a tile.

    q_ref (1, Hkv, rows, D); ``k_at(h)`` / ``v_at(h)`` give head h's (bk, D)
    K / V tile; mask (rows, bk) bool, shared by all heads; scratch acc (Hkv,
    rows, D) and lane-replicated m / l (Hkv, rows, _LANES).  ``k_scale`` /
    ``v_scale`` (Hkv, bk): int8 page scales.  Mirrors the oracle's scan
    body: on the first tile ``alpha`` is 0 and the update reduces to
    single-shot softmax; on tiles that leave the running max unchanged
    ``alpha == exp(0) == 1``.
    """
    for h in range(q_ref.shape[1]):
        q = q_ref[0, h].astype(jnp.float32) * scale
        s = jax.lax.dot_general(q, k_at(h).astype(jnp.float32),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if k_scale is not None:
            s = s * k_scale[h:h + 1]
        if cap is not None:
            s = cap * jnp.tanh(s / cap)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[h][:, :1]
        l_prev = l_ref[h][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        pv_in = p if v_scale is None else p * v_scale[h:h + 1]
        pv = jax.lax.dot_general(pv_in, v_at(h).astype(jnp.float32),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[h] = acc_ref[h] * alpha + pv
        m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        l_ref[h] = jnp.broadcast_to(
            l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape[1:])


def _init_scratch(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _finalize(o_ref, acc_ref, l_ref):
    for h in range(o_ref.shape[1]):
        o = acc_ref[h] / jnp.maximum(l_ref[h][:, :1], 1e-30)
        o_ref[0, h] = o.astype(o_ref.dtype)


def _scratch(Hkv, rows, D):
    return [pltpu.VMEM((Hkv, rows, D), jnp.float32),
            pltpu.VMEM((Hkv, rows, _LANES), jnp.float32),
            pltpu.VMEM((Hkv, rows, _LANES), jnp.float32)]


# ------------------------------------------------------------ flash prefill
def _flash_kernel(q_ref, k_ref, v_ref, qp_ref, kp_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, nk, causal, window, cap, scale):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _init_scratch(acc_ref, m_ref, l_ref)

    mask = _tile_mask(qp_ref[0], kp_ref[0], causal=causal, window=window)
    _attend_heads(q_ref, lambda h: k_ref[0, h], lambda h: v_ref[0, h], mask,
                  acc_ref, m_ref, l_ref, scale=scale, cap=cap)

    @pl.when(j == nk - 1)
    def _done():
        _finalize(o_ref, acc_ref, l_ref)


@functools.partial(jax.jit, static_argnames=("causal", "window", "attn_cap",
                                             "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, q_pos, kv_pos, causal=True, window=None,
                    attn_cap=None, bq=128, bk=128, interpret=None):
    """Tiled flash-attention forward (prefill / dense-cache decode).

    q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D); q_pos: (B, Sq) int32;
    kv_pos: (B, Skv) int32.  Returns (B, Sq, Hq, D) in q.dtype.  Pure
    function of positions: causal / sliding-window validity comes from
    comparing q_pos against kv_pos, so ring-buffer (rolled) caches and
    padded tails (position == sentinel) need no extra arguments.  On TPU a
    KV tile shorter than the (padded) sequence must be a multiple of 128
    (the lane width of its position row); the defaults satisfy this.
    """
    interpret = interpret_mode(interpret)
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    bq = min(bq, -(-Sq // _SUBLANES) * _SUBLANES)
    bk = min(bk, -(-Skv // _SUBLANES) * _SUBLANES)
    Sq_p = -(-Sq // bq) * bq
    # padded q rows carry the sentinel position and are sliced off; padded
    # kv slots carry the sentinel position too -> never attended
    q_ = _fold_heads(_pad_axis(q, bq, 1), Hkv)
    qp_ = _row_positions(q_pos, G, Sq_p * G)
    k_ = _pad_axis(k, bk, 1).transpose(0, 2, 1, 3)          # (B, Hkv, S, D)
    v_ = _pad_axis(v, bk, 1).transpose(0, 2, 1, 3)
    kp_ = _pad_axis(kv_pos.astype(jnp.int32), bk, 1,
                    value=POS_SENTINEL)[:, None, :]         # (B, 1, Skv)
    nq, nk = Sq_p // bq, k_.shape[2] // bk
    rq = bq * G
    out = pl.pallas_call(
        functools.partial(_flash_kernel, nk=nk, causal=causal, window=window,
                          cap=attn_cap, scale=scale),
        grid=(B, nq, nk),
        in_specs=[
            pl.BlockSpec((1, Hkv, rq, D), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, Hkv, bk, D), lambda b, i, j: (b, 0, j, 0)),
            pl.BlockSpec((1, Hkv, bk, D), lambda b, i, j: (b, 0, j, 0)),
            pl.BlockSpec((1, rq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, i, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, rq, D), lambda b, i, j: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q_.shape, q.dtype),
        scratch_shapes=_scratch(Hkv, rq, D),
        interpret=interpret,
        name="flash_attention",
    )(q_, k_, v_, qp_, kp_)
    return _unfold_heads(out, Sq, G)


# --------------------------------------------- paged prefill / decode
def live_pages(q_pos, *, page_size, n_blocks, window=None, xp=np):
    """The logical blocks ``[first, first + n)`` of each row's block table
    that a paged attention call walks: the pages any of the row's query
    positions can attend.

    q_pos: (B, k) int32, real positions left-aligned in ascending order and
    padded with ``POS_SENTINEL``.  ``last`` is the block of the row's
    highest real position -- causality hides every key past it -- and
    ``first`` the oldest block inside the sliding window of its lowest (0
    without a window).  A row with no real position walks nothing (``n ==
    0``).  Pure: ``xp=np`` counts on the host (``StepLoop._count``),
    ``xp=jnp`` feeds the kernel, so the two cannot disagree.  Returns
    (first, n), each (B,) int32.
    """
    real = q_pos != POS_SENTINEL
    last = xp.minimum(xp.max(xp.where(real, q_pos, -1), axis=1) // page_size,
                      n_blocks - 1)
    if window is None:
        first = xp.zeros_like(last)
    else:
        first = xp.clip((q_pos[:, 0] - (window - 1)) // page_size, 0,
                        n_blocks - 1)
    return first, xp.maximum(last - first + 1, 0)


def _paged_kernel(bt_ref, first_ref, n_ref, q_ref, qp_ref, *rest, ppb, ps,
                  window, cap, scale, quant):
    # ppb page refs per pool: K, V, positions[, K scales, V scales]
    n_pools = 5 if quant else 3
    pools = [rest[g * ppb:(g + 1) * ppb] for g in range(n_pools)]
    o_ref, acc_ref, m_ref, l_ref = rest[n_pools * ppb:]
    k_refs, v_refs, pos_refs = pools[:3]
    b = pl.program_id(0)
    i = pl.program_id(1)
    live = n_ref[b] - i * ppb          # pages of this block inside the walk

    @pl.when(i == 0)
    def _init():
        _init_scratch(acc_ref, m_ref, l_ref)

    @pl.when(live > 0)
    def _block():
        def cat(refs, h):               # ppb (ps, D) pages -> (ppb*ps, D) f32
            return jnp.concatenate(
                [r[0, h].astype(jnp.float32) for r in refs], axis=0)

        kp = jnp.concatenate([r[0] for r in pos_refs], axis=-1)  # (1, bk)
        mask = _tile_mask(qp_ref[0], kp, causal=True, window=window)
        # slots past the walk's last page (clipped onto it by the index_map,
        # or past the table) are unattendable
        slot = jax.lax.broadcasted_iota(jnp.int32, kp.shape, 1)
        mask &= slot < live * ps
        k_scale = v_scale = None
        if quant:
            k_scale, v_scale = (jnp.concatenate([r[0] for r in refs],
                                                axis=-1)  # (Hkv, bk)
                                for refs in pools[3:])
        _attend_heads(q_ref, functools.partial(cat, k_refs),
                      functools.partial(cat, v_refs), mask, acc_ref, m_ref,
                      l_ref, scale=scale, cap=cap, k_scale=k_scale,
                      v_scale=v_scale)

    @pl.when(i == pl.num_programs(1) - 1)
    def _done():
        _finalize(o_ref, acc_ref, l_ref)


def _paged_vmem_bytes(*, Hkv, rows, D, ps, ppb, q_dtype, kv_dtype, quant):
    """VMEM a paged attention call holds: the double-buffered q / out /
    q-position blocks and page blocks, the f32 scratch, and the f32 K / V
    blocks and (rows, bk) score tiles one head's step builds."""
    def tile(r, c, itemsize=4):            # a 2-d slab padded to (8, 128)
        return (-(-r // _SUBLANES) * _SUBLANES) * (-(-c // _LANES) * _LANES) \
            * itemsize
    bk = ppb * ps
    q_it, kv_it = np.dtype(q_dtype).itemsize, np.dtype(kv_dtype).itemsize
    blocks = 2 * Hkv * tile(rows, D, q_it) + tile(rows, 1)
    page = 2 * Hkv * tile(ps, D, kv_it) + tile(1, ps)
    if quant:
        page += 2 * tile(Hkv, ps)
    scratch = Hkv * (tile(rows, D) + 2 * tile(rows, _LANES))
    temps = 2 * tile(bk, D) + 4 * tile(rows, bk) + 2 * tile(rows, D)
    return 2 * (blocks + ppb * page) + scratch + temps


@functools.partial(jax.jit, static_argnames=("window", "attn_cap",
                                             "interpret"))
def paged_prefill_attention(q, k_pages, v_pages, pos_pages, block_tables, *,
                            q_pos, window=None, attn_cap=None,
                            k_scale_pages=None, v_scale_pages=None,
                            interpret=None):
    """Causal attention over the paged KV pool for q-tiles of k tokens.

    The block-table page walk generalized from single-token decode to the
    chunked-prefill q tile: each sequence contributes ``k`` query rows (a
    prompt chunk, a lone decode token, or sentinel padding) that all read KV
    through the same scalar-prefetched block-table row.

    q: (B, k, Hq, D); ``k_pages`` / ``v_pages``: (P, Hkv, page_size, D)
    head-major physical pool; ``pos_pages`` (P, page_size) int32;
    block_tables: (B, nb) int32; q_pos: (B, k) int32 per-row token
    positions, **left-aligned**: real tokens occupy columns ``0..c-1`` in
    ascending position order and padded columns carry ``POS_SENTINEL``.
    int8 pools pass ``k_scale_pages`` / ``v_scale_pages`` (P, Hkv,
    page_size) f32 and the kernel applies them in VMEM.  Returns (B, k, Hq,
    D) in q.dtype.

    The walk covers only each row's live pages ``[first, first + n)``
    (:func:`live_pages`), ``ppb = 128 // page_size`` pages (one lane width
    of slots) to a grid step.  Grid (B, cdiv(nb, ppb)): step ``i`` of
    sequence ``b`` DMAs the physical pages ``bt[b, first[b] + i*ppb + j]``,
    ``j < ppb`` -- every KV head of each, with its position row and int8
    scale rows -- through index_maps over the scalar-prefetched table, and
    attends them as one ``(rows, ppb*page_size)`` score tile.  Slots past
    the row's last live page are masked; steps past it repeat the last
    block's indices, so the pipeline fetches nothing and the kernel skips
    them.  ``first`` re-bases the walk past the blocks wholly below a
    sliding window, so out-of-window pages never leave HBM.  Causal masking
    against each row's own position handles chunk offsets: a chunk token
    attends earlier chunks' pages plus its own chunk's already-written
    slots, never its future.  A row with no real position walks nothing
    and returns exact zeros.
    """
    interpret = interpret_mode(interpret)
    B, k, Hq, D = q.shape
    P, Hkv, ps, _ = k_pages.shape
    nb = block_tables.shape[1]
    G = Hq // Hkv
    quant = k_pages.dtype == jnp.int8
    assert quant == (k_scale_pages is not None), \
        "int8 pools require scale pages (and f32/bf16 pools must not pass them)"
    scale = 1.0 / math.sqrt(D)
    qp = q_pos.reshape(B, k).astype(jnp.int32)
    first, n = live_pages(qp, page_size=ps, n_blocks=nb, window=window,
                          xp=jnp)
    ppb = min(max(1, _LANES // ps), nb)
    rows = -(-(k * G) // _SUBLANES) * _SUBLANES
    q_ = _pad_axis(_fold_heads(q, Hkv), _SUBLANES, 2)
    qp_ = _row_positions(qp, G, rows)

    def page(j, b, i, fr, nr):
        # logical block of page j of compute block i; steps past the walk
        # repeat its last block, so the pipeline re-fetches nothing
        i = jnp.minimum(i, jnp.maximum((nr[b] + ppb - 1) // ppb - 1, 0))
        return jnp.minimum(fr[b] + i * ppb + j,
                           fr[b] + jnp.maximum(nr[b] - 1, 0))

    def page_map(j):                                   # (P, Hkv, ps, D)
        return lambda b, i, bt, fr, nr: (bt[b, page(j, b, i, fr, nr)], 0, 0,
                                         0)

    def row_map(j):                           # (P, 1, ps) / (P, Hkv, ps)
        return lambda b, i, bt, fr, nr: (bt[b, page(j, b, i, fr, nr)], 0, 0)

    def q_map(b, i, bt, fr, nr):
        return (b, 0, 0, 0)

    def per_page(shape, index_map):
        return [pl.BlockSpec(shape, index_map(j)) for j in range(ppb)]

    def qp_map(b, i, bt, fr, nr):
        return (b, 0, 0)

    in_specs = ([pl.BlockSpec((1, Hkv, rows, D), q_map),
                 pl.BlockSpec((1, rows, 1), qp_map)]
                + per_page((1, Hkv, ps, D), page_map)
                + per_page((1, Hkv, ps, D), page_map)
                + per_page((1, 1, ps), row_map))
    operands = ([q_, qp_] + [k_pages] * ppb + [v_pages] * ppb
                + [pos_pages[:, None, :]] * ppb)
    if quant:
        in_specs += (per_page((1, Hkv, ps), row_map)
                     + per_page((1, Hkv, ps), row_map))
        operands += [k_scale_pages] * ppb + [v_scale_pages] * ppb

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, -(-nb // ppb)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hkv, rows, D), q_map),
        scratch_shapes=_scratch(Hkv, rows, D),
    )
    vmem = _paged_vmem_bytes(Hkv=Hkv, rows=rows, D=D, ps=ps, ppb=ppb,
                             q_dtype=q.dtype, kv_dtype=k_pages.dtype,
                             quant=quant)
    out = pl.pallas_call(
        functools.partial(_paged_kernel, ppb=ppb, ps=ps, window=window,
                          cap=attn_cap, scale=scale, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem + vmem // 4 + (4 << 20)),
        interpret=interpret,
        name="paged_prefill_attention",
    )(block_tables.astype(jnp.int32), first, n, *operands)
    return _unfold_heads(out, k, G)


def paged_decode_attention(q, k_pages, v_pages, pos_pages, block_tables, *,
                           q_pos, window=None, attn_cap=None,
                           k_scale_pages=None, v_scale_pages=None,
                           interpret=None):
    """Single-token decode over the paged pool: the ``k == 1`` q tile of
    :func:`paged_prefill_attention` (kept as the decode-path entry point).

    q: (B, 1, Hq, D); q_pos: (B, 1) or (B,) int32.  Returns (B, 1, Hq, D).
    """
    B = q.shape[0]
    return paged_prefill_attention(
        q, k_pages, v_pages, pos_pages, block_tables,
        q_pos=q_pos.reshape(B, 1), window=window, attn_cap=attn_cap,
        k_scale_pages=k_scale_pages, v_scale_pages=v_scale_pages,
        interpret=interpret)
