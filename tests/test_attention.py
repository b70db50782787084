"""Pallas attention kernels vs the jnp oracle.

Property tests (hypothesis) drive both kernels across GQA ratios, sliding
windows, softcaps, ragged page counts and mixed in-flight lengths, always
comparing against ``models.layers.attention_ref`` / ``paged_attention_ref``
-- the pure-jnp flash schedule that predates the kernels and stays their
oracle.  Tolerances are the documented f32 rounding (online-softmax
rescale, matmul accumulation order, int8 scale placement: a few ulps).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.attention import (flash_attention, paged_decode_attention,
                                     paged_prefill_attention)
from repro.models import layers
from repro.models.layers import (attention, attention_ref, paged_attention,
                                 paged_attention_ref)
from repro.models.transformer import POS_SENTINEL, _kv_quant

# documented f32-accumulation tolerance: online-softmax rescale rounding
TOL = dict(rtol=2e-4, atol=2e-5)


def _qkv(rng, B, Sq, Skv, Hkv, G, D):
    q = jnp.asarray(rng.normal(size=(B, Sq, Hkv * G, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Skv, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Skv, Hkv, D)), jnp.float32)
    q_pos = jnp.broadcast_to(
        jnp.arange(Skv - Sq, Skv, dtype=jnp.int32)[None], (B, Sq))
    kv_pos = jnp.broadcast_to(jnp.arange(Skv, dtype=jnp.int32)[None],
                              (B, Skv))
    return q, k, v, q_pos, kv_pos


# ------------------------------------------------------------ flash prefill
@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), Sq=st.integers(1, 12),
       Skv=st.integers(1, 40), hkv=st.sampled_from([1, 2]),
       g=st.sampled_from([1, 2, 4]), window=st.sampled_from([None, 5, 16]),
       cap=st.sampled_from([None, 30.0]))
def test_flash_kernel_matches_oracle(seed, Sq, Skv, hkv, g, window, cap):
    """Multi-tile flash kernel == jnp oracle across GQA ratios, windows,
    softcaps (small bq/bk force the online-softmax accumulation path)."""
    Sq = min(Sq, Skv)
    rng = np.random.default_rng(seed)
    q, k, v, q_pos, kv_pos = _qkv(rng, 2, Sq, Skv, hkv, g, 8)
    ref = attention_ref(q, k, v, q_pos=q_pos, kv_pos=kv_pos, window=window,
                        attn_cap=cap, chunk=10**9)
    got = flash_attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos, window=window,
                          attn_cap=cap, bq=8, bk=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)


def test_flash_kernel_single_tile_bitwise_and_noncausal():
    """One KV tile degenerates to the oracle's single-shot softmax, causal
    and non-causal (cross-attention) alike.  Not bit-equal: XLA's CPU
    backend rounds the kernel's 2-d tile matmul and the oracle's batched
    einsum differently (a few f32 ulps), so the documented TOL holds."""
    rng = np.random.default_rng(0)
    q, k, v, q_pos, kv_pos = _qkv(rng, 2, 12, 40, 2, 3, 16)
    for causal in (True, False):
        ref = attention_ref(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                            causal=causal, chunk=10**9)
        got = flash_attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                              causal=causal, bq=128, bk=128)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   err_msg=f"causal={causal}", **TOL)


def test_flash_kernel_ring_buffer_positions():
    """Ring (rolled) kv_pos order -- the dense local_attn decode layout --
    masks by position value, not storage index."""
    rng = np.random.default_rng(1)
    W, B, Hkv, G, D = 8, 2, 2, 2, 8
    k = jnp.asarray(rng.normal(size=(B, W, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, W, Hkv, D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, 1, Hkv * G, D)), jnp.float32)
    pos = jnp.asarray([[(i - 3) % W + 5 for i in range(W)]] * B, jnp.int32)
    q_pos = jnp.full((B, 1), 12, jnp.int32)
    ref = attention_ref(q, k, v, q_pos=q_pos, kv_pos=pos, window=W)
    got = flash_attention(q, k, v, q_pos=q_pos, kv_pos=pos, window=W, bk=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)


# --------------------------------------------------------------- dispatcher
def test_attention_dispatcher_impls_agree_and_validate():
    rng = np.random.default_rng(2)
    q, k, v, q_pos, kv_pos = _qkv(rng, 2, 6, 24, 2, 2, 8)
    ref = attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos, impl="ref")
    default = attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(default))
    pal = attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos, impl="pallas")
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref), **TOL)
    with pytest.raises(ValueError, match="impl"):
        attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos, impl="cuda")
    assert layers._check_impl(None) == "ref"


# ----------------------------------------------------------- paged decode
def _paged_pool(rng, lens, ps, Hkv, D, kv_bits=None, extra_blocks=1):
    """Build a pool + block tables for sequences of the given lengths.

    Returns (q, pools dict, block_tables, q_pos): sequence i has written
    positions 0..lens[i]-1 (q_pos = lens[i]-1 attends all of them);
    lens[i] == 0 marks an idle lane (all-trash table, sentinel q_pos).
    """
    B = len(lens)
    nb = max(-(-max(lens) // ps), 1) + extra_blocks   # ragged not-grown tail
    P = 1 + sum(-(-s // ps) for s in lens if s)
    kf = rng.normal(size=(P, Hkv, ps, D)).astype(np.float32)
    vf = rng.normal(size=(P, Hkv, ps, D)).astype(np.float32)
    pos = np.full((P, ps), POS_SENTINEL, np.int32)
    bt = np.zeros((B, nb), np.int32)
    nxt = 1
    for i, s in enumerate(lens):
        npages = -(-s // ps)
        bt[i, :npages] = range(nxt, nxt + npages)
        for p in range(s):
            pos[bt[i, p // ps], p % ps] = p
        nxt += npages
    q = jnp.asarray(rng.normal(size=(B, 1, Hkv * 2, D)), jnp.float32)
    q_pos = jnp.asarray([[s - 1 if s else POS_SENTINEL] for s in lens],
                        jnp.int32)
    pools = {"k": jnp.asarray(kf), "v": jnp.asarray(vf),
             "pos": jnp.asarray(pos), "k_s": None, "v_s": None}
    if kv_bits == 8:
        kq, ks = _kv_quant(pools["k"])
        vq, vs = _kv_quant(pools["v"])
        pools = {"k": kq, "v": vq, "pos": pools["pos"], "k_s": ks, "v_s": vs}
    return q, pools, jnp.asarray(bt), q_pos


def _run_paged(q, pools, bt, q_pos, impl, **kw):
    return paged_attention(q, pools["k"], pools["v"], pools["pos"], bt,
                           q_pos=q_pos, k_scale_pages=pools["k_s"],
                           v_scale_pages=pools["v_s"], impl=impl, **kw)


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), ps=st.sampled_from([4, 8]),
       window=st.sampled_from([None, 6]), cap=st.sampled_from([None, 30.0]),
       lens=st.lists(st.integers(0, 25), min_size=1, max_size=5))
def test_paged_kernel_matches_oracle(seed, ps, window, cap, lens):
    """Block-table walk == dense gather + oracle, across ragged page
    counts, mixed in-flight lengths, idle lanes, windows and softcaps."""
    if not any(lens):
        lens = lens + [3]
    rng = np.random.default_rng(seed)
    q, pools, bt, q_pos = _paged_pool(rng, lens, ps, Hkv=2, D=8)
    ref = _run_paged(q, pools, bt, q_pos, "ref", window=window, attn_cap=cap)
    got = _run_paged(q, pools, bt, q_pos, "pallas", window=window,
                     attn_cap=cap)
    active = [i for i, s in enumerate(lens) if s]
    np.testing.assert_allclose(np.asarray(got)[active],
                               np.asarray(ref)[active], **TOL)
    # idle lanes: every slot masks -> exact zeros (the oracle leaves them
    # attending trash; the scheduler ignores both)
    idle = [i for i, s in enumerate(lens) if not s]
    assert np.all(np.asarray(got)[idle] == 0.0)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), window=st.sampled_from([None, 6]))
def test_paged_kernel_int8_pages_match_oracle(seed, window):
    """int8 pools: in-VMEM dequant == gather-then-dequant oracle."""
    rng = np.random.default_rng(seed)
    q, pools, bt, q_pos = _paged_pool(rng, [10, 3, 17], 4, Hkv=2, D=8,
                                      kv_bits=8)
    ref = _run_paged(q, pools, bt, q_pos, "ref", window=window)
    got = _run_paged(q, pools, bt, q_pos, "pallas", window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)


def test_paged_kernel_requires_scales_iff_int8():
    rng = np.random.default_rng(3)
    q, pools, bt, q_pos = _paged_pool(rng, [5], 4, Hkv=2, D=8, kv_bits=8)
    with pytest.raises(AssertionError, match="scale"):
        paged_decode_attention(q, pools["k"], pools["v"], pools["pos"], bt,
                               q_pos=q_pos)


def test_paged_kernel_window_skips_leading_blocks():
    """With a sliding window, the walk re-bases at the first in-window
    block -- the result still matches the oracle even when most of the
    sequence's pages are out of window."""
    rng = np.random.default_rng(4)
    q, pools, bt, q_pos = _paged_pool(rng, [24], 4, Hkv=2, D=8)
    ref = _run_paged(q, pools, bt, q_pos, "ref", window=5)
    got = _run_paged(q, pools, bt, q_pos, "pallas", window=5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)


# ------------------------------------------------- paged prefill (q tiles)
def _paged_chunk_pool(rng, lens, k, ps, Hkv, G, D, kv_bits=None):
    """Pool + q tiles for the chunked-prefill layout.

    Sequence i has ``lens[i]`` written positions; its q tile is the *last*
    ``c = min(k, lens[i])`` of them (the chunk just scattered into the pool,
    mirroring model_step's write-then-attend order), left-aligned with
    sentinel padding -- so chunk offsets, ragged page counts and idle lanes
    (lens[i] == 0) all appear.  Returns (q (B,k,Hq,D), pools, bt, q_pos).
    """
    B = len(lens)
    nb = max(-(-max(lens) // ps), 1) + 1
    P = 1 + sum(-(-s // ps) for s in lens if s)
    kf = rng.normal(size=(P, Hkv, ps, D)).astype(np.float32)
    vf = rng.normal(size=(P, Hkv, ps, D)).astype(np.float32)
    pos = np.full((P, ps), POS_SENTINEL, np.int32)
    bt = np.zeros((B, nb), np.int32)
    q_pos = np.full((B, k), POS_SENTINEL, np.int32)
    nxt = 1
    for i, s in enumerate(lens):
        npages = -(-s // ps)
        bt[i, :npages] = range(nxt, nxt + npages)
        for p in range(s):
            pos[bt[i, p // ps], p % ps] = p
        c = min(k, s)
        q_pos[i, :c] = range(s - c, s)
        nxt += npages
    q = jnp.asarray(rng.normal(size=(B, k, Hkv * G, D)), jnp.float32)
    pools = {"k": jnp.asarray(kf), "v": jnp.asarray(vf),
             "pos": jnp.asarray(pos), "k_s": None, "v_s": None}
    if kv_bits == 8:
        kq, ks = _kv_quant(pools["k"])
        vq, vs = _kv_quant(pools["v"])
        pools = {"k": kq, "v": vq, "pos": pools["pos"], "k_s": ks, "v_s": vs}
    return q, pools, jnp.asarray(bt), jnp.asarray(q_pos)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), ps=st.sampled_from([4, 8]),
       k=st.sampled_from([2, 3, 5, 8]), hkv=st.sampled_from([1, 2]),
       g=st.sampled_from([1, 2, 4]), window=st.sampled_from([None, 6]),
       cap=st.sampled_from([None, 30.0]),
       lens=st.lists(st.integers(0, 25), min_size=1, max_size=4))
def test_paged_prefill_kernel_matches_oracle(seed, ps, k, hkv, g, window,
                                             cap, lens):
    """Acceptance: the q-tile block-table walk == dense gather + oracle
    across chunk sizes x windows x GQA ratios x ragged page counts x chunk
    offsets (tiles mid-sequence), softcaps and idle lanes."""
    if not any(lens):
        lens = lens + [3]
    rng = np.random.default_rng(seed)
    q, pools, bt, q_pos = _paged_chunk_pool(rng, lens, k, ps, hkv, g, 8)
    ref = np.asarray(_run_paged(q, pools, bt, q_pos, "ref", window=window,
                                attn_cap=cap))
    got = np.asarray(_run_paged(q, pools, bt, q_pos, "pallas", window=window,
                                attn_cap=cap))
    # compare the real (left-aligned) columns only: sentinel-padded columns
    # are never read by the scheduler, and the jnp oracle's mask has no
    # sentinel-q test (a sentinel q row attends everything under global
    # attention) while the kernel masks them -- a deliberate difference on
    # dead lanes
    for i, s in enumerate(lens):
        c = min(k, s)
        np.testing.assert_allclose(got[i, :c], ref[i, :c], err_msg=f"row {i}",
                                   **TOL)
    # idle rows (all-trash tables, all slots sentinel) produce exact zeros
    idle = [i for i, s in enumerate(lens) if not s]
    assert np.all(got[idle] == 0.0)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), window=st.sampled_from([None, 6]),
       k=st.sampled_from([2, 4]))
def test_paged_prefill_kernel_int8_pages_match_oracle(seed, window, k):
    """int8 pools under q tiles: in-VMEM dequant == gather-then-dequant."""
    rng = np.random.default_rng(seed)
    lens = [10, 3, 17]
    q, pools, bt, q_pos = _paged_chunk_pool(rng, lens, k, 4, 2, 2, 8,
                                            kv_bits=8)
    ref = np.asarray(_run_paged(q, pools, bt, q_pos, "ref", window=window))
    got = np.asarray(_run_paged(q, pools, bt, q_pos, "pallas", window=window))
    for i, s in enumerate(lens):           # real columns (see above)
        np.testing.assert_allclose(got[i, :min(k, s)], ref[i, :min(k, s)],
                                   err_msg=f"row {i}", **TOL)


# (lens, k, window, cap, pool): page_size 4 -> 32 pages (128 slots) to a
# compute block; the longest lane (300 positions, 75 pages) makes nb 76,
# not a multiple of 32, so lanes walk one, two or three blocks
LONG_WALKS = {
    "decode-int8": ([150, 0, 50, 300], 1, None, None, "int8"),
    "decode-bf16-window-softcap": ([300, 93, 0], 1, 70, 30.0, "bf16"),
    "chunk-f32": ([300, 129, 0, 7], 5, None, None, "f32"),
    "chunk-bf16-window-softcap": ([250, 0, 20, 300], 6, 70, 30.0, "bf16"),
    "chunk-int8-window": ([250, 300, 40, 0], 8, 70, None, "int8"),
}


@pytest.mark.parametrize("case", list(LONG_WALKS), ids=list(LONG_WALKS))
def test_paged_kernel_multi_block_walk_matches_oracle(case):
    """Walks of several 32-page compute blocks == dense gather + oracle:
    contexts ending mid-block and shorter than one block, a last block cut
    by the table's end, windows whose first live page falls mid-block,
    softcap, f32 / bf16 / int8 pools, decode and chunk tiles.  Idle lanes
    walk nothing and return exact zeros."""
    lens, k, window, cap, pool = LONG_WALKS[case]
    rng = np.random.default_rng(sorted(LONG_WALKS).index(case))
    q, pools, bt, q_pos = _paged_chunk_pool(
        rng, lens, k, 4, 2, 2, 8, kv_bits=8 if pool == "int8" else None)
    if pool == "bf16":
        pools = dict(pools, k=pools["k"].astype(jnp.bfloat16),
                     v=pools["v"].astype(jnp.bfloat16))
    nb = bt.shape[1]
    assert nb > 32 and nb % 32
    ref = np.asarray(_run_paged(q, pools, bt, q_pos, "ref", window=window,
                                attn_cap=cap))
    got = np.asarray(_run_paged(q, pools, bt, q_pos, "pallas",
                                window=window, attn_cap=cap))
    for i, s in enumerate(lens):           # real columns (see above)
        np.testing.assert_allclose(got[i, :min(k, s)], ref[i, :min(k, s)],
                                   err_msg=f"row {i}", **TOL)
    idle = [i for i, s in enumerate(lens) if not s]
    assert idle and np.all(got[idle] == 0.0)


def test_paged_prefill_single_page_single_tile_bitwise():
    """One page and one q tile degenerate to the oracle's single-shot
    softmax: bit equality, like the flash kernel's single-tile case."""
    rng = np.random.default_rng(7)
    q, pools, bt, q_pos = _paged_chunk_pool(rng, [4], 3, 8, 2, 2, 8)
    ref = _run_paged(q, pools, bt, q_pos, "ref")
    got = _run_paged(q, pools, bt, q_pos, "pallas")
    assert bool(jnp.all(got == ref))


def test_paged_decode_is_the_k1_tile():
    """The decode entry point is exactly the k == 1 q tile of the prefill
    kernel (same kernel, same numerics)."""
    rng = np.random.default_rng(8)
    q, pools, bt, q_pos = _paged_pool(rng, [9, 4], 4, Hkv=2, D=8)
    dec = paged_decode_attention(q, pools["k"], pools["v"], pools["pos"], bt,
                                 q_pos=q_pos)
    pre = paged_prefill_attention(q, pools["k"], pools["v"], pools["pos"],
                                  bt, q_pos=q_pos.reshape(-1, 1))
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(pre))
