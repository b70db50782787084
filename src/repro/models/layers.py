"""Core LM layers: RMSNorm, RoPE, GQA attention, SwiGLU FFN,
capacity-based top-k MoE.  Pure functions over explicit parameter dicts.

Attention dispatches between two backends through an ``impl`` selector:

* ``impl="ref"`` (default) -- a running-logsumexp scan over KV chunks
  (flash-attention schedule in jnp) so prefill at 32k..512k sequence
  lengths never materializes an (Sq, Skv) score matrix.  This is the
  bit-accuracy oracle; the train path always uses it.
* ``impl="pallas"`` -- the fused kernels in ``kernels/attention.py``: a
  tiled flash forward for prefill/dense decode, and a block-table-aware
  paged decode kernel that streams KV pages into VMEM instead of running
  the dense ``paged_gather``.  The serving engine defaults to this path.

``attention`` / ``paged_attention`` are the dispatchers; ``attention_ref``
/ ``paged_attention_ref`` are the jnp implementations (kept public: tests
pin them as the oracle).  ``impl`` must be static under jit.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.pack import PackedWeight
from repro.quant.linear_quant import fake_quant_per_token

NEG_INF = float("-inf")


# --------------------------------------------------------------------- basics
def wcol(w):
    """Column-parallel weight at use: gather FSDP shards, keep TP shard.

    Under the "weight_gather" rules (launch/steps.py) this pins the gathered
    layout P(None, "model") so GSPMD gathers the (cheap) weight over "data"
    instead of resharding the (expensive) activations every matmul."""
    from repro.sharding.ctx import constrain
    return constrain(deq(w), "w_col")


def wrow(w):
    """Row-parallel weight at use: gathered layout P("model", None)."""
    from repro.sharding.ctx import constrain
    return constrain(deq(w), "w_row")


def deq(w):
    """Dequantize quantized-serving weights at use.

    Two stored layouts dispatch here:
      * {"q": int8, "s": scale} -- uniform int8 (quantize_params_int8);
      * kernels.pack.PackedWeight -- the bucketed sub-byte layout a searched
        mixed-QBN policy compiles to (apply_policy_packed): QBN <= 4 channels
        bit-packed along K, 5..8 int8, > 8 bf16.
    Full-precision leaves pass through untouched.  Everything here runs
    under the name scope ``dequant``, which XLA keeps in the metadata of
    the ops it compiles from it (metadata only), so a profiler trace can
    tell dequant time from matmul time.

    On TPU the unpack does *not* fuse into the consuming matmul.  In the
    compiled ``model_step`` (v5e, phi4-mini-3.8b widths, packed policy over
    all four buckets) every int2, int4 and int8 bucket of every packed
    weight is unpacked and scaled by a fusion of its own that writes its
    bf16 columns, and one concatenate per weight writes the whole bf16
    (K, N) weight, which the matmul then reads: per layer, every step.
    HBM weight traffic per step is therefore the stored bytes plus a bf16
    write and read of each weight, not the stored width.  Traced on one
    v5e chip (4 lanes of 256-token chunks), the ops of this scope take
    77 ms of a 744 ms step, 41% of it in the concatenates.
    kernels/quant_matmul.py and kernels/packed_matmul.py are
    explicit-tiling versions of the same contractions that read the
    stored width.
    """
    with jax.named_scope("dequant"):
        if isinstance(w, PackedWeight):
            return w.dequant()
        if isinstance(w, dict) and "q" in w:
            return w["q"].astype(w["s"].dtype) * w["s"]
        return w


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(dt)


def rope(x: jnp.ndarray, pos: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding.  x: (B, S, H, D); pos: (B, S) int32."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * freqs          # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def softcap(x: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def maybe_quant_act(x: jnp.ndarray, bits) -> jnp.ndarray:
    """Per-token activation fake-quant; bits None/static-0 disables.

    Row-wise dynamic scales (amax over the model dim) keep each token's
    quantization independent of its batch: a continuous-batching decode
    step quantizes a sequence's activation exactly as the batch-1 oracle
    would -- the invariant behind run()/generate() parity under a policy
    with activation QBNs (tests/test_paged_kv.py).
    """
    if bits is None:
        return x
    return fake_quant_per_token(x, bits)


# ------------------------------------------------------------------ attention
def _mask_scores(s, q_pos, kv_pos, *, causal, window, kv_valid_len):
    """s: (B, Hkv, G, Sq, Ck); q_pos (B,Sq); kv_pos (B,Ck)."""
    qp = q_pos[:, None, None, :, None]
    kp = kv_pos[:, None, None, None, :]
    mask = jnp.ones(s.shape, bool)
    if causal:
        mask &= kp <= qp
    if window is not None:
        mask &= kp > qp - window
    if kv_valid_len is not None:
        kv = kv_valid_len.reshape(-1, 1, 1, 1, 1)
        mask &= kp < kv
    return jnp.where(mask, s, NEG_INF)


ATTN_IMPLS = ("ref", "pallas")


def _check_impl(impl):
    impl = impl or "ref"
    if impl not in ATTN_IMPLS:
        raise ValueError(f"unknown attention impl {impl!r}; "
                         f"expected one of {ATTN_IMPLS}")
    return impl


def attention(q, k, v, *, q_pos, kv_pos, causal=True, window=None,
              attn_cap=None, kv_valid_len=None, chunk=1024, impl=None):
    """GQA attention dispatcher: ``impl="ref"`` (jnp oracle, default) or
    ``"pallas"`` (kernels/attention.flash_attention).

    q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D); q_pos: (B, Sq) int32;
    kv_pos: (B, Skv) int32.  Returns (B, Sq, Hq, D) in q.dtype.  ``impl``
    must be static under jit; ``kv_valid_len`` (ragged prefill batches)
    stays on the ref path -- the kernels express validity through positions
    alone.  ``chunk`` applies to the ref path only.
    """
    impl = _check_impl(impl)
    if impl == "pallas" and kv_valid_len is None:
        from repro.kernels.attention import flash_attention
        return flash_attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                               causal=causal, window=window,
                               attn_cap=attn_cap)
    return attention_ref(q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=causal,
                         window=window, attn_cap=attn_cap,
                         kv_valid_len=kv_valid_len, chunk=chunk)


def attention_ref(q, k, v, *, q_pos, kv_pos, causal=True, window=None,
                  attn_cap=None, kv_valid_len=None, chunk=1024):
    """GQA attention with a flash (running-softmax) scan over KV chunks.

    The pure-jnp oracle the Pallas kernels are property-tested against.
    """
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, Hkv, G, D)

    def score(kc, kvp):  # kc: (B, Ck, Hkv, D) -> (B, Hkv, G, Sq, Ck)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kc.astype(jnp.float32))
        s = softcap(s, attn_cap)
        return _mask_scores(s, q_pos, kvp, causal=causal, window=window,
                            kv_valid_len=kv_valid_len)

    if Skv <= chunk:
        s = score(k, kv_pos)
        m = jnp.max(s, axis=-1, keepdims=True)
        msafe = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.exp(s - msafe)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
        o = o / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2, 4)
        return o.reshape(B, Sq, Hq, D).astype(q.dtype)

    n_chunks = -(-Skv // chunk)
    pad = n_chunks * chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)),
                         constant_values=jnp.iinfo(jnp.int32).max)
    kcs = k.reshape(B, n_chunks, chunk, Hkv, D).transpose(1, 0, 2, 3, 4)
    vcs = v.reshape(B, n_chunks, chunk, Hkv, D).transpose(1, 0, 2, 3, 4)
    pcs = kv_pos.reshape(B, n_chunks, chunk).transpose(1, 0, 2)

    m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    o0 = jnp.zeros((B, Sq, Hkv, G, D), jnp.float32)

    def body(carry, xs):
        m, l, o = carry
        kc, vc, kvp = xs
        s = score(kc, kvp)                                   # (B,Hkv,G,Sq,Ck)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bqhgd", p, vc.astype(jnp.float32))
        o = o * alpha.transpose(0, 3, 1, 2)[..., None] + pv
        return (m_new, l, o), None

    (m, l, o), _ = jax.lax.scan(body, (m0, l0, o0), (kcs, vcs, pcs))
    o = o / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return o.reshape(B, Sq, Hq, D).astype(q.dtype)


# ----------------------------------------------------- paged-KV attention
def paged_gather(pages: jnp.ndarray, block_tables: jnp.ndarray) -> jnp.ndarray:
    """Gather per-sequence KV through block tables.

    pages: (P, page_size) position pool, or a head-major (P, Hkv, page_size,
    ...) K/V / scale pool; block_tables: (B, nb) int32 physical page ids
    (logical block order).  Returns (B, nb*page_size[, Hkv, ...]) -- each
    sequence's pages flattened back into logical position order, heads
    after positions like a dense cache.  Unmapped blocks point at the trash
    page (id 0); its slots carry sentinel positions, so the attention mask
    rejects them.
    """
    g = pages[block_tables]                      # (B, nb, [Hkv,] ps, ...)
    if g.ndim > 3:
        g = jnp.moveaxis(g, 2, 3)                # (B, nb, ps, Hkv, ...)
    return g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])


def paged_attention(q, k_pages, v_pages, pos_pages, block_tables, *, q_pos,
                    causal=True, window=None, attn_cap=None,
                    k_scale_pages=None, v_scale_pages=None, impl=None):
    """Attention over a paged KV pool: dispatcher (decode *and* chunks).

    q: (B, Sq, Hq, D) -- ``Sq == 1`` is the decode step, ``Sq > 1`` a
    prompt chunk whose K/V were already scattered into the pool this step;
    ``k_pages`` / ``v_pages``: (P, Hkv, page_size, D) head-major
    (``pos_pages`` (P, page_size) int32); block_tables: (B, nb); q_pos:
    (B, Sq) int32, real rows left-aligned and sentinel-padded.  int8 pools
    carry per-(head, slot) ``*_scale_pages`` (P, Hkv, page_size) f32.

    ``impl="ref"`` (default) gathers each sequence's pages into logical
    order and runs the standard masked flash attention; ``"pallas"``
    (kernels/attention.paged_prefill_attention) walks the block table
    in-kernel, streaming pages into VMEM with no dense gather.  Slots whose
    position is the sentinel (unwritten, scrubbed, or trash) mask to -inf
    exactly like the dense cache's convention on both paths, so the result
    matches dense-cache decode on the same written positions.
    """
    impl = _check_impl(impl)
    if impl == "pallas" and causal:
        from repro.kernels.attention import paged_prefill_attention
        return paged_prefill_attention(
            q, k_pages, v_pages, pos_pages, block_tables, q_pos=q_pos,
            window=window, attn_cap=attn_cap, k_scale_pages=k_scale_pages,
            v_scale_pages=v_scale_pages)
    return paged_attention_ref(
        q, k_pages, v_pages, pos_pages, block_tables, q_pos=q_pos,
        causal=causal, window=window, attn_cap=attn_cap,
        k_scale_pages=k_scale_pages, v_scale_pages=v_scale_pages)


def paged_attention_ref(q, k_pages, v_pages, pos_pages, block_tables, *,
                        q_pos, causal=True, window=None, attn_cap=None,
                        k_scale_pages=None, v_scale_pages=None):
    """jnp oracle for paged decode: dense gather + masked flash attention.

    The gather materializes each sequence's whole (nb*page_size) KV window
    -- the HBM round trip the Pallas kernel exists to avoid; int8 pools
    additionally dequantize the entire gathered window to f32 here.
    """
    k = paged_gather(k_pages, block_tables)
    v = paged_gather(v_pages, block_tables)
    kv_pos = paged_gather(pos_pages, block_tables)
    if k_scale_pages is not None:
        ks = paged_gather(k_scale_pages, block_tables)
        vs = paged_gather(v_scale_pages, block_tables)
        k = k.astype(jnp.float32) * ks[..., None]
        v = v.astype(jnp.float32) * vs[..., None]
    return attention_ref(q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=causal,
                         window=window, attn_cap=attn_cap, chunk=k.shape[1])


# ----------------------------------------------------------------------- FFN
def swiglu(x, p, act_bits=None):
    """p: {wg: (d, ff), wu: (d, ff), wd: (ff, d)}."""
    x = maybe_quant_act(x, act_bits)
    h = jax.nn.silu(x @ wcol(p["wg"])) * (x @ wcol(p["wu"]))
    return h @ wrow(p["wd"])


# ----------------------------------------------------------------------- MoE
def moe_ffn(x, p, *, n_experts, top_k, capacity_factor=1.25, act_bits=None,
            local_dispatch=False):
    """Capacity-based top-k MoE with scatter dispatch (no TxExC one-hot).

    x: (..., d).  p: {router: (d, E), wg/wu: (E, d, ff), wd: (E, ff, d)}.
    Tokens beyond an expert's capacity are dropped (standard Switch-style),
    contributing only their residual path.  capacity_factor <= 0 disables
    dropping (C = T; exact but unbalanced -- used by tiny smoke configs).

    local_dispatch=True (small-expert MoE under a mesh): split tokens into
    one group per data shard and vmap the dispatch over groups, with the
    group dim pinned to the DP axes -- every routing cumsum/scatter becomes
    shard-local, eliminating the cross-data all-reduce of the (E, C, d)
    dispatch buffer.  Pairs with DP-replicated (TP-sharded) expert weights
    (sharding/specs.py honors cfg.moe.local_dispatch), which is the right
    trade for small experts (EXPERIMENTS.md §Perf, granite hillclimb).
    """
    from repro.sharding.ctx import constrain, current_mesh
    mesh = current_mesh() if local_dispatch else None
    if mesh is not None:
        G = 1
        for a in ("pod", "data"):
            G *= mesh.shape.get(a, 1)
        T = 1
        for dim in x.shape[:-1]:
            T *= dim
        if G > 1 and T % G == 0:
            d = x.shape[-1]
            xg = constrain(x.reshape(G, T // G, d), "moe_group")

            def one_group(xl):
                return _moe_ffn_impl(
                    xl, p, n_experts=n_experts, top_k=top_k,
                    capacity_factor=capacity_factor, act_bits=act_bits)

            out, probs = jax.vmap(one_group)(xg)
            out = constrain(out, "moe_group")
            return out.reshape(x.shape), probs.reshape(T, -1)
    return _moe_ffn_impl(x, p, n_experts=n_experts, top_k=top_k,
                         capacity_factor=capacity_factor, act_bits=act_bits)


def _moe_ffn_impl(x, p, *, n_experts, top_k, capacity_factor, act_bits):
    orig_shape = x.shape
    d = x.shape[-1]
    xt = x.reshape(-1, d)
    T = xt.shape[0]
    E, K = n_experts, top_k
    wg, wu, wd = deq(p["wg"]), deq(p["wu"]), deq(p["wd"])
    E_phys = wg.shape[0]          # >= E when experts are padded for EP
    if capacity_factor <= 0:
        C = T
    else:
        C = min(T, max(8, int(math.ceil(T * K / E * capacity_factor))))

    # router matmul in model dtype (f32 softmax after): an f32 upcast of xt
    # here promotes the whole dispatch backward to f32, doubling the TP
    # all-reduce of the (E, C, d) buffer cotangent (§Perf, jamba hillclimb)
    logits = (xt @ deq(p["router"]).astype(xt.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                   # (T, E)
    gate_v, gate_i = jax.lax.top_k(probs, K)                  # (T, K)
    gate_v = gate_v / jnp.maximum(gate_v.sum(-1, keepdims=True), 1e-9)

    # Flatten (token, slot) pairs and compute position-in-expert by cumsum.
    eidx = gate_i.reshape(-1)                                 # (T*K,)
    onehot = jax.nn.one_hot(eidx, E_phys, dtype=jnp.int32)    # (T*K, E_phys)
    pos_all = jnp.cumsum(onehot, axis=0) - 1                  # (T*K, E_phys)
    pos = jnp.take_along_axis(pos_all, eidx[:, None], axis=1)[:, 0]
    keep = pos < C

    xq = maybe_quant_act(xt, act_bits)
    xrep = jnp.repeat(xq, K, axis=0)                          # (T*K, d)
    buf = jnp.zeros((E_phys, C, d), xt.dtype)
    buf = buf.at[eidx, jnp.clip(pos, 0, C - 1)].add(
        jnp.where(keep[:, None], xrep, 0))

    h = jnp.einsum("ecd,edf->ecf", buf, wg)
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    h = jax.nn.silu(h) * u
    out_buf = jnp.einsum("ecf,efd->ecd", h, wd)               # (E_phys, C, d)

    gathered = out_buf[eidx, jnp.clip(pos, 0, C - 1)]         # (T*K, d)
    gathered = jnp.where(keep[:, None], gathered, 0)
    weighted = gathered * gate_v.reshape(-1)[:, None].astype(gathered.dtype)
    out = weighted.reshape(T, K, d).sum(axis=1)
    return out.reshape(orig_shape), probs


def moe_aux_loss(probs, gate_i, n_experts):
    """Switch-style load-balance loss from router probs + top-1 assignment."""
    T = probs.shape[0]
    frac_tokens = jnp.mean(
        jax.nn.one_hot(gate_i[:, 0], n_experts, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    return n_experts * jnp.sum(frac_tokens * frac_probs)
