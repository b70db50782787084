"""Config-driven decoder LM covering all assigned architecture families.

One implementation assembles: GQA attention (RoPE, sliding window, softcap),
SwiGLU / MoE FFNs, Mamba2 (SSD) blocks, and cross-attention (VLM) blocks from
an :class:`LMConfig` periodic pattern.  The stack lowers as ``lax.scan`` over
pattern repeats (stacked parameters, leading axis n_repeat), so HLO size is
O(pattern period), not O(depth).

Entry points: ``apply`` (full-sequence train forward), ``prefill`` (forward +
cache fill, last-token logits), ``decode_step`` (single token with cache),
``decode_step_paged`` (paged single token), and ``model_step`` -- the
serving engine's unified token-budget step, where every row is a prompt
chunk or a decode token written straight into block-table pages.
Kernel-wise quantization hooks: weights are fake-quantized outside the forward
via ``quant.apply_policy_to_params``; activations via ``act_bits``, one scalar
per (repeat, pattern-position) block.

KV-cache convention: unwritten slots carry position ``POS_SENTINEL`` (int32
max) so the causal mask ``kv_pos <= q_pos`` rejects them without a separate
validity length.  ``local_attn`` blocks use a ring buffer of size ``window``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import ssm as ssm_mod
from repro.models.api import BlockDef, LMConfig
from repro.models.layers import (attention, deq, maybe_quant_act, moe_ffn,
                                 paged_attention, rmsnorm, rope, softcap,
                                 swiglu, wcol, wrow)
from repro.quant.policy import LayerInfo, QuantizableGraph
from repro.sharding.ctx import constrain

POS_SENTINEL = np.iinfo(np.int32).max
# physical page 0 of every paged pool is the never-allocated trash page
# (serve/paged_kv.py re-exports this and owns the lifecycle invariants;
# defined here, like POS_SENTINEL, because the paged write path below must
# route sentinel lanes to it without importing the serve layer)
TRASH_PAGE = 0


def _lin_init(key, fan_in, shape, dtype):
    return (jax.random.normal(key, shape) / np.sqrt(fan_in)).astype(dtype)


# ----------------------------------------------------- quantized KV caching
def _kv_quant(x):
    """(B, S, Hkv, hd) -> (int8 values, f32 scale (B, S, Hkv))."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    s = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s.astype(jnp.float32)


def _kv_deq(cache, key):
    kq = cache[key]
    if kq.dtype == jnp.int8:
        return kq.astype(jnp.float32) * cache[key + "_s"][..., None]
    return kq


def _kv_write(cache, k, v, pos, slot):
    """Write (k, v, pos) into the cache window starting at `slot`,
    quantizing per (position, head) when the cache stores int8."""
    out = dict(cache)
    for key, val in (("k", k), ("v", v)):
        if cache[key].dtype == jnp.int8:
            q, s = _kv_quant(val)
            out[key] = jax.lax.dynamic_update_slice(cache[key], q,
                                                    (0, slot, 0, 0))
            out[key + "_s"] = jax.lax.dynamic_update_slice(
                cache[key + "_s"], s, (0, slot, 0))
        else:
            out[key] = jax.lax.dynamic_update_slice(
                cache[key], val.astype(cache[key].dtype), (0, slot, 0, 0))
    out["pos"] = jax.lax.dynamic_update_slice(cache["pos"],
                                              pos.astype(jnp.int32),
                                              (0, slot))
    return out


def _kv_store_full(cache, k, v):
    """Cross-attention memory: overwrite the whole (fixed-length) cache."""
    out = dict(cache)
    for key, val in (("k", k), ("v", v)):
        if cache[key].dtype == jnp.int8:
            q, s = _kv_quant(val)
            out[key], out[key + "_s"] = q, s
        else:
            out[key] = val.astype(cache[key].dtype)
    return out


class LM:
    """Stateless model object: config + pure init/apply functions."""

    def __init__(self, cfg: LMConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------ init
    def _init_block(self, rng, bdef: BlockDef, dtype):
        cfg = self.cfg
        d, hd = cfg.d_model, cfg.hdim
        Hq, Hkv = cfg.n_heads, cfg.n_kv_heads
        ks = iter(jax.random.split(rng, 16))
        p: Dict[str, Any] = {"norm": jnp.zeros((d,), dtype)}
        if bdef.kind in ("attn", "local_attn", "cross_attn"):
            p["wq"] = _lin_init(next(ks), d, (d, Hq * hd), dtype)
            p["wk"] = _lin_init(next(ks), d, (d, Hkv * hd), dtype)
            p["wv"] = _lin_init(next(ks), d, (d, Hkv * hd), dtype)
            p["wo"] = _lin_init(next(ks), Hq * hd, (Hq * hd, d), dtype)
        elif bdef.kind == "mamba":
            p["mamba"] = ssm_mod.init_mamba_params(next(ks), d, cfg.ssm, dtype)
        else:
            raise ValueError(bdef.kind)
        if bdef.has_ffn:
            p["ffn_norm"] = jnp.zeros((d,), dtype)
            if bdef.use_moe:
                m = cfg.moe
                ep = m.n_experts_phys
                p["router"] = _lin_init(next(ks), d, (d, m.n_experts), dtype)
                p["wg"] = _lin_init(next(ks), d, (ep, d, m.d_ff), dtype)
                p["wu"] = _lin_init(next(ks), d, (ep, d, m.d_ff), dtype)
                p["wd"] = _lin_init(next(ks), m.d_ff,
                                    (ep, m.d_ff, d), dtype)
            else:
                p["wg"] = _lin_init(next(ks), d, (d, cfg.d_ff), dtype)
                p["wu"] = _lin_init(next(ks), d, (d, cfg.d_ff), dtype)
                p["wd"] = _lin_init(next(ks), cfg.d_ff, (cfg.d_ff, d), dtype)
        return p

    def init(self, rng, dtype=jnp.float32):
        cfg = self.cfg
        keys = jax.random.split(rng, len(cfg.pattern) + 2)
        blocks = []
        for p_idx, bdef in enumerate(cfg.pattern):
            reps = jax.random.split(keys[p_idx], cfg.n_repeat)
            stacked = jax.vmap(
                lambda k, b=bdef, dt=dtype: self._init_block(k, b, dt))(reps)
            blocks.append(stacked)
        params = {
            "blocks": tuple(blocks),
            "final_norm": jnp.zeros((cfg.d_model,), dtype),
            "unembed": _lin_init(keys[-1], cfg.d_model,
                                 (cfg.d_model, cfg.vocab_padded), dtype),
        }
        if cfg.frontend != "audio_stub":
            params["embed"] = (jax.random.normal(
                keys[-2], (cfg.vocab_padded, cfg.d_model)) /
                np.sqrt(cfg.d_model)).astype(dtype)
        return params

    # ---------------------------------------------------------------- blocks
    def _attn_block(self, bp, bdef, x, *, q_pos, mode, img_embeds=None,
                    cache=None, write_pos=None, act_bits=None,
                    block_tables=None, attn_impl=None):
        """Self- or cross-attention + residual.  Returns (x, new_cache).

        block_tables (decode only): (B, nb) int32 physical page ids -- the
        cache entry is then a paged pool (P, Hkv, page_size, hd) shared by
        the batch, written through the table and attended per sequence
        (``write_pos`` is per-sequence (B,) in that mode).  An int8 pool
        (``init_paged_cache(kv_bits=8)``) quantizes the write and carries
        per-(slot, head) scale pages.  ``attn_impl`` selects the attention
        backend (layers.ATTN_IMPLS; None -> "ref"); it must be static."""
        cfg = self.cfg
        B, S, _ = x.shape
        Hq, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hdim
        h = rmsnorm(x, bp["norm"], cfg.norm_eps)
        h = maybe_quant_act(h, act_bits)
        q = (h @ wcol(bp["wq"])).reshape(B, S, Hq, hd)
        new_cache = cache

        if bdef.kind == "cross_attn":
            causal, window = False, None
            if mode == "decode":
                k, v = _kv_deq(cache, "k"), _kv_deq(cache, "v")
            else:
                Si = img_embeds.shape[1]
                k = (img_embeds @ wcol(bp["wk"])).reshape(B, Si, Hkv, hd)
                v = (img_embeds @ wcol(bp["wv"])).reshape(B, Si, Hkv, hd)
                if cache is not None:
                    new_cache = _kv_store_full(cache, k, v)
            kv_pos = jnp.zeros((B, k.shape[1]), jnp.int32)
        else:
            causal = True
            window = cfg.window if bdef.kind == "local_attn" else None
            k = (h @ wcol(bp["wk"])).reshape(B, S, Hkv, hd)
            v = (h @ wcol(bp["wv"])).reshape(B, S, Hkv, hd)
            q = rope(q, q_pos, cfg.rope_theta)
            k = rope(k, q_pos, cfg.rope_theta)
            kv_pos = q_pos
            if cache is not None:
                if block_tables is not None:   # paged write+attend (S >= 1:
                    # one decode token or a k-token prompt chunk per row)
                    ps = cache["k"].shape[-2]
                    nb = block_tables.shape[1]
                    wp = write_pos if write_pos.ndim == 2 \
                        else write_pos[:, None]            # (B, S)
                    # sentinel lanes (idle decode slots, chunk padding)
                    # route to the trash page *explicitly*: an active row's
                    # clipped block index would land in one of its own real
                    # pages and corrupt a live KV slot
                    blk = jnp.minimum((wp // ps).astype(jnp.int32), nb - 1)
                    phys = jnp.take_along_axis(block_tables, blk, axis=1)
                    phys = jnp.where(wp == POS_SENTINEL, TRASH_PAGE, phys)
                    fp = phys.reshape(-1)                  # flat (B*S,)
                    fs = (wp % ps).reshape(-1)
                    # head-major pool (P, Hkv, ps, ...): [fp, :, fs] picks
                    # one (Hkv, ...) slot per written token
                    new_cache = dict(cache)
                    if cache["k"].dtype == jnp.int8:   # quantized page write
                        for key, val in (("k", k), ("v", v)):
                            qv, sv = _kv_quant(val)
                            new_cache[key] = cache[key].at[fp, :, fs].set(
                                qv.reshape((-1,) + qv.shape[2:]))
                            new_cache[key + "_s"] = \
                                cache[key + "_s"].at[fp, :, fs].set(
                                    sv.reshape((-1,) + sv.shape[2:]))
                    else:
                        for key, val in (("k", k), ("v", v)):
                            new_cache[key] = cache[key].at[fp, :, fs].set(
                                val.reshape((-1,) + val.shape[2:])
                                .astype(cache[key].dtype))
                    new_cache["pos"] = cache["pos"].at[fp, fs].set(
                        wp.reshape(-1).astype(jnp.int32))
                    out = paged_attention(
                        q, new_cache["k"], new_cache["v"], new_cache["pos"],
                        block_tables, q_pos=q_pos, causal=causal,
                        window=window, attn_cap=cfg.attn_softcap,
                        k_scale_pages=new_cache.get("k_s"),
                        v_scale_pages=new_cache.get("v_s"), impl=attn_impl)
                    x = x + out.reshape(B, S, Hq * hd) @ wrow(bp["wo"])
                    return x, new_cache
                W = cache["k"].shape[1]
                if mode == "decode":
                    slot = write_pos % W if bdef.kind == "local_attn" \
                        else write_pos
                    new_cache = _kv_write(cache, k, v, q_pos, slot)
                    k = _kv_deq(new_cache, "k")
                    v = _kv_deq(new_cache, "v")
                    kv_pos = new_cache["pos"]
                else:  # prefill: write last W positions, ring-aligned
                    kw, vw, pw = k, v, q_pos
                    if W < S:
                        # keep positions S-W..S-1, rolled so position p sits
                        # at its ring slot p % W -- decode's overwrite at
                        # write_pos % W then evicts exactly the oldest
                        # position (evicting an arbitrary one would drop a
                        # still-in-window entry, diverging from the paged
                        # and full-forward paths)
                        sh = (S - W) % W
                        kw = jnp.roll(k[:, -W:], sh, axis=1)
                        vw = jnp.roll(v[:, -W:], sh, axis=1)
                        pw = jnp.roll(q_pos[:, -W:], sh, axis=1)
                    new_cache = _kv_write(cache, kw, vw, pw, 0)
                    if cache["k"].dtype == jnp.int8:
                        # serve-consistent numerics: prompt tokens attend
                        # the int8 round trip of the in-flight K/V -- the
                        # exact values decode reads back from the cache and
                        # the chunked paged path reads from int8 pages
                        # (per-position scales, so the round trip covers
                        # even ring-evicted positions identically)
                        kq, ks = _kv_quant(k)
                        k = kq.astype(jnp.float32) * ks[..., None]
                        vq, vs = _kv_quant(v)
                        v = vq.astype(jnp.float32) * vs[..., None]
                    elif cache["k"].dtype != k.dtype:
                        # same contract for narrow fp caches (bf16): attend
                        # the cache-dtype round trip the chunked paged path
                        # reads back, keeping run() == generate() parity
                        # independent of cache_dtype
                        k = k.astype(cache["k"].dtype).astype(k.dtype)
                        v = v.astype(cache["v"].dtype).astype(v.dtype)
        chunk = k.shape[1] if S == 1 else 1024
        out = attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=causal,
                        window=window, attn_cap=cfg.attn_softcap, chunk=chunk,
                        impl=attn_impl)
        x = x + out.reshape(B, S, Hq * hd) @ wrow(bp["wo"])
        return x, new_cache

    def _ffn(self, bp, bdef, x, act_bits=None):
        cfg = self.cfg
        h = rmsnorm(x, bp["ffn_norm"], cfg.norm_eps)
        if bdef.use_moe:
            m = cfg.moe
            out, probs = moe_ffn(h, bp, n_experts=m.n_experts, top_k=m.top_k,
                                 capacity_factor=m.capacity_factor,
                                 act_bits=act_bits,
                                 local_dispatch=m.local_dispatch)
            frac = jnp.mean(probs, axis=0)
            aux = m.n_experts * jnp.sum(frac * frac)
            return x + out, aux
        return x + swiglu(h, bp, act_bits=act_bits), jnp.float32(0.0)

    def _apply_block(self, bp, bdef: BlockDef, x, *, q_pos, mode,
                     img_embeds=None, cache=None, write_pos=None,
                     act_bits=None, block_tables=None, attn_impl=None):
        if bdef.kind == "mamba":
            h = rmsnorm(x, bp["norm"], self.cfg.norm_eps)
            h = maybe_quant_act(h, act_bits)
            if mode == "decode":
                out, mcache = ssm_mod.mamba_decode_step(
                    bp["mamba"], h, cache, self.cfg.ssm, self.cfg.d_model)
            else:
                out, mcache = ssm_mod.mamba_forward(
                    bp["mamba"], h, self.cfg.ssm, self.cfg.d_model)
                if cache is not None:
                    mcache = jax.tree.map(lambda a, c: a.astype(c.dtype),
                                          mcache, cache)
            x = x + out
            new_cache = mcache
        else:
            x, new_cache = self._attn_block(
                bp, bdef, x, q_pos=q_pos, mode=mode, img_embeds=img_embeds,
                cache=cache, write_pos=write_pos, act_bits=act_bits,
                block_tables=None if bdef.kind == "cross_attn"
                else block_tables, attn_impl=attn_impl)
        aux = jnp.float32(0.0)
        if bdef.has_ffn:
            x, aux = self._ffn(bp, bdef, x, act_bits=act_bits)
        return x, new_cache, aux

    # --------------------------------------------------------------- helpers
    def _embed_tokens(self, params, tokens):
        emb = params["embed"]
        if isinstance(emb, dict):          # int8 rows + per-row scale
            return jnp.take(emb["q"], tokens, axis=0).astype(
                emb["s"].dtype) * jnp.take(emb["s"], tokens, axis=0)
        return jnp.take(emb, tokens, axis=0)

    def _embed(self, params, batch):
        if self.cfg.frontend == "audio_stub":
            x = batch["embeds"]
        else:
            x = self._embed_tokens(params, batch["tokens"])
        return constrain(x, "hidden")

    def logits_of(self, params, x):
        cfg = self.cfg
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        lg = x @ wcol(params["unembed"])
        lg = constrain(lg, "logits")
        lg = softcap(lg, cfg.logit_softcap)
        if cfg.vocab_padded != cfg.vocab:   # mask padded vocab entries
            valid = jnp.arange(cfg.vocab_padded) < cfg.vocab
            lg = jnp.where(valid, lg, jnp.asarray(-1e30, lg.dtype))
        return lg

    # ------------------------------------------------- int8 serving weights
    def quantize_params_int8(self, params):
        """Deployment transform: every matmul weight -> {"q": int8, "s"}.

        Scales are per output channel (last axis), reducing over the
        contraction axis; embedding rows get per-row scales.  Norms, biases
        and scalar leaves stay full precision.  The forward dequantizes at
        use (layers.deq), which fuses into the consuming matmul on TPU --
        HBM weight traffic drops to 1 byte/element.
        """
        MATMUL_LEAVES = {"wq", "wk", "wv", "wo", "wg", "wu", "wd", "router",
                         "w_xz", "w_bc", "w_dt", "w_out", "embed", "unembed"}

        def one(path, w):
            name = str(path[-1])
            if name not in MATMUL_LEAVES or w.ndim < 2 or \
                    w.dtype == jnp.int8:
                return w
            if name == "embed":
                red = (1,)                         # per-row (vocab) scale
            else:
                red = (w.ndim - 2,)                # over contraction axis
            amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=red,
                           keepdims=True)
            s = jnp.where(amax > 0, amax / 127.0, 1.0)
            q = jnp.clip(jnp.round(w.astype(jnp.float32) / s), -127,
                         127).astype(jnp.int8)
            return {"q": q, "s": s.astype(jnp.float32)}

        flat = jax.tree_util.tree_map_with_path(
            lambda p, w: one([getattr(k, "key", getattr(k, "idx", "?"))
                              for k in p], w), params)
        return flat

    # ---------------------------------------------------------------- train
    def apply(self, params, batch, act_bits: Optional[jnp.ndarray] = None,
              remat: bool = False):
        """Full-sequence forward.  Returns (logits, aux_loss).

        act_bits: optional (n_repeat, len(pattern)) activation QBN array.
        remat: rematerialize each pattern repeat in the backward pass
        (activation memory O(1) in depth; standard at 70B+ scale).
        """
        cfg = self.cfg
        x = self._embed(params, batch)
        B, S, _ = x.shape
        q_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        img_embeds = batch.get("img_embeds")

        def repeat_body(carry, xs):
            x, aux = carry
            blocks_slice, ab_slice = xs
            for p_idx, bdef in enumerate(cfg.pattern):
                ab = None if ab_slice is None else ab_slice[p_idx]
                x, _, a = self._apply_block(
                    blocks_slice[p_idx], bdef, x, q_pos=q_pos, mode="train",
                    img_embeds=img_embeds, act_bits=ab)
                x = constrain(x, "hidden")
                aux = aux + a
            return (x, aux), None

        if act_bits is None:
            body = lambda c, bs: repeat_body(c, (bs, None))
            xs = params["blocks"]
        else:
            body, xs = repeat_body, (params["blocks"], act_bits)
        if remat:
            # True -> save nothing per repeat; "dots" -> keep matmul outputs
            # (incl. FSDP-gathered weight products: no re-gather in backward)
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if remat == "dots" else None)
            body = jax.checkpoint(body, policy=policy)
        (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), xs)
        return self.logits_of(params, x), aux

    def loss(self, params, batch, act_bits=None, remat: bool = False):
        logits, aux = self.apply(params, batch, act_bits=act_bits,
                                 remat=remat)
        labels = batch["labels"]
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(
            logits.astype(jnp.float32), jnp.maximum(labels, 0)[..., None],
            axis=-1)[..., 0]
        mask = (labels >= 0).astype(jnp.float32)
        nll = jnp.sum((lse - gold) * mask) / jnp.maximum(mask.sum(), 1.0)
        return nll + 0.01 * aux

    # ---------------------------------------------------------------- caches
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16,
                   kv_bits: Optional[int] = None):
        """Per-pattern-position stacked cache pytree (leading dim n_repeat).

        kv_bits=8 stores K/V int8 with per-(position, head) scales -- halving
        the dominant HBM term of long-context decode (DESIGN.md section 3)."""
        cfg = self.cfg
        kv_dt = jnp.int8 if kv_bits == 8 else dtype

        def kv_entry(b, s):
            one = {
                "k": jnp.zeros((b, s, cfg.n_kv_heads, cfg.hdim), kv_dt),
                "v": jnp.zeros((b, s, cfg.n_kv_heads, cfg.hdim), kv_dt),
            }
            if kv_bits == 8:
                one["k_s"] = jnp.ones((b, s, cfg.n_kv_heads), jnp.float32)
                one["v_s"] = jnp.ones((b, s, cfg.n_kv_heads), jnp.float32)
            return one

        caches = []
        for bdef in cfg.pattern:
            if bdef.kind == "mamba":
                one = ssm_mod.init_mamba_cache(batch, cfg.d_model, cfg.ssm,
                                               dtype)
            elif bdef.kind == "cross_attn":
                one = kv_entry(batch, cfg.n_img_tokens)
            else:
                W = max_len if (bdef.kind != "local_attn" or cfg.window is None) \
                    else min(max_len, cfg.window)
                one = kv_entry(batch, W)
                one["pos"] = jnp.full((batch, W), POS_SENTINEL, jnp.int32)
            stacked = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (cfg.n_repeat,) + a.shape),
                one)
            caches.append(stacked)
        return tuple(caches)

    def init_paged_cache(self, n_slots: int, num_pages: int, page_size: int,
                         dtype=jnp.bfloat16, kv_bits: Optional[int] = None,
                         n_repeat: Optional[int] = None):
        """Paged decode cache for the continuous-batching engine.

        Per pattern position (stacked over n_repeat like ``init_cache``),
        keyed by ``cfg.cache_kinds()``:

        * ``"paged"`` (attn / local_attn): a pool of ``num_pages`` physical
          pages of ``page_size`` KV slots shared by all sequences --
          ``{"k","v": (R, P, Hkv, ps, hd), "pos": (R, P, ps) int32}``,
          head-major: each head's (ps, hd) page plane is contiguous,
          the block the Pallas page walk DMAs (kernels/attention.py).
          ``pos`` starts at ``POS_SENTINEL`` so unwritten slots are masked;
          page 0 is the trash page (serve/paged_kv.py owns the lifecycle).
        * ``"memory"`` (cross_attn) / ``"state"`` (mamba): dense per-slot
          caches with batch axis ``n_slots``, exactly the single-batch
          layouts, since neither grows with decoded length.

        ``kv_bits=8`` stores K/V pages int8 with one scale page per KV page
        (``"k_s","v_s": (R, P, Hkv, ps) f32``, per-(head, slot) scales) --
        the same quantizer as the dense cache (``_kv_quant``), so paged
        serving is bit-identical to dense int8 decode; the Pallas decode
        kernel dequantizes the pages in VMEM.

        ``n_repeat`` overrides the stack depth (default ``cfg.n_repeat``):
        the speculative engine's shallow-prefix *draft* cache stacks only
        the first ``draft_layers`` repeats (:meth:`draft_prefix_params`),
        sharing the main stream's block tables.
        """
        cfg = self.cfg
        R = cfg.n_repeat if n_repeat is None else n_repeat
        if not 1 <= R <= cfg.n_repeat:
            raise ValueError(f"n_repeat override {R} outside 1.."
                             f"{cfg.n_repeat}")
        kv_dt = jnp.int8 if kv_bits == 8 else dtype

        def kv_pages():
            one = {
                "k": jnp.zeros((num_pages, cfg.n_kv_heads, page_size,
                                cfg.hdim), kv_dt),
                "v": jnp.zeros((num_pages, cfg.n_kv_heads, page_size,
                                cfg.hdim), kv_dt),
                "pos": jnp.full((num_pages, page_size), POS_SENTINEL,
                                jnp.int32),
            }
            if kv_bits == 8:
                one["k_s"] = jnp.ones((num_pages, cfg.n_kv_heads,
                                       page_size), jnp.float32)
                one["v_s"] = jnp.ones((num_pages, cfg.n_kv_heads,
                                       page_size), jnp.float32)
            return one

        caches = []
        for bdef, kind in zip(cfg.pattern, cfg.cache_kinds()):
            if kind == "state":
                one = ssm_mod.init_mamba_cache(n_slots, cfg.d_model, cfg.ssm,
                                               dtype)
            elif kind == "memory":
                one = {
                    "k": jnp.zeros((n_slots, cfg.n_img_tokens,
                                    cfg.n_kv_heads, cfg.hdim), dtype),
                    "v": jnp.zeros((n_slots, cfg.n_img_tokens,
                                    cfg.n_kv_heads, cfg.hdim), dtype),
                }
            else:
                one = kv_pages()
            stacked = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (R,) + a.shape), one)
            caches.append(stacked)
        return tuple(caches)

    # -------------------------------------------------- draft-prefix view
    def draft_prefix_params(self, params, draft_layers: int):
        """Shallow self-draft view: the first ``draft_layers`` pattern
        repeats of ``params``, sharing embed/final_norm/unembed.

        The stacked-block layout makes a depth-truncated model a pure
        *slice*: every leaf of ``params["blocks"]`` carries the repeat
        stack as its leading axis (including :class:`PackedWeight`
        children, whose static aux -- bucket membership -- is R-invariant
        by construction), so ``leaf[:draft_layers]`` is a valid parameter
        pytree for the same entry points.  ``model_step`` then runs the
        draft exactly like the target, against a draft cache stacked to
        the same depth (``init_paged_cache(n_repeat=draft_layers)``).
        Used by the speculative serving loop (docs/speculative.md); with
        ``draft_layers == n_repeat`` the draft *is* the target (acceptance
        1.0 -- the parity-bench sanity ceiling).
        """
        if not 1 <= draft_layers <= self.cfg.n_repeat:
            raise ValueError(
                f"draft_layers={draft_layers} outside 1..{self.cfg.n_repeat}"
                f" (cfg.n_repeat)")
        blocks = jax.tree.map(lambda a: a[:draft_layers], params["blocks"])
        return {**params, "blocks": blocks}

    # ------------------------------------------------------------ prefill
    def prefill(self, params, batch, cache, act_bits=None, attn_impl=None):
        """Run the prompt, fill the cache, return last-token logits.

        act_bits: optional (n_repeat, len(pattern)) activation QBN array --
        the same per-block hook ``apply`` takes, so a searched policy's
        activation bits follow the model into serving.  attn_impl: static
        attention backend selector (layers.ATTN_IMPLS)."""
        cfg = self.cfg
        x = self._embed(params, batch)
        B, S, _ = x.shape
        q_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        img_embeds = batch.get("img_embeds")

        def repeat_body(x, xs):
            blocks_slice, cache_slice, ab_slice = xs
            new_slices = []
            for p_idx, bdef in enumerate(cfg.pattern):
                ab = None if ab_slice is None else ab_slice[p_idx]
                x, nc, _ = self._apply_block(
                    blocks_slice[p_idx], bdef, x, q_pos=q_pos, mode="prefill",
                    img_embeds=img_embeds, cache=cache_slice[p_idx],
                    act_bits=ab, attn_impl=attn_impl)
                x = constrain(x, "hidden")
                new_slices.append(nc)
            return x, tuple(new_slices)

        body, xs = self._with_act_bits(repeat_body, params, cache, act_bits)
        x, new_cache = jax.lax.scan(body, x, xs)
        logits = self.logits_of(params, x[:, -1:, :])
        return logits, new_cache

    @staticmethod
    def _with_act_bits(repeat_body, params, cache, act_bits):
        """Scan inputs for a cached step, with or without the act-QBN rows."""
        if act_bits is None:
            return (lambda c, xs: repeat_body(c, xs + (None,)),
                    (params["blocks"], cache))
        return repeat_body, (params["blocks"], cache, act_bits)

    # ------------------------------------------------------------- decode
    def decode_step(self, params, tokens, cache, pos, act_bits=None,
                    attn_impl=None):
        """One decode step.  tokens: (B, 1) int32 (or (B, 1, d) embeds for
        audio_stub); pos: scalar int32.  act_bits / attn_impl as in
        :meth:`prefill`.  Returns (logits, new_cache)."""
        cfg = self.cfg
        if cfg.frontend == "audio_stub":
            x = tokens
        else:
            x = self._embed_tokens(params, tokens)
        x = constrain(x, "hidden")
        B = x.shape[0]
        q_pos = jnp.full((B, 1), pos, jnp.int32)

        def repeat_body(x, xs):
            blocks_slice, cache_slice, ab_slice = xs
            new_slices = []
            for p_idx, bdef in enumerate(cfg.pattern):
                ab = None if ab_slice is None else ab_slice[p_idx]
                x, nc, _ = self._apply_block(
                    blocks_slice[p_idx], bdef, x, q_pos=q_pos, mode="decode",
                    cache=cache_slice[p_idx], write_pos=pos, act_bits=ab,
                    attn_impl=attn_impl)
                x = constrain(x, "hidden")
                new_slices.append(nc if nc is not None else cache_slice[p_idx])
            return x, tuple(new_slices)

        body, xs = self._with_act_bits(repeat_body, params, cache, act_bits)
        x, new_cache = jax.lax.scan(body, x, xs)
        return self.logits_of(params, x), new_cache

    # ------------------------------------------------------ paged decode
    def decode_step_paged(self, params, tokens, cache, block_tables, pos,
                          act_bits=None, attn_impl=None):
        """One decode step over a paged KV pool, per-sequence positions.

        tokens: (B, 1) int32; block_tables: (B, nb) int32 physical page ids
        (``paged_kv.BlockTables.as_array``); pos: (B,) int32 -- the position
        each sequence's token occupies (mixed lengths, unlike
        ``decode_step``'s single scalar).  ``cache`` is an
        ``init_paged_cache`` tuple.  Inactive batch slots carry all-trash
        block tables: their writes land in page 0 and their outputs are
        garbage the scheduler ignores.  act_bits / attn_impl as in
        :meth:`prefill`.  Returns (logits, new_cache)."""
        cfg = self.cfg
        x = self._embed_tokens(params, tokens)
        x = constrain(x, "hidden")
        B = x.shape[0]
        q_pos = pos.astype(jnp.int32)[:, None]

        def repeat_body(x, xs):
            blocks_slice, cache_slice, ab_slice = xs
            new_slices = []
            for p_idx, bdef in enumerate(cfg.pattern):
                ab = None if ab_slice is None else ab_slice[p_idx]
                x, nc, _ = self._apply_block(
                    blocks_slice[p_idx], bdef, x, q_pos=q_pos, mode="decode",
                    cache=cache_slice[p_idx], write_pos=pos,
                    block_tables=block_tables, act_bits=ab,
                    attn_impl=attn_impl)
                x = constrain(x, "hidden")
                new_slices.append(nc if nc is not None else cache_slice[p_idx])
            return x, tuple(new_slices)

        body, xs = self._with_act_bits(repeat_body, params, cache, act_bits)
        x, new_cache = jax.lax.scan(body, x, xs)
        return self.logits_of(params, x), new_cache

    # ------------------------------------------- unified token-budget step
    def model_step(self, params, tokens, positions, slot_map, cache,
                   block_tables, logit_cols, act_bits=None, attn_impl=None):
        """One token-budget step: prompt chunks and decode tokens together.

        The chunked-prefill serving loop's single entry point -- prefill and
        decode are the same call.  Row ``r`` of the fixed-shape ``(R, k)``
        batch carries slot ``slot_map[r]``'s contribution this step: a
        prompt chunk of up to ``k`` tokens, one decode token, or nothing.
        Real tokens are **left-aligned in ascending position order**; padded
        columns carry ``positions == POS_SENTINEL`` (their K/V writes route
        to the trash page and their query rows mask or are ignored).  K/V is
        written *straight into block-table pages* -- there is no dense
        intermediate cache and no per-prompt-length shape anywhere, so jit
        variants are bounded by (R, k, pool shape) alone.

        tokens / positions: (R, k) int32; slot_map: (R,) int32 row ->
        scheduler slot (selects each row's block-table row); block_tables:
        (n_slots, nb) int32; logit_cols: (R,) *or* (R, C) int32 -- the
        token columns whose hidden states feed the returned logits.  The
        1-D form is the chunked-prefill contract (each row's last real
        column, mirror of ``prefill``'s last-token slice; returns
        ``(R, 1, V)``).  The 2-D form is the speculative-verify
        generalization: ``C`` columns per row -- a speculating lane reads
        logits at *every* column of its ``[feedback, draft_1..draft_k]``
        span (repeat a column to pad; duplicates are free, it is one
        gather) and the call returns ``(R, C, V)``.  Rows without real
        tokens produce garbage the scheduler ignores.  ``cache`` is an
        ``init_paged_cache`` tuple whose kinds must all be ``"paged"``:
        recurrent ("state") and cross-attention ("memory") blocks cannot
        chunk and stay on the monolithic prefill path.  act_bits /
        attn_impl as in :meth:`prefill`.  Returns (logits (R, C, V) with
        ``C = 1`` for 1-D ``logit_cols``, new_cache).
        """
        cfg = self.cfg
        kinds = cfg.cache_kinds()
        if any(kd != "paged" for kd in kinds):
            raise ValueError(
                "model_step requires a pure paged-cache pattern (attn / "
                f"local_attn only); got cache kinds {kinds} -- serve hybrid "
                "architectures through the monolithic prefill path")
        x = self._embed_tokens(params, tokens)
        x = constrain(x, "hidden")
        q_pos = positions.astype(jnp.int32)
        bt_rows = jnp.take(block_tables, slot_map, axis=0)     # (R, nb)

        def repeat_body(x, xs):
            blocks_slice, cache_slice, ab_slice = xs
            new_slices = []
            for p_idx, bdef in enumerate(cfg.pattern):
                ab = None if ab_slice is None else ab_slice[p_idx]
                x, nc, _ = self._apply_block(
                    blocks_slice[p_idx], bdef, x, q_pos=q_pos, mode="decode",
                    cache=cache_slice[p_idx], write_pos=q_pos,
                    block_tables=bt_rows, act_bits=ab, attn_impl=attn_impl)
                x = constrain(x, "hidden")
                new_slices.append(nc)
            return x, tuple(new_slices)

        body, xs = self._with_act_bits(repeat_body, params, cache, act_bits)
        x, new_cache = jax.lax.scan(body, x, xs)
        R, _, d = x.shape
        cols = logit_cols.astype(jnp.int32)
        if cols.ndim == 1:
            cols = cols[:, None]
        C = cols.shape[1]
        idx = jnp.broadcast_to(cols[:, :, None], (R, C, d))
        return self.logits_of(params, jnp.take_along_axis(x, idx, axis=1)), \
            new_cache

    # -------------------------------------------------- activation QBNs
    def block_act_bits(self, graph: QuantizableGraph, values,
                       default: float = None) -> jnp.ndarray:
        """Collapse per-graph-site activation QBNs onto the model's hook.

        The forward takes one activation scalar per (repeat, pattern
        position) block; ``values`` is a sequence aligned with
        ``graph.layers`` (floats or traced scalars).  All sites of pattern
        position ``p`` share ``p``'s scalar and the *first* site wins --
        the block's input projection (``wq`` / ``w_xz``), whose input
        activation is the one the hook quantizes; ``wk``/``wv``/FFN share
        it.  Positions with no searched site (and the unembed, whose
        logits stay fp) get ``default`` (FULL_BITS pass-through).  This is
        the single source of the search->serve collapse: both the
        evaluator (core/evaluate.py) and the engine use it, so search-time
        evaluation and serving quantize activations identically.
        """
        from repro.quant.linear_quant import FULL_BITS
        if default is None:
            default = float(FULL_BITS)
        n_pat = len(self.cfg.pattern)
        site_pos = [int(l.name[1:].split(".")[0])
                    if l.name.startswith("p") else -1 for l in graph.layers]
        per_pos = []
        for p in range(n_pat):
            cand = [v for sp, v in zip(site_pos, values) if sp == p]
            per_pos.append(jnp.asarray(cand[0] if cand else default,
                                       jnp.float32))
        row = jnp.stack(per_pos)
        return jnp.tile(row[None, :], (self.cfg.n_repeat, 1))

    # ------------------------------------------------------- quant graph
    def graph(self, seq_len: int, batch: int,
              max_groups: int = 64) -> QuantizableGraph:
        """Quantizable-layer graph (weights of every matmul site).

        Stacked (scan) weights appear as one LayerInfo per (pattern position,
        site); its bit vector is shared across the n_repeat stack (DESIGN.md
        section 4).  Small tiny-LM configs use period == n_layers so every
        layer is searched independently, matching the paper's regime.
        """
        cfg = self.cfg
        R = cfg.n_repeat
        toks = seq_len * batch
        layers = []

        def add(name, path, c_in, c_out, macs, numel, axis, kind="linear"):
            n_groups = min(max_groups, c_out)
            layers.append(LayerInfo(
                name=name, kind=kind, c_in=c_in, c_out=c_out, k=1, stride=1,
                macs=float(macs), numel=int(numel), param_path=path,
                channel_axis=axis, n_groups=n_groups))

        d, hd = cfg.d_model, cfg.hdim
        for p_idx, bdef in enumerate(cfg.pattern):
            pre = ("blocks", p_idx)
            nm = f"p{p_idx}"
            if bdef.kind in ("attn", "local_attn", "cross_attn"):
                qd, kvd = cfg.n_heads * hd, cfg.n_kv_heads * hd
                add(f"{nm}.wq", pre + ("wq",), d, qd, R * toks * d * qd,
                    R * d * qd, -1)
                kv_toks = cfg.n_img_tokens * batch \
                    if bdef.kind == "cross_attn" else toks
                add(f"{nm}.wk", pre + ("wk",), d, kvd, R * kv_toks * d * kvd,
                    R * d * kvd, -1)
                add(f"{nm}.wv", pre + ("wv",), d, kvd, R * kv_toks * d * kvd,
                    R * d * kvd, -1)
                add(f"{nm}.wo", pre + ("wo",), qd, d, R * toks * qd * d,
                    R * qd * d, -1)
            else:
                s = cfg.ssm
                di = s.d_inner(d)
                add(f"{nm}.w_xz", pre + ("mamba", "w_xz"), d, 2 * di,
                    R * toks * d * 2 * di, R * d * 2 * di, -1)
                add(f"{nm}.w_bc", pre + ("mamba", "w_bc"), d, 2 * s.d_state,
                    R * toks * d * 2 * s.d_state, R * d * 2 * s.d_state, -1)
                add(f"{nm}.w_out", pre + ("mamba", "w_out"), di, d,
                    R * toks * di * d, R * di * d, -1)
            if bdef.has_ffn:
                if bdef.use_moe:
                    m = cfg.moe
                    eff_toks = toks * m.top_k / m.n_experts
                    for site, cin, cout in (("wg", d, m.d_ff),
                                            ("wu", d, m.d_ff),
                                            ("wd", m.d_ff, d)):
                        add(f"{nm}.{site}", pre + (site,), cin, cout,
                            R * m.n_experts * eff_toks * cin * cout,
                            R * m.n_experts * cin * cout, -1, kind="expert")
                else:
                    add(f"{nm}.wg", pre + ("wg",), d, cfg.d_ff,
                        R * toks * d * cfg.d_ff, R * d * cfg.d_ff, -1)
                    add(f"{nm}.wu", pre + ("wu",), d, cfg.d_ff,
                        R * toks * d * cfg.d_ff, R * d * cfg.d_ff, -1)
                    add(f"{nm}.wd", pre + ("wd",), cfg.d_ff, d,
                        R * toks * cfg.d_ff * d, R * cfg.d_ff * d, -1)
        add("unembed", ("unembed",), d, cfg.vocab_padded,
            toks * d * cfg.vocab_padded, d * cfg.vocab_padded, -1,
            kind="unembed")
        return QuantizableGraph(layers=layers)
