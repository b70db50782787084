"""Serving engine: single-batch prefill/decode plus continuous batching.

Two execution models share one weight store and one model:

* :meth:`ServeEngine.generate` -- the original batch-at-a-time path: one
  dense ``[B, max_len]`` KV cache, every sequence prefilled together, the
  whole batch decoded in lockstep.  It is the *oracle*: the paged path must
  reproduce its token streams per request.
* :meth:`ServeEngine.run` -- continuous batching over a paged KV cache
  with a unified token-budget step loop (``prefill="chunked"``, default):
  requests are admitted as soon as their *first prompt chunk* fits
  (serve/scheduler.py), and one jit'd ``model_step`` per iteration
  advances every in-flight sequence -- each contributing up to
  ``chunk_tokens`` prompt-chunk tokens or 1 decode token, K/V written
  straight into block-table pages (serve/paged_kv.py).  jit variants are
  bounded per (max_slots, chunk, pool shape), independent of prompt
  lengths.  ``prefill="monolithic"`` keeps the legacy
  prefill-then-decode state machine (batch-1 prefill scattered into the
  pool + ``decode_step_paged``): the only mode for hybrid mamba /
  cross-attention patterns, and the chunked mode's TTFT baseline.
  ``run(speculative=True)`` adds multi-token decode on top of the chunked
  loop: a draft pass proposes ``draft_k`` tokens per decoding lane, one
  verify ``model_step`` scores each lane's whole span as a chunk past its
  current position, and over-speculated KV pages roll back the same step
  -- emitted streams stay bit-identical for any draft
  (docs/speculative.md).

AutoQ integration: the engine deploys a searched :class:`QuantPolicy` at
weight-load time, with per-layer dispatch between two weight stores:

* ``weight_store="fake"`` -- fake-quantized f32 tensors (search-time
  numerics, full-size HBM footprint);
* ``weight_store="packed"`` -- the bucketed sub-byte layout
  (quant.apply.apply_policy_packed): channels with QBN <= 4 bit-packed
  along K (kernels/pack.py), 5..8 int8, > 8 bf16, so stored bytes track the
  searched policy.  ``models.layers.deq`` unpacks at use, on TPU into a
  bf16 copy of each weight per step, not inside the matmul (its docstring;
  kernels/packed_matmul.py is the explicit-tiling version that reads the
  stored width, benchmarked in benchmarks/packed_vs_int8.py).

Both stores serve through *both* execution models unchanged -- the store is
a property of the parameters, not of the cache layout (invariant guarded by
tests/test_paged_kv.py parity tests).

Attention runs on the Pallas kernels by default (``attn_impl="pallas"``:
kernels/attention.py -- fused flash prefill + block-table paged decode, in
interpret mode off-TPU); ``attn_impl="ref"`` is the escape hatch back to
the jnp oracle path, which is also what the train/dry-run paths use.

Activation quantization: a policy's per-block activation QBNs are threaded
into prefill and decode (``serve_act_bits``, on by default), closing the
search->serve gap for activations the same way the weight stores close it
for weights.  ``kv_bits=8`` extends the int8 KV cache to the paged pool
(scale page per KV page; the Pallas decode kernel dequantizes in VMEM).
Everything still runs on a laptop CPU and under a production mesh unchanged
(the dry-run lowers the same prefill/decode steps against the 256/512-chip
meshes).
"""
from __future__ import annotations

import collections
import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.pack import PackedWeight
from repro.models.transformer import LM
from repro.quant.apply import apply_policy_packed, apply_policy_to_params
from repro.quant.policy import QuantPolicy
from repro.serve import paged_kv
from repro.serve.frontend import FrontEnd, as_request
from repro.serve.scheduler import Request, Scheduler
from repro.serve.stats import ServeStats          # re-export (home moved)
from repro.serve.step_loop import StepLoop

__all__ = ["ServeEngine", "ServeStats"]


class ServeEngine:
    def __init__(self, model: LM, params, policy: Optional[QuantPolicy] = None,
                 graph=None, max_len: int = 512, cache_dtype=jnp.float32,
                 weight_store: str = "fake", attn_impl: str = "pallas",
                 kv_bits: Optional[int] = None, serve_act_bits: bool = True):
        """attn_impl: attention backend for every engine model call
        (``"pallas"`` default / ``"ref"`` oracle escape hatch).  kv_bits=8
        stores the KV cache -- dense and paged alike -- as int8 with
        per-(position, head) scales.  serve_act_bits: thread the policy's
        per-block activation QBNs into prefill/decode (no-op without a
        policy)."""
        if weight_store not in ("fake", "packed"):
            raise ValueError(f"unknown weight_store {weight_store!r}")
        if weight_store == "packed" and policy is None:
            raise ValueError("weight_store='packed' requires a policy "
                             "(without one the engine would silently serve "
                             "dense full-precision weights)")
        from repro.models.layers import ATTN_IMPLS
        if attn_impl not in ATTN_IMPLS:
            raise ValueError(f"unknown attn_impl {attn_impl!r}; "
                             f"expected one of {ATTN_IMPLS}")
        if kv_bits not in (None, 8):
            raise ValueError(f"unsupported kv_bits {kv_bits!r}: only 8 "
                             "(int8 + per-(position, head) scales) is "
                             "implemented; None serves full-precision KV")
        self.model = model
        self.max_len = max_len
        self.cache_dtype = cache_dtype
        self.weight_store = weight_store
        self.attn_impl = attn_impl
        self.kv_bits = kv_bits
        self.act_bits = None
        if policy is not None:
            graph = graph or model.graph(seq_len=1, batch=1)
            if weight_store == "packed":
                params = apply_policy_packed(params, graph, policy)
            else:
                params = apply_policy_to_params(params, graph, policy)
            if serve_act_bits:
                # the same policy -> per-block collapse the evaluator uses,
                # so serving quantizes activations exactly like search-time
                # evaluation (block scalar = input projection site's QBN)
                from repro.quant.linear_quant import FULL_BITS
                self.act_bits = model.block_act_bits(
                    graph, [policy.act_bits.get(l.name, float(FULL_BITS))
                            for l in graph.layers])
        self.params = params
        # the latest serve() session's counters, set before its loop runs
        self.stats: Optional[ServeStats] = None
        # trace counters: each jit *trace* (i.e. each compiled variant) runs
        # the python wrapper once, cache hits never do -- so these count
        # compiled variants per entry point.  The chunked step loop is
        # designed to keep trace_counts["model_step"] independent of the
        # number of distinct prompt lengths (regression-tested).
        self.trace_counts: Dict[str, int] = collections.Counter()

        def counted(name, fn):
            @functools.wraps(fn)
            def wrapped(*a, **kw):
                self.trace_counts[name] += 1
                return fn(*a, **kw)
            return wrapped

        self._prefill = jax.jit(counted("prefill", model.prefill),
                                static_argnames=("attn_impl",))
        self._decode = jax.jit(counted("decode_step", model.decode_step),
                               static_argnames=("attn_impl",))
        self._decode_paged = jax.jit(
            counted("decode_step_paged", model.decode_step_paged),
            static_argnames=("attn_impl",))
        self._model_step = jax.jit(counted("model_step", model.model_step),
                                   static_argnames=("attn_impl",))
        # the speculative draft pass runs the same unified step under its
        # own trace counter, so variant boundedness is auditable per role
        self._draft_step = jax.jit(counted("draft_step", model.model_step),
                                   static_argnames=("attn_impl",))

        def sample_span(logits, keys, temps):
            """Batched on-device sampling: every lane's candidate token(s)
            plus the rng key state per acceptance length, one device call.

            logits (R, C, V); keys (R, 2) raw uint32; temps (R,).  Returns
            ``toks`` (R, C) int32 and ``keys_seq`` (R, C+1, 2) where
            ``keys_seq[r, m]`` is lane r's key after consuming *m* tokens
            -- the caller gathers the state matching how many tokens each
            lane actually emitted, so rejected speculative columns never
            consume rng.  Bit-identical to the historical eager per-lane
            path: greedy lanes argmax (key untouched), sampled lanes
            split-then-categorical per emitted token, matching a
            single-request generate(seed) stream split-for-split.
            """
            def lane(lg, key, temp):
                safe = jnp.where(temp > 0, temp, jnp.float32(1.0))

                def col(key, row):
                    nk, k = jax.random.split(key)
                    samp = jax.random.categorical(
                        k, row.astype(jnp.float32) / safe, -1)
                    tok = jnp.where(temp > 0, samp,
                                    jnp.argmax(row, -1)).astype(jnp.int32)
                    nxt = jnp.where(temp > 0, nk, key)
                    return nxt, (tok, nxt)

                _, (toks, ks) = jax.lax.scan(col, key, lg)
                return toks, jnp.concatenate([key[None], ks], 0)

            return jax.vmap(lane)(logits, keys, temps)

        self._sample_span = jax.jit(counted("sample_step", sample_span))

        def draft_tail(params, cache, tables, slot_map, tok0, pos0, spans,
                       steps, act):
            """Fused draft proposal tail: the autoregressive (R, 1) chain
            ``d_2 .. d_k`` as one scanned jit instead of k-1 separate
            dispatches.  ``spans`` masks each lane (a lane proposes while
            its verify span still has columns: ``spans >= m + 2`` at tail
            iteration m); masked lanes carry sentinel positions, so their
            writes land in the trash page.  Returns the (k-1, R) proposal
            stack and the advanced draft cache."""
            zeros = jnp.zeros(tok0.shape, jnp.int32)

            def body(carry, mm):
                cache, tok = carry
                active = spans >= mm + 2
                pos = jnp.where(active, pos0 + mm, paged_kv.POS_SENTINEL)
                logits, cache = model.model_step(
                    params, tok[:, None], pos[:, None], slot_map, cache,
                    tables, zeros, act, attn_impl=self.attn_impl)
                prop = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
                tok = jnp.where(active, prop, tok)
                return (cache, tok), prop

            (cache, _), props = jax.lax.scan(body, (cache, tok0), steps)
            return props, cache

        self._draft_tail = jax.jit(counted("draft_tail", draft_tail))

    def weight_hbm_bytes(self) -> Dict[str, int]:
        """Stored weight bytes by leaf kind.

        ``packed`` counts PackedWeight buffers + scales (the sub-byte
        store); ``int8`` counts {"q","s"} leaves; ``dense`` everything else.
        The packed total is what a searched 4-bit-average policy's HBM
        weight traffic actually costs -- the quantity core/roofline.py's
        reward models."""
        out = {"packed": 0, "int8": 0, "dense": 0}
        leaves = jax.tree_util.tree_leaves_with_path(
            self.params, is_leaf=lambda x: isinstance(x, PackedWeight))
        for path, leaf in leaves:
            if isinstance(leaf, PackedWeight):
                out["packed"] += leaf.hbm_bytes()
            elif any(getattr(p, "key", None) in ("q", "s") for p in path):
                out["int8"] += leaf.size * leaf.dtype.itemsize
            else:
                out["dense"] += leaf.size * leaf.dtype.itemsize
        out["total"] = out["packed"] + out["int8"] + out["dense"]
        return out

    # --------------------------------------------------------- single batch
    def generate(self, tokens: np.ndarray, n_new: int,
                 temperature: float = 0.0, seed: int = 0
                 ) -> Dict[str, Any]:
        """tokens: (B, S_prompt) int32.  Greedy (T=0) or sampled decode."""
        B, S = tokens.shape
        assert S + n_new <= self.max_len
        cache = self.model.init_cache(B, self.max_len, dtype=self.cache_dtype,
                                      kv_bits=self.kv_bits)
        stats = ServeStats(n_requests=B)
        t0 = time.time()
        logits, cache = self._prefill(self.params,
                                      {"tokens": jnp.asarray(tokens)}, cache,
                                      self.act_bits,
                                      attn_impl=self.attn_impl)
        logits.block_until_ready()
        stats.prefill_s = time.time() - t0

        rng = jax.random.PRNGKey(seed)
        out = []
        t0 = time.time()
        cur = None
        for i in range(n_new):
            if temperature > 0:
                rng, k = jax.random.split(rng)
                cur = jax.random.categorical(
                    k, logits[:, -1].astype(jnp.float32) / temperature, -1)
            else:
                cur = jnp.argmax(logits[:, -1], -1)
            cur = cur.astype(jnp.int32)[:, None]
            out.append(np.asarray(cur))
            logits, cache = self._decode(self.params, cur, cache,
                                         jnp.int32(S + i), self.act_bits,
                                         attn_impl=self.attn_impl)
        jax.block_until_ready(logits)
        stats.decode_s = time.time() - t0
        stats.tokens_out = B * n_new
        stats.steps = n_new
        return {"tokens": np.concatenate(out, axis=1), "stats": stats}

    # --------------------------------------------------- continuous batching
    def run(self, requests: Sequence[Union[Request, Dict[str, Any], tuple]],
            *, page_size: int = 16, max_slots: int = 8,
            num_pages: Optional[int] = None, prefill: Optional[str] = None,
            chunk_tokens: Optional[int] = None,
            token_budget: Optional[int] = None, speculative: bool = False,
            draft_k: int = 4, draft_policy: str = "prefix",
            draft_layers: Optional[int] = None,
            draft_act_bits: Optional[float] = None,
            overlap: bool = True) -> Dict[str, Any]:
        """Serve a workload of mixed-length requests with continuous batching.

        Since the open-loop split (docs/serving.md), ``run()`` is a thin
        *closed-loop client* of the open-loop core: it submits every
        request to a :class:`~repro.serve.frontend.FrontEnd` up front
        (all arriving "now") and drains :meth:`serve` -- the degenerate
        arrival pattern.  ``overlap`` (chunked mode) selects the
        pipelined back-end that dispatches step t+1 before syncing step
        t's tokens; ``overlap=False`` forces synchronous stepping.  Both
        produce bit-identical streams -- the parity suite runs the
        matrix.

        requests: each a :class:`Request`, a ``{"tokens", "n_new",
        "temperature"?, "seed"?}`` dict, or a ``(tokens, n_new)`` tuple;
        ``tokens`` is a 1-D prompt.  Per-request greedy/sampled decode
        follows the same rng discipline as a single-request
        :meth:`generate` call with that request's seed, so greedy outputs
        are comparable token-for-token against independent ``generate``
        calls -- under *either* prefill mode:

        * ``prefill="chunked"`` (default where supported): the unified
          token-budget step loop.  Prefill and decode are one jit'd
          ``model_step`` per iteration; each in-flight sequence contributes
          up to ``chunk_tokens`` prompt-chunk tokens or 1 decode token,
          bounded by ``token_budget`` real tokens per step, and prompt K/V
          is written straight into block-table pages (no batch-1 dense
          prefill, no per-prompt-length jit variants).  A request is
          admitted as soon as its *first chunk* fits.  Requires every cache
          kind to be ``"paged"`` (pure attention patterns).
        * ``prefill="monolithic"``: the legacy state machine -- one batch-1
          full-prompt prefill per admitted request scattered into the pool,
          then batched single-token decode steps.  The only mode for hybrid
          (mamba / cross-attention) patterns, whose recurrent state cannot
          chunk; kept as the TTFT baseline for the chunked path
          (benchmarks/continuous_batching.py).

        ``prefill=None`` auto-selects chunked where supported.
        chunk_tokens defaults to ``page_size``; token_budget to
        ``max_slots + chunk_tokens - 1`` (every decode lane plus one full
        chunk; with ``speculative=True``, ``max_slots * (draft_k + 1) +
        chunk_tokens - 1`` so full verify spans fit) and must be >=
        max_slots so decode lanes are never starved.

        ``speculative=True`` turns on multi-token decode
        (docs/speculative.md): each step a *draft* proposes up to
        ``draft_k`` tokens per decoding lane, one jit'd verify
        ``model_step`` runs every lane's ``[feedback, draft_1..draft_k]``
        span as a chunk past its current position (the same q-tile path
        chunked prefill uses), and the sampler keeps the longest
        draft/sample agreement prefix plus the corrected token --
        over-speculated KV pages roll back the same step.  Acceptance
        changes *throughput only*: token streams are bit-identical to a
        non-speculative ``run()`` for any draft, greedy and sampled alike
        (each emitted token comes from the same logits + rng split plain
        decode would use).  ``draft_policy="prefix"`` self-drafts with the
        first ``draft_layers`` (default ``n_repeat // 2``) repeats of this
        very model; ``"lowbit"`` re-runs the full model as the AutoQ-native
        cheap proxy -- ``draft_act_bits`` activation QBNs (default 4.0)
        and an int8-KV draft cache.  Each knob belongs to one policy and
        is rejected with the other: ``draft_layers`` is ``"prefix"``-only,
        ``draft_act_bits`` is ``"lowbit"``-only.
        Requires chunked prefill: hybrid (mamba / cross-attn) patterns
        raise, like forcing ``prefill="chunked"`` does -- serve them
        non-speculatively through ``prefill="monolithic"``.

        page_size: KV positions per page.  max_slots: decode-batch width
        (compiled shape).  num_pages: pool size; default sizes for the
        worst case (``max_slots`` sequences at ``max_len``), which can never
        stall.  A smaller pool throttles admission (a request is admitted
        when its prompt -- chunked: first chunk -- plus one page of decode
        headroom fits); in chunked mode a sequence that cannot grow
        mid-*prefill* is preempted and requeued (it has emitted nothing, so
        its restarted stream is unchanged), and prefilling sequences are
        preempted to keep *decode* lanes growing.  Only when nothing is
        left to preempt -- the pool cannot back the running set's decode
        growth, or a lone request can never fit -- does
        :class:`~.paged_kv.PagesExhausted` propagate (in monolithic mode it
        still propagates on any mid-run growth failure, as before).  For
        all-sliding-window patterns, pages that fall wholly out of every
        future attention window are reclaimed at each step boundary, so
        pool occupancy is O(window) per sequence, not O(generated length).

        Returns ``{"outputs": [np.ndarray per request, submit order],
        "stats": ServeStats}`` (with per-request TTFT in ``stats``).
        """
        reqs = [as_request(i, r) for i, r in enumerate(requests)]
        for r in reqs:
            if r.prompt_len + r.n_new > self.max_len:
                raise ValueError(
                    f"request {r.rid}: {r.prompt_len}+{r.n_new} tokens "
                    f"exceeds max_len={self.max_len}")
        kinds = self.model.cfg.cache_kinds()
        chunkable = all(kd == "paged" for kd in kinds)
        if prefill is None:
            prefill = "chunked" if chunkable else "monolithic"
        if prefill not in ("chunked", "monolithic"):
            raise ValueError(f"unknown prefill mode {prefill!r}")
        if prefill == "chunked" and not chunkable:
            raise ValueError(
                f"prefill='chunked' needs all-paged cache kinds, got "
                f"{kinds}: recurrent/memory blocks cannot chunk -- use "
                "prefill='monolithic'")
        if speculative:
            # fail fast, before any model call: running the verify chunk
            # against recurrent state would silently corrupt it
            if not chunkable:
                raise ValueError(
                    f"speculative=True needs all-paged cache kinds, got "
                    f"{kinds}: recurrent/memory blocks cannot run the "
                    "multi-token verify chunk -- serve hybrid patterns "
                    "non-speculatively through prefill='monolithic'")
            if prefill == "monolithic":
                raise ValueError(
                    "speculative=True runs through the chunked model_step "
                    "loop; prefill='monolithic' cannot carry verify spans "
                    "-- drop speculative=True or use prefill='chunked'")
            self._validate_draft_args(draft_k, draft_policy, draft_layers,
                                      draft_act_bits)
        if prefill == "chunked":
            fe = FrontEnd()
            for r in reqs:
                fe.submit(r)
            res = self.serve(fe, page_size=page_size, max_slots=max_slots,
                             num_pages=num_pages, chunk_tokens=chunk_tokens,
                             token_budget=token_budget,
                             speculative=speculative, draft_k=draft_k,
                             draft_policy=draft_policy,
                             draft_layers=draft_layers,
                             draft_act_bits=draft_act_bits, overlap=overlap)
            return {"outputs": [res["outputs"][r.rid] for r in reqs],
                    "stats": res["stats"]}
        blocks_per_seq = paged_kv.pages_needed(self.max_len, page_size)
        if num_pages is None:
            num_pages = max_slots * blocks_per_seq + 1      # +1: trash page
        cache = self.model.init_paged_cache(max_slots, num_pages, page_size,
                                            dtype=self.cache_dtype,
                                            kv_bits=self.kv_bits)
        sched = Scheduler(max_slots, page_size,
                          blocks_per_seq, paged_kv.PageAllocator(num_pages))
        for r in reqs:
            sched.submit(r)
        outputs: Dict[int, List[int]] = {r.rid: [] for r in reqs}
        stats = ServeStats(n_requests=len(reqs), mode=prefill)
        self._run_monolithic(reqs, sched, cache, kinds, outputs, stats,
                             num_pages, page_size,
                             self._reclaim_window(kinds))
        return {"outputs": [np.asarray(outputs[r.rid], np.int32)
                            for r in reqs],
                "stats": stats}

    def serve(self, frontend: FrontEnd, *, page_size: int = 16,
              max_slots: int = 8, num_pages: Optional[int] = None,
              chunk_tokens: Optional[int] = None,
              token_budget: Optional[int] = None, speculative: bool = False,
              draft_k: int = 4, draft_policy: str = "prefix",
              draft_layers: Optional[int] = None,
              draft_act_bits: Optional[float] = None,
              overlap: bool = True) -> Dict[str, Any]:
        """Open-loop serving: drain a :class:`FrontEnd` of timestamped
        arrivals through the overlapped step loop.

        The open-loop core of the serving split (docs/serving.md):
        requests may arrive *while the loop runs* -- pre-scheduled with
        ``frontend.submit(..., at=t)`` (the Poisson bench), or live from
        another thread.  Each iteration pumps due arrivals into the
        scheduler (shedding SLO-overdue waiters), admits what fits, and
        runs one token-budget ``model_step``; with ``overlap=True``
        (default, non-speculative) the host plans and dispatches step
        t+1 before syncing step t's sampled tokens, so the device never
        waits on host sampling (serve/step_loop.py documents the
        pipeline and its exact-feedback invariant).  The loop returns
        when every scheduled arrival has been served or shed -- a
        closed-*loop* client like :meth:`run` simply submits everything
        up front.

        Chunked-only: the open-loop core requires all-paged cache kinds
        (hybrid mamba / cross-attention patterns serve through
        ``run(prefill="monolithic")``).  ``speculative=True`` rides the
        same back-end synchronously (acceptance control flow needs token
        values); the remaining knobs match :meth:`run`.

        Returns ``{"outputs": {rid: np.ndarray}, "stats": ServeStats,
        "shed": [rid, ...]}`` -- shed requests (reported in both
        ``shed`` and ``stats.shed``) have empty output streams.  The same
        ``ServeStats`` is ``self.stats`` from before the loop runs, so a
        session ended by an exception still leaves its counters readable.
        """
        kinds = self.model.cfg.cache_kinds()
        if not all(kd == "paged" for kd in kinds):
            raise ValueError(
                f"open-loop serving needs all-paged cache kinds, got "
                f"{kinds}: recurrent/memory blocks cannot chunk -- serve "
                "hybrid patterns through run(prefill='monolithic')")
        if speculative:
            self._validate_draft_args(draft_k, draft_policy, draft_layers,
                                      draft_act_bits)
        chunk = chunk_tokens if chunk_tokens is not None else page_size
        if token_budget is not None:
            budget = token_budget
        elif speculative:
            # room for every lane's full verify span plus one chunk
            budget = max_slots * (draft_k + 1) + chunk - 1
        else:
            budget = max_slots + chunk - 1
        if chunk < 1:
            raise ValueError(f"chunk_tokens must be >= 1, got {chunk}")
        if budget < max_slots:
            raise ValueError(
                f"token_budget={budget} < max_slots={max_slots}: every "
                "decode lane needs a token each step (decode is never "
                "deferred); raise the budget or shrink the batch")
        blocks_per_seq = paged_kv.pages_needed(self.max_len, page_size)
        if num_pages is None:
            num_pages = max_slots * blocks_per_seq + 1      # +1: trash page
        cache = self.model.init_paged_cache(max_slots, num_pages, page_size,
                                            dtype=self.cache_dtype,
                                            kv_bits=self.kv_bits)
        sched = Scheduler(max_slots, page_size,
                          blocks_per_seq, paged_kv.PageAllocator(num_pages))
        spec = self._make_draft(
            max_slots, num_pages, page_size, draft_k, draft_policy,
            draft_layers, draft_act_bits) if speculative else None
        stats = ServeStats(mode="chunked",
                           overlapped=bool(overlap) and not speculative)
        loop = StepLoop(self, frontend, sched, cache, kinds, stats,
                        num_pages=num_pages, page_size=page_size,
                        chunk=chunk, budget=budget,
                        reclaim=self._reclaim_window(kinds), spec=spec,
                        overlap=overlap)
        self.stats = stats
        try:
            loop.run()
        finally:
            stats.n_requests = frontend.n_submitted
            stats.shed = list(frontend.shed)
        outputs = {rid: np.asarray(toks, np.int32)
                   for rid, toks in loop.outputs.items()}
        for rid in frontend.shed:
            outputs.setdefault(rid, np.zeros((0,), np.int32))
        return {"outputs": outputs, "stats": stats,
                "shed": list(frontend.shed)}

    def _reclaim_window(self, kinds) -> Optional[int]:
        # out-of-window reclamation is sound only when *every* block of the
        # pattern attends through the same sliding window (a single global
        # block needs the whole history; one block table serves all layers)
        cfg = self.model.cfg
        chunkable = all(kd == "paged" for kd in kinds)
        return cfg.window if (chunkable and cfg.window is not None and
                              all(b.kind == "local_attn"
                                  for b in cfg.pattern)) else None

    @staticmethod
    def _validate_draft_args(draft_k, draft_policy, draft_layers,
                             draft_act_bits) -> None:
        if draft_k < 1:
            raise ValueError(f"draft_k must be >= 1, got {draft_k}")
        if draft_policy not in ("prefix", "lowbit"):
            raise ValueError(f"unknown draft_policy {draft_policy!r}; "
                             "expected 'prefix' or 'lowbit'")
        if draft_layers is not None and draft_policy != "prefix":
            raise ValueError("draft_layers applies to "
                             "draft_policy='prefix' only")
        if draft_act_bits is not None and draft_policy != "lowbit":
            raise ValueError("draft_act_bits applies to "
                             "draft_policy='lowbit' only (the prefix "
                             "draft serves the target's own act QBNs)")

    # ------------------------------------------------- speculative drafting
    def _make_draft(self, max_slots, num_pages, page_size, draft_k,
                    draft_policy, draft_layers, draft_act_bits):
        """Build the draft pass state for one speculative ``run()``.

        The draft is *another view of the same engine*: it proposes tokens
        through the very ``model_step`` the target verifies with, against
        its own paged cache that shares the main stream's block tables
        (same positions, same page ids -- rollback and scrub cover both).

        * ``"prefix"``: the first ``draft_layers`` stacked repeats of the
          served params (``LM.draft_prefix_params``) -- no extra weights,
          cache stacked to the prefix depth.  ``draft_layers == n_repeat``
          makes the draft the target (acceptance 1.0, the bench ceiling).
        * ``"lowbit"``: the full model as its own cheap proxy, AutoQ
          style -- ``draft_act_bits`` activation QBNs everywhere and an
          int8-KV draft cache, so the draft pays low-bit compute/traffic
          for the same depth.
        """
        model, cfg = self.model, self.model.cfg
        if draft_policy == "prefix":
            d = draft_layers if draft_layers is not None \
                else max(1, cfg.n_repeat // 2)
            params = model.draft_prefix_params(self.params, d)
            act = None if self.act_bits is None else self.act_bits[:d]
            dcache = model.init_paged_cache(
                max_slots, num_pages, page_size, dtype=self.cache_dtype,
                kv_bits=self.kv_bits, n_repeat=d)
        else:                                     # "lowbit"
            params = self.params
            act = jnp.full((cfg.n_repeat, len(cfg.pattern)),
                           4.0 if draft_act_bits is None
                           else float(draft_act_bits), jnp.float32)
            dcache = model.init_paged_cache(
                max_slots, num_pages, page_size, dtype=self.cache_dtype,
                kv_bits=8)
        return {"params": params, "cache": dcache, "act": act, "k": draft_k,
                "frontier": {}}

    def _draft_propose(self, spec, plan, sched, spec_lanes, w1):
        """Run the draft pass for one step; returns slot -> draft tokens.

        Call 1 carries three kinds of rows: prompt-chunk rows keep the
        draft cache's prompt KV warm (without this, chunks fed while no
        lane was speculating would leave holes and crater acceptance);
        every decode row feeds its feedback token, *preceded by a one-token
        catch-up when the previous verify step accepted its whole span*
        (the last draft was proposed but never fed back, so the draft
        cache trails the stream by one position -- ``spec["frontier"]``
        tracks each lane's draft write cursor; after a rejection the
        frontier clamps back, because everything past the acceptance
        point is rejected-token KV that the stream overwrites in place);
        and each speculating row's last-real-column logits propose its
        first draft token.  The remaining proposals ``d_2 .. d_k`` are
        one *fused* ``draft_tail`` jit -- a scanned (R, 1) chain feeding
        each lane's previous proposal at the next position, exactly the
        autoregressive loop the verify step collapses, without the k-1
        per-call dispatch + transfer overhead the overlapped back-end
        would otherwise stall on (lanes whose span ends early are
        sentinel-masked; the whole proposal stack syncs as one
        transfer).  Draft proposals are greedy by design: the draft is a
        guess, the verify sampler is the ground truth.  ``w1`` is call
        1's width (the chunk width, or 2 on chunkless steps -- feedback
        plus the catch-up column), so the draft compiles two bounded
        ``draft_step`` shapes plus a single ``draft_tail`` shape."""
        n = plan["tokens"].shape[0]
        tables = jnp.asarray(sched.tables.as_array())
        slot_map = jnp.asarray(plan["slot_map"])
        frontier = spec.setdefault("frontier", {})
        dtok = np.zeros((n, w1), np.int32)
        dpos = np.full((n, w1), paged_kv.POS_SENTINEL, np.int32)
        lcols = np.zeros((n,), np.int32)
        for i, c in plan["chunked"].items():      # mirror prompt chunks
            dtok[i, :c] = plan["tokens"][i, :c]
            dpos[i, :c] = plan["positions"][i, :c]
            lcols[i] = c - 1
        for i in plan["spec"]:                    # decode rows (any span)
            s = sched.slot(i)
            catch = min(s.pos - frontier.get(i, s.pos), 1)
            if catch:                             # re-feed the accepted
                dtok[i, 0] = s.out[s.pos - 1 - s.req.prompt_len]
                dpos[i, 0] = s.pos - 1            # last draft of last span
            dtok[i, catch] = s.out[-1]
            dpos[i, catch] = s.pos
            lcols[i] = catch
        logits, spec["cache"] = self._draft_step(
            spec["params"], jnp.asarray(dtok), jnp.asarray(dpos), slot_map,
            spec["cache"], tables, jnp.asarray(lcols), spec["act"],
            attn_impl=self.attn_impl)
        prop = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        max_cols = max(spec_lanes.values(), default=1)
        if max_cols > 2:
            # fused tail: d_2..d_k for every lane in one scanned jit, one
            # proposal-stack transfer.  Always k-1 iterations (static scan
            # length keeps draft_tail at one compiled variant); lanes whose
            # span ends early run sentinel-masked into the trash page.
            spans = np.zeros((n,), np.int32)
            pos0 = np.zeros((n,), np.int32)
            for i, cols in spec_lanes.items():
                spans[i] = cols
                pos0[i] = sched.slot(i).pos
            props, spec["cache"] = self._draft_tail(
                spec["params"], spec["cache"], tables, slot_map, prop,
                jnp.asarray(pos0), jnp.asarray(spans),
                jnp.arange(1, spec["k"], dtype=jnp.int32), spec["act"])
            all_props = np.array(jnp.concatenate([prop[None], props], 0),
                                 np.int32)
        else:
            all_props = np.array(prop, np.int32)[None]
        # np.array (not asarray): callers own writable draft arrays
        drafts = {i: all_props[:cols - 1, i]
                  for i, cols in spec_lanes.items()}
        for i, cols in plan["spec"].items():      # draft write cursors
            frontier[i] = sched.slot(i).pos + max(cols - 1, 1)
        return drafts

    def _run_monolithic(self, reqs, sched, cache, kinds, outputs, stats,
                        num_pages, page_size, reclaim):
        """Legacy prefill-then-decode state machine (hybrid archs; TTFT
        baseline for the chunked loop).  Sampling runs through the same
        batched device sampler as the chunked back-end: one
        ``sample_step`` call and one (R,)-token transfer per decode step
        instead of a full logits pull plus per-lane host sampling."""
        t_run = time.time()
        n = sched.n_slots
        keys = jnp.zeros((n, 2), jnp.uint32)
        temps = jnp.zeros((n,), jnp.float32)
        while sched.has_work:
            # ---- admission: prefill queued requests into free slots/pages
            admitted = 0
            while (adm := sched.try_admit()) is not None:
                admitted += 1
                req, slot, pages = adm
                t0 = time.time()
                logits, dense = self._prefill_one(req, page_size)
                cache = paged_kv.scrub_pages(cache, kinds, pages)
                cache = paged_kv.write_prefill(cache, dense, kinds, slot,
                                               pages, page_size)
                keys = keys.at[slot].set(jax.random.PRNGKey(req.seed))
                temps = temps.at[slot].set(jnp.float32(req.temperature))
                toks, kseq = self._sample_span(logits[:, -1:],
                                               keys[slot:slot + 1],
                                               temps[slot:slot + 1])
                keys = keys.at[slot].set(kseq[0, 1])
                tok = int(np.asarray(toks)[0, 0])
                stats.prefill_s += time.time() - t0
                outputs[req.rid].append(tok)
                stats.tokens_out += 1
                stats.prefill_tokens += 1
                stats.mono_prefill_tokens += req.prompt_len
                stats.ttft_steps[req.rid] = stats.steps + 1
                stats.ttft_s[req.rid] = time.time() - t_run
                sched.bind(slot, req, tok)
            stats.peak_pages = max(stats.peak_pages,
                                   num_pages - 1 - sched.allocator.n_free)

            running = sched.running_slots()
            if not running:
                if sched.has_work and not admitted:
                    raise paged_kv.PagesExhausted(
                        "queued request cannot ever be admitted: pool of "
                        f"{num_pages} pages (page_size={page_size}) is too "
                        "small for its prompt + decode headroom")
                continue                    # everything admitted finished

            # ---- one batched decode step over all in-flight sequences
            # reclaim outside the timed section, like the chunked loop, so
            # decode_s compares like-for-like across modes
            if reclaim is not None:
                stats.reclaimed_pages += len(
                    sched.reclaim_out_of_window(reclaim))
            t0 = time.time()
            fresh = sched.ensure_pages()
            cache = paged_kv.scrub_pages(cache, kinds, fresh)
            b = sched.batch()
            logits, cache = self._decode_paged(
                self.params, jnp.asarray(b["tokens"]), cache,
                jnp.asarray(b["block_tables"]), jnp.asarray(b["pos"]),
                self.act_bits, attn_impl=self.attn_impl)
            toks, kseq = self._sample_span(logits[:, -1:], keys, temps)
            m = np.zeros((n,), np.int32)
            m[running] = 1                  # idle lanes never consume rng
            keys = kseq[jnp.arange(n), jnp.asarray(m)]
            vals = np.asarray(toks)         # one transfer for the batch
            for i in running:
                req = sched.slot(i).req
                tok = int(vals[i, 0])
                outputs[req.rid].append(tok)
                stats.tokens_out += 1
                sched.record(i, tok)
            stats.decode_s += time.time() - t0
            stats.steps += 1

    # ---------------------------------------------------------- run helpers
    def _prefill_one(self, req: Request, page_size: int):
        """Batch-1 prefill into a dense cache sized to whole pages.

        The cache length only pads the KV store (prefill logits are computed
        from the in-flight k/v, not read back), so rounding the prompt up to
        a page multiple bounds jit variants without changing numerics."""
        L = paged_kv.pages_needed(req.prompt_len, page_size) * page_size
        dense = self.model.init_cache(1, L, dtype=self.cache_dtype,
                                      kv_bits=self.kv_bits)
        logits, dense = self._prefill(
            self.params, {"tokens": jnp.asarray(req.tokens[None])}, dense,
            self.act_bits, attn_impl=self.attn_impl)
        return logits, dense
