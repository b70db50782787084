"""Overlapped token-budget step loop: the serving back-end.

This is ``ServeEngine``'s chunked step loop extracted into its own
layer, restructured so host and device work overlap.  The closed-loop
original dispatched ``model_step`` for step *t*, then blocked on the
full logits transfer, sampled every lane on the host, and only then
planned step *t+1* -- the device idled through all of it.  The split
loop instead pipelines (docs/serving.md has the diagram):

* **sample on device** -- one jit'd sampler (``sample_step``) draws
  every lane's token(s) from the step's logits in a single device call,
  keeping the per-request rng discipline bit-exact (a lane's key
  advances once per *emitted* token, greedy lanes never advance).  Only
  the (R,)-token vector ever crosses to the host: one transfer per
  step, replacing a full (R, C, V) logits pull plus per-lane host
  sampling.
* **plan value-free** -- ``plan_step`` is one-step-stale tolerant by
  construction (scheduler docstring): control flow depends on token
  counts and positions only, so step *t+1* is planned while step *t*'s
  tokens are still device-resident.  The loop records a ``PENDING``
  placeholder for each token it has not synced yet.
* **feed back on device** -- a decode lane's column-0 input for step
  *t+1* is scattered in from step *t*'s device-resident sample vector
  at dispatch, so the model always sees the *exact* sampled token; the
  placeholder never reaches the model.  Decode feedback stays exact --
  only the host's *view* is stale.
* **retire one step late** -- after dispatching step *t+1*, the host
  syncs step *t*'s token vector (the pipeline's only blocking point),
  backfills its ``PENDING`` output slots, fires stream callbacks in
  token order, and records arrival-relative latency.  Output streams
  are bit-identical to the synchronous loop; tokens simply become
  host-visible one step later.

Speculative decode rides the same class but steps synchronously
(``overlap`` is ignored): acceptance-length control flow needs token
*values*, so each verify step retires immediately -- still through the
batched device sampler, which draws every lane's whole candidate span
and the rng key for every possible acceptance length in one call.

Every phase runs under a profiler span named in ``SPANS`` below; the
``serve.step`` span carries the step's counts as args, and
``ServeStats`` adds up the same counts (``_count``), so a trace and the
counters cannot disagree (docs/serving.md, "Tracing the step loop").

jit-variant boundedness is unchanged: the loop adds no ``model_step``
shapes (2 per run: mixed width + pure-decode width), and the sampler
compiles at most two shapes of its own ((R, 1, V) plain, (R, k+1, V)
verify) regardless of arrival pattern.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.kernels.attention import live_pages
from repro.serve import paged_kv
from repro.serve.frontend import FrontEnd
from repro.serve.scheduler import Request, Scheduler
from repro.serve.stats import ServeStats

__all__ = ["StepLoop", "PENDING", "SPANS"]

# every host span the loop writes into a profiler trace
# (``jax.profiler.TraceAnnotation``: inactive unless a trace is running)
SPANS = (
    # one StepLoop.step; args are its dispatch's counts: step, rows, width,
    # prompt_tokens, decode_tokens, slots (rows x width; 0 when every
    # planned slot was preempted and nothing ran), fresh_pages,
    # pages_in_use, requeued, kv_pages (block-table pages the paged
    # attention kernel walks per layer, summed over lanes) and
    # kv_pages_table (rows x table width) -- the numbers ServeStats adds up
    "serve.step",
    "serve.admit",     # child of serve.step: first-chunk admissions
    "serve.plan",      # child of serve.step: Scheduler.plan_step
    "serve.scrub",     # child of serve.step: scrubbing the fresh pages
    "serve.draft",     # child of serve.step: speculative draft pass
    "serve.dispatch",  # child of serve.step: building the step's inputs
    # inside serve.dispatch and serve.finish: the calls that hand the step
    # to the device (input transfers, feedback scatter, model_step,
    # sampler, rng-key gather); they return before the device has run
    # them, but may wait on the runtime for the step before
    "serve.enqueue",
    # child of serve.step: the host's record of the sampled tokens (lane
    # bookkeeping; speculative accept/rollback and its stream callbacks)
    "serve.finish",
    # one step's blocking token sync, plus the stream callbacks of plain
    # steps: inside serve.finish when the pipeline retires the step
    # before, alone at idle and at exit
    "serve.retire",
    "serve.pump",      # FrontEnd.pump: arrivals into the scheduler
    "serve.wait",      # FrontEnd.wait: idle until the next arrival
)

# placeholder for a sampled-but-not-yet-synced token in host bookkeeping
# (scheduler ``out`` lists and the output streams); never fed to the model
# -- dispatch overrides decode feedback with the device-resident value
PENDING = -1


class StepLoop:
    """One serving session's back-end: drives a :class:`Scheduler` fed by
    a :class:`FrontEnd` until both are drained.

    Built by :meth:`ServeEngine.serve` (and through it by the closed-loop
    ``run()`` wrapper); owns the paged cache value, the per-slot device
    rng/temperature state, and the per-request output streams.
    ``overlap=False`` forces synchronous stepping (retire each step
    before planning the next) -- the bit-parity reference for the
    pipelined path, and automatic under ``spec`` (speculative decode).
    """

    def __init__(self, engine, frontend: FrontEnd, sched: Scheduler, cache,
                 kinds, stats: ServeStats, *, num_pages: int, page_size: int,
                 chunk: int, budget: int, reclaim: Optional[int] = None,
                 spec: Optional[Dict[str, Any]] = None, overlap: bool = True):
        self.eng = engine
        self.fe = frontend
        self.sched = sched
        self.cache = cache
        self.kinds = kinds
        self.stats = stats
        self.num_pages = num_pages
        self.page_size = page_size
        self.chunk = chunk
        self.budget = budget
        self.reclaim = reclaim
        self.spec = spec
        self.overlap = bool(overlap) and spec is None
        n = sched.n_slots
        self.outputs: Dict[int, List[int]] = {}
        # per-slot device sampling state: rng key + temperature, written at
        # admission (a requeued request re-seeds identically -- it emitted
        # nothing, so no rng splits were ever consumed)
        self._keys = jnp.zeros((n, 2), jnp.uint32)
        self._temps = jnp.zeros((n,), jnp.float32)
        self._last_tok = jnp.zeros((n,), jnp.int32)  # last step's samples
        # in-flight retirement record: (device token vector, emit rows)
        self._inflight: Optional[Tuple[Any, List[tuple]]] = None
        self._last_t: Dict[int, float] = {}   # rid -> last host-visible time

    # ------------------------------------------------------------ the loop
    def run(self) -> None:
        """Drain the front-end and scheduler: pump arrivals, step, idle
        between future arrivals.  Ends when no request is scheduled,
        queued, or running."""
        try:
            while True:
                with TraceAnnotation("serve.pump"):
                    now, released = self.fe.pump(self.sched)
                for req in released:
                    if req.prompt_len + req.n_new > self.eng.max_len:
                        raise ValueError(
                            f"request {req.rid}: {req.prompt_len}+"
                            f"{req.n_new} tokens exceeds "
                            f"max_len={self.eng.max_len}")
                if not self.sched.has_work:
                    if self.fe.n_scheduled == 0:
                        break
                    self._retire()        # flush streams before idling
                    with TraceAnnotation("serve.wait"):
                        self.fe.wait(now)
                    continue
                self.step(now)
        finally:
            self._retire()

    def step(self, now: float) -> None:
        """One engine step: admit, plan, dispatch, sample, account."""
        with TraceAnnotation("serve.step") as span:
            self._step(now, span)

    def _step(self, now: float, span) -> None:
        eng, sched, stats, spec = self.eng, self.sched, self.stats, self.spec
        k = spec["k"] if spec else 0
        W = max(self.chunk, k + 1) if spec else self.chunk
        if self.reclaim is not None:
            stats.reclaimed_pages += len(
                sched.reclaim_out_of_window(self.reclaim))
        # ---- admission: a request joins when its first chunk fits
        fresh = []
        with TraceAnnotation("serve.admit"):
            while (adm := sched.try_admit_chunked(self.chunk)) is not None:
                req, slot, pages = adm
                fresh += pages
                self._admit(req, slot, now)
        if not sched.running_slots():
            raise paged_kv.PagesExhausted(
                "queued request cannot ever be admitted: pool of "
                f"{self.num_pages} pages (page_size={self.page_size}) is "
                "too small for its first chunk + decode headroom")
        t0 = self.fe.now()
        with TraceAnnotation("serve.plan"):
            plan = sched.plan_step(self.chunk, self.budget, draft_k=k)
        # a request admitted above may have been preempted inside this very
        # plan_step: its admission pages are back on the free list (possibly
        # re-allocated -- then they are in plan["fresh"] under the new
        # owner), so drop the stale aliases from the scrub set
        drop = set(plan["freed"])
        fresh = [p for p in fresh if p not in drop] + plan["fresh"]
        # scrub unconditionally: admission pages must be sentinel-clean
        # before any later step writes chunks into them, even if this step
        # is abandoned below.  The draft cache shares the block tables, so
        # it scrubs the same pages.
        with TraceAnnotation("serve.scrub"):
            self.cache = paged_kv.scrub_pages(self.cache, self.kinds,
                                              fresh)
            if spec:
                spec["cache"] = paged_kv.scrub_pages(
                    spec["cache"], self.kinds, fresh)
        if not plan["sample"] and not plan["chunked"]:
            # every planned slot was preempted: nothing dispatched
            self._count(span, plan, 0, len(fresh))
            return
        # pure-decode steps run the (R, 1) column slice -- a full-width
        # step would burn masked lanes per slot once every prompt is in.
        # jit variants stay bounded per (max_slots, chunk, pool shape[,
        # draft_k]): mixed/verify width + pure-decode width, still
        # independent of prompt lengths and arrival pattern.
        spec_lanes = {i: c for i, c in plan["spec"].items() if c > 1}
        w = W if (plan["chunked"] or spec_lanes) else 1
        tokens = plan["tokens"]
        if spec and (plan["chunked"] or plan["spec"]):
            # draft pass: mirrors prompt chunks into the draft cache, feeds
            # every decode lane's feedback token, and proposes each
            # speculating lane's draft tokens, which fill the placeholder
            # verify columns (engine._draft_propose documents the pass)
            with TraceAnnotation("serve.draft"):
                drafts = eng._draft_propose(spec, plan, sched, spec_lanes,
                                            W if plan["chunked"] else 2)
            for i, cols in spec_lanes.items():
                tokens[i, 1:cols] = drafts[i][:cols - 1]
        with TraceAnnotation("serve.dispatch"):
            toks_np = tokens[:, :w]
            pos_np = plan["positions"][:, :w]
            tables_np = sched.tables.as_array()
            # decode feedback stays exact: the host's view of these tokens
            # is a PENDING placeholder (plain mode never syncs values into
            # the scheduler, pipelined or not), the device value is
            # authoritative.  Spec mode records real values and skips this.
            feed = (np.asarray(plan["decode"], np.int32)
                    if spec is None and plan["decode"] else None)
            with TraceAnnotation("serve.enqueue"):
                tok_in = jnp.asarray(toks_np)
                if feed is not None:
                    rows_d = jnp.asarray(feed)
                    tok_in = tok_in.at[rows_d, 0].set(
                        self._last_tok[rows_d])
                logits, self.cache = eng._model_step(
                    eng.params, tok_in, jnp.asarray(pos_np),
                    jnp.asarray(plan["slot_map"]), self.cache,
                    jnp.asarray(tables_np),
                    jnp.asarray(plan["logit_cols"]),
                    eng.act_bits, attn_impl=eng.attn_impl)
                # one device call samples every lane's candidate token(s)
                # and the rng key state for every possible acceptance
                # length
                toks, keys_seq = eng._sample_span(logits, self._keys,
                                                  self._temps)
        with TraceAnnotation("serve.finish"):
            if spec:
                emitted_step = self._finish_spec(plan, spec_lanes, tokens,
                                                 toks, keys_seq)
            else:
                emitted_step = self._finish_plain(plan, toks, keys_seq)
        dt = self.fe.now() - t0
        # chunk-carrying steps are prefill-side: their time AND their
        # sampled tokens (first tokens plus any decode lanes riding the
        # step) leave the decode rate, so decode_tok_per_s measures the
        # steady-state decode batch -- comparable across modes
        if plan["chunked"]:
            stats.prefill_s += dt
            stats.prefill_tokens += emitted_step
        else:
            stats.decode_s += dt
        self._count(span, plan, w, len(fresh))
        stats.steps += 1

    def _count(self, span, plan, width: int, fresh_pages: int) -> None:
        """Count one step once, as the ``serve.step`` span's args and into
        :class:`ServeStats`, so the trace and the counters cannot disagree.
        ``width`` is 0 when nothing was dispatched."""
        sched, stats = self.sched, self.stats
        rows = sched.n_slots
        prompt = sum(plan["chunked"].values())
        decode = sum(plan["spec"].get(i, 1) for i in plan["decode"])
        in_use = self.num_pages - 1 - sched.allocator.n_free
        # the attention walk of a full-attention layer (of a windowed one
        # when every layer shares the window): the kernel's own live_pages
        kv_pages = kv_table = 0
        if width:
            nb = sched.tables.blocks_per_seq
            _, walked = live_pages(plan["positions"][:, :width],
                                   page_size=self.page_size, n_blocks=nb,
                                   window=self.reclaim)
            kv_pages, kv_table = int(walked.sum()), rows * nb
        span.set_metadata(
            step=stats.steps, rows=rows, width=width, prompt_tokens=prompt,
            decode_tokens=decode, slots=rows * width,
            fresh_pages=fresh_pages, pages_in_use=in_use,
            requeued=len(plan["requeued"]), kv_pages=kv_pages,
            kv_pages_table=kv_table)
        stats.kv_pages_walked += kv_pages
        stats.kv_pages_table += kv_table
        stats.requeues += len(plan["requeued"])
        stats.chunk_prefill_tokens += prompt
        stats.step_slots += rows * width
        stats.step_tokens += prompt + decode
        stats.pages_in_use = in_use
        stats.peak_pages = max(stats.peak_pages, in_use)

    # ---------------------------------------------------------- inner steps
    def _admit(self, req: Request, slot: int, now: float) -> None:
        rid = req.rid
        if rid not in self.stats.queue_wait_s:
            arrival = self.fe.arrival_s.get(rid)
            if arrival is not None:
                self.stats.queue_wait_s[rid] = now - arrival
        self.fe.note_admitted(rid)
        self._keys = self._keys.at[slot].set(jax.random.PRNGKey(req.seed))
        self._temps = self._temps.at[slot].set(
            jnp.float32(req.temperature))

    def _finish_plain(self, plan, toks, keys_seq) -> int:
        """Value-free advance for the plain (non-speculative) step: record
        PENDING placeholders, queue the device token vector for
        retirement, retire the previous step's (pipelined) or this one's
        (synchronous)."""
        sched, stats = self.sched, self.stats
        n = sched.n_slots
        m = np.zeros((n,), np.int32)          # rng splits consumed per lane
        rows = []
        for i in plan["sample"]:
            s = sched.slot(i)
            rid = s.req.rid
            m[i] = 1
            out = self.outputs.setdefault(rid, [])
            idx = len(out)
            out.append(PENDING)
            first = not s.out
            if first:
                stats.ttft_steps[rid] = stats.steps + 1
                done = sched.record_first(i, PENDING)
            else:
                done = sched.record(i, PENDING)
            rows.append((i, rid, idx, first, done))
            stats.tokens_out += 1
        with TraceAnnotation("serve.enqueue"):
            self._keys = keys_seq[jnp.arange(n), jnp.asarray(m)]
            tok_dev = toks[:, 0]
        self._last_tok = tok_dev
        pending = (tok_dev, rows)
        if self.overlap:
            prev, self._inflight = self._inflight, pending
            if prev is not None:
                self._retire_record(prev)
        else:
            self._retire_record(pending)
        return len(rows)

    def _finish_spec(self, plan, spec_lanes, tokens, toks, keys_seq) -> int:
        """Synchronous accept/rollback for a speculative verify step: walk
        each lane's candidate span (host control flow needs the values),
        keep the longest draft/sample agreement prefix plus the corrected
        token.  Every emitted token comes from the same logits row + rng
        split plain decode would produce (rejected columns never consume
        rng -- the sampler returned the key state per acceptance length),
        so acceptance changes speed, never output."""
        sched, stats, spec = self.sched, self.stats, self.spec
        n = sched.n_slots
        with TraceAnnotation("serve.retire"):
            vals = np.asarray(toks)           # (R, C): one transfer
        now = self.fe.now()
        m = np.zeros((n,), np.int32)
        emitted_step = 0
        for i in plan["sample"]:
            s = sched.slot(i)
            rid = s.req.rid
            out = self.outputs.setdefault(rid, [])
            if not s.out:                     # the request's first token
                tok = int(vals[i, 0])
                m[i] = 1
                out.append(tok)
                stats.tokens_out += 1
                emitted_step += 1
                stats.ttft_steps[rid] = stats.steps + 1
                done = sched.record_first(i, tok)
                self._emit(rid, len(out) - 1, tok, now, True, done)
                continue
            cols = plan["spec"].get(i, 1)
            emitted = []
            for j in range(cols):
                tok = int(vals[i, j])
                emitted.append(tok)
                if j + 1 >= cols or tokens[i, j + 1] != tok:
                    break
            m[i] = len(emitted)
            if cols > 1:
                stats.record_acceptance(rid, cols - 1, len(emitted) - 1)
            done = False
            for tok in emitted:
                out.append(tok)
                stats.tokens_out += 1
                done = sched.record(i, tok)
                self._emit(rid, len(out) - 1, tok, now, False, done)
            emitted_step += len(emitted)
            if done:
                spec["frontier"].pop(i, None)  # slot may be re-admitted
            elif cols > 1:
                # pages past the acceptance point backed only rejected
                # draft positions: return them now; the draft write cursor
                # clamps back too (rejected-token KV is overwritten in
                # place by the stream)
                sched.rollback_speculation(i)
                f = spec["frontier"]
                f[i] = min(f.get(i, s.pos), s.pos)
        if spec_lanes:
            stats.spec_steps += 1
        with TraceAnnotation("serve.enqueue"):
            self._keys = keys_seq[jnp.arange(n), jnp.asarray(m)]
        return emitted_step

    # ----------------------------------------------------------- retirement
    def _retire(self) -> None:
        """Retire the in-flight step, if any (loop exit / idle / error)."""
        prev, self._inflight = self._inflight, None
        if prev is not None:
            self._retire_record(prev)

    def _retire_record(self, pending) -> None:
        """Sync one step's device token vector -- the only blocking
        device->host transfer per step -- and make its tokens
        host-visible: backfill PENDING output slots, fire stream
        callbacks, stamp latency."""
        tok_dev, rows = pending
        with TraceAnnotation("serve.retire"):
            vals = np.asarray(tok_dev)
            now = self.fe.now()
            for slot, rid, idx, first, done in rows:
                tok = int(vals[slot])
                self.outputs[rid][idx] = tok
                self._emit(rid, idx, tok, now, first, done)

    def _emit(self, rid: int, idx: int, tok: int, now: float, first: bool,
              done: bool) -> None:
        """One token became host-visible: latency stats + stream callback."""
        stats = self.stats
        arrival = self.fe.arrival_s.get(rid)
        if first:
            if arrival is not None:
                stats.ttft_s[rid] = now - arrival
        else:
            prev_t = self._last_t.get(rid)
            if prev_t is not None:
                stats.itl_s.append(now - prev_t)
        self._last_t[rid] = now
        if done:
            if arrival is not None:
                stats.e2e_s[rid] = now - arrival
            self._last_t.pop(rid, None)
        self.fe.emit(rid, idx, tok)
