"""The system under test: build the engine, warm it up, drive one window.

The window drives ``ServeEngine.serve`` with a :class:`WindowFrontEnd`, a
``FrontEnd`` whose ``pump`` (called by the step loop once per iteration)
opens the measured window's trace span once the ramp has passed and ends
the session by raising :class:`WindowClosed` once the window has closed.
Serving starts ``ramp_s`` (a traffic file's key) before the window opens,
so the window sees the mix in its steady state, not its start.  Clients
stamp each streamed token from the ``on_token`` callback;
``note_admitted`` (the loop's admission hook) stamps admission, which
gives the front end's queue wait; ``pump`` also records every running
request's position, from which :func:`step_entries` recovers the tokens
each step processed.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic as traffic_mod
from bench import weights
from repro.kernels.pack import PackedWeight
from repro.models import LM
from repro.quant.apply import apply_policy_packed
from repro.quant.policy import QuantMode, QuantPolicy
from repro.serve import FrontEnd, ServeEngine, paged_kv

# a program compiled, or loaded from the persistent cache
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")
WINDOW = "bench_window"


class WindowClosed(Exception):
    """Raised from the front end's pump when the window has closed."""


def program_policy(arch, model, cfg: dict):
    """The configuration's policy as the program's ``QuantPolicy`` over
    its graph, plus that graph; ``arch`` (the configuration's
    architecture module) maps each graph site to its role."""
    graph = model.graph(seq_len=1, batch=1,
                        max_groups=cfg["policy"]["max_groups"])
    bits = arch.policy_bits(cfg)
    by_site = {l.name: arch.site_role(l.name) for l in graph.layers}
    if sorted(by_site.values()) != sorted(bits):
        raise RuntimeError(f"program graph sites {sorted(by_site)} do not "
                           f"match the configuration's {sorted(bits)}")
    act = float(cfg["policy"]["act_bits"])
    return QuantPolicy(mode=QuantMode.QUANT,
                       weight_bits={n: bits[s] for n, s in by_site.items()},
                       act_bits={n: act for n in by_site}), graph


def build_engine(arch, cfg: dict, traffic: dict, seed: int):
    """Random bf16 weights from ``seed`` in the layout of ``arch`` (the
    configuration's architecture module), packed by the program under
    the configuration's policy in one jitted call, handed to
    ``ServeEngine``.

    The engine has no entry for an already-packed tree: it is given the
    tree with no policy, and the policy's activation QBNs are set on it
    as ``ServeEngine(policy=...)`` would have set them."""
    model = LM(arch.lm_config(cfg))
    policy, graph = program_policy(arch, model, cfg)
    want = jax.eval_shape(lambda k: model.init(k, dtype=jnp.bfloat16),
                          jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda k: arch.program_tree(k, cfg),
                         jax.random.PRNGKey(0))
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("the program's parameter layout changed: "
                           f"{want} vs the benchmark's {got}")
    build = jax.jit(lambda k: apply_policy_packed(
        arch.program_tree(k, cfg), graph, policy))
    params = jax.block_until_ready(build(weights.base_key(seed)))
    buckets = {name for leaf in jax.tree.leaves(
        params, is_leaf=lambda x: isinstance(x, PackedWeight))
        if isinstance(leaf, PackedWeight) for name, _ in leaf.buckets}
    if not {"int2", "int4", "int8", "full"} <= buckets:
        raise RuntimeError(f"policy misses a packed bucket: {buckets}")
    e = cfg["engine"]
    eng = ServeEngine(model, params, max_len=traffic["engine"]["max_len"],
                      attn_impl=e["attn_impl"], kv_bits=e["kv_bits"])
    eng.weight_store = e["weight_store"]
    eng.act_bits = model.block_act_bits(
        graph, [policy.act_bits[l.name] for l in graph.layers])
    return eng


def serve_kwargs(cfg: dict, traffic: dict) -> dict:
    t = traffic["engine"]
    return {"page_size": cfg["engine"]["page_size"],
            "max_slots": t["max_slots"], "chunk_tokens": t["chunk_tokens"],
            "token_budget": t["token_budget"],
            "overlap": cfg["engine"]["overlap"]}


def warm_up(eng, cfg: dict, traffic: dict, vocab: int) -> None:
    """Compile every program the window runs: both ``model_step`` widths
    and the sampler, through a short ``serve``; then the step loop's
    eager ops whose shapes follow the step's contents -- the page scrub
    (one shape per number of fresh pages) and the decode-feedback
    scatter (one per number of decode rows, at both widths)."""
    kw = serve_kwargs(cfg, traffic)
    R, chunk, ps = kw["max_slots"], kw["chunk_tokens"], kw["page_size"]
    fe = FrontEnd()
    rng = np.random.default_rng(0)
    for i in range(R):
        fe.submit((rng.integers(0, vocab, chunk + 1 + i, dtype=np.int32),
                   2 + i % 3))
    eng.serve(fe, **kw)
    kinds = eng.model.cfg.cache_kinds()
    blocks = paged_kv.pages_needed(eng.max_len, ps)
    num_pages = R * blocks + 1
    pos = jnp.full((eng.model.cfg.n_repeat, num_pages, ps),
                   paged_kv.POS_SENTINEL, jnp.int32)
    cache = tuple({"pos": pos} for _ in kinds)
    for n in range(1, scrub_max(R, chunk, ps, num_pages) + 1):
        jax.block_until_ready(
            paged_kv.scrub_pages(cache, kinds, list(range(1, n + 1))))
    last = jnp.zeros((R,), jnp.int32)
    for w in sorted({1, chunk}):
        toks = np.zeros((R, w), np.int32)
        for n in range(1, R + 1):
            rows = jnp.asarray(np.arange(n, dtype=np.int32))
            jax.block_until_ready(
                jnp.asarray(toks).at[rows, 0].set(last[rows]))


def scrub_max(R: int, chunk: int, ps: int, num_pages: int) -> int:
    """Most pages one step can scrub: each lane takes either the pages of
    its first chunk (admitted this step: the chunk it then runs needs no
    more) or those of one chunk that may straddle a page boundary (one
    page more), or one page for a decode token."""
    per_lane = -(-chunk // ps) + 1
    return min(num_pages - 1, R * per_lane)


class WindowFrontEnd(FrontEnd):
    """The program's ``FrontEnd`` with the window's hooks: ``pump`` records
    the running requests' positions, opens the host span ``bench_window``
    (which the trace reduction clips to) at the first step from ``t0``,
    and closes the window at ``t_end``; admission is stamped with the
    step's pump time, as ``ServeStats.queue_wait_s`` stamps it.  With
    ``drained`` the session serves on past ``t_end`` (the span closed,
    nothing new sent) until ``drained()`` holds or ``drain_s`` more
    seconds have passed."""

    def __init__(self, t0: float, t_end: float, drained=None,
                 drain_s: float = 0.0):
        super().__init__()
        self.t0 = t0
        self.t_end = t_end
        self.drained = drained
        self.drain_s = drain_s
        self.admitted_s = {}
        self.snapshots = []
        self._pump_now = None
        self._span = None
        self._span_done = False

    def pump(self, sched):
        self.snapshots.append((self._clock(), {
            sched.slot(i).req.rid: sched.slot(i).pos
            for i in sched.running_slots()}))
        now, released = super().pump(sched)
        if now >= self.t_end:
            self.close_span()
            if self.drained is None or self.drained() \
                    or now >= self.t_end + self.drain_s:
                raise WindowClosed()
        if self._span is None and not self._span_done and now >= self.t0:
            self._span = jax.profiler.TraceAnnotation(WINDOW)
            self._span.__enter__()
        self._pump_now = now
        return now, released

    def close_span(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
        self._span, self._span_done = None, True

    def note_admitted(self, rid):
        self.admitted_s.setdefault(rid, self._pump_now)
        super().note_admitted(rid)


class Client:
    """Open- or closed-loop client: submits requests, stamps tokens."""

    def __init__(self, fe, pool, traffic: dict):
        self.fe = fe
        self.pool = pool
        self.closed = traffic["loop"] == "closed"
        self.recs = {}

    def submit(self, at=None, first_of=None):
        """Submit the pool's next request; ``first_of=(i, n)`` keeps
        ``(i + 1/2) / n`` of its output length, rounded up (client ``i``'s
        first request in a closed loop of ``n``)."""
        prompt, n_new = next(self.pool)
        if first_of is not None:
            i, n = first_of
            n_new = max(1, -(-n_new * (2 * i + 1) // (2 * n)))
        req = self.fe.submit((prompt, n_new), at=at, on_token=self.on_token)
        self.recs[req.rid] = {"rid": req.rid, "due": self.fe.arrival_s[
            req.rid], "prompt": prompt, "prompt_len": len(prompt),
            "n_new": n_new, "times": [], "tokens": []}

    def on_token(self, rid, index, token):
        rec = self.recs[rid]
        rec["times"].append(time.monotonic())
        rec["tokens"].append(int(token))
        if self.closed and index == rec["n_new"] - 1 \
                and time.monotonic() < self.fe.t_end:
            self.submit()

    def prefills_done(self) -> bool:
        """Every request sent has streamed its first token."""
        return all(r["times"] for r in self.recs.values())


def run_window(eng, cfg: dict, traffic: dict, seed: int, seconds: float,
               trace_dir=None):
    """Serve the seed's traffic from ``ramp_s`` before the window and
    through it (traced into ``trace_dir`` when given).

    A closed loop's clients all start at once, the first request of
    client ``i`` of ``n`` keeping ``(i + 1/2) / n`` of its output length,
    so completions, and the next round's arrivals, are spread evenly from
    the start.  Returns the client's records, the window's bounds,
    admission stamps, position snapshots, and the programs compiled or
    loaded and jit traces made while serving (ramp and window).  A
    traffic file's ``drain_s`` serves on past the window, sending
    nothing, until every prompt sent has streamed its first token (or
    for ``drain_s`` seconds at most): ``e2e.prompt_tok_s`` needs when
    each prefill ended."""
    kw = serve_kwargs(cfg, traffic)
    pool = traffic_mod.requests(traffic, seed, cfg["vocab_size"])
    ramp = float(traffic.get("ramp_s", 0.0))
    compiles = []

    def listener(event, duration, **_):
        if event in COMPILE_EVENTS:
            compiles.append((event, duration))

    traced = trace_dir is not None
    if traced:
        jax.profiler.start_trace(str(trace_dir))
    t_start = time.monotonic() + 0.05
    t0 = t_start + ramp
    t1 = t0 + seconds
    fe = WindowFrontEnd(t0, t1)
    client = Client(fe, pool, traffic)
    if traffic.get("drain_s"):
        fe.drained, fe.drain_s = client.prefills_done, float(
            traffic["drain_s"])
    if client.closed:
        n = traffic["clients"]
        for i in range(n):
            client.submit(at=t_start, first_of=(i, n))
    else:
        for off in traffic_mod.arrival_offsets(traffic, seed,
                                               ramp + seconds):
            client.submit(at=t_start + off)
    counts0 = dict(eng.trace_counts)
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        try:
            eng.serve(fe, **kw)
        except WindowClosed:
            pass
    finally:
        fe.close_span()
        jax.monitoring.unregister_event_duration_listener(listener)
        if traced:
            jax.profiler.stop_trace()
    traced_new = {k: v - counts0.get(k, 0) for k, v in
                  eng.trace_counts.items() if v != counts0.get(k, 0)}
    return {"reqs": list(client.recs.values()), "t0": t0, "t1": t1,
            "admitted_s": dict(fe.admitted_s),
            "snapshots": fe.snapshots,
            "compiles_in_window": len(compiles),
            "traces_in_window": sum(traced_new.values()),
            "traced_new": traced_new}


def step_entries(win: dict):
    """The steps dispatched in the window, each a list of ``(start, n,
    samples)`` (see ``bench.flops``), from consecutive position snapshots:
    a request seen at ``p`` and then at ``q`` processed ``q - p`` tokens
    from ``p``; one first seen at ``q`` was admitted and processed ``q``
    from 0; one gone processed what remained of its
    ``prompt_len + n_new - 1`` positions if it finished, else nothing
    (preempted)."""
    recs = {r["rid"]: r for r in win["reqs"]}
    snaps = win["snapshots"]
    steps = []
    for (t, prev), (_, cur) in zip(snaps, snaps[1:]):
        if not win["t0"] <= t <= win["t1"]:
            continue
        entries = []
        for rid in set(prev) | set(cur):
            r = recs[rid]
            start = prev.get(rid, 0)
            if rid in cur:
                end = cur[rid]
            elif len(r["times"]) == r["n_new"]:
                end = r["prompt_len"] + r["n_new"] - 1
            else:
                continue
            if end > start:
                entries.append((start, end - start,
                                end >= r["prompt_len"]))
        if entries:
            steps.append(entries)
    return steps
