"""What the serve path writes into a profiler trace, and the counters
``ServeStats`` keeps beside it.

* the step loop's host spans (``serve/step_loop.py`` ``SPANS``) read back
  from a real ``jax.profiler`` trace: one ``serve.step`` per dispatched
  step, its args summing to the ``ServeStats`` counters, every child
  inside its parent, every device enqueue inside the phase that makes it;
* the step-shape counters at exact values for a known plan, and the
  paged kernel's walk recomputed from each step's positions;
* ``engine.stats`` outlives a session ended by an exception;
* every weight store's dequant carries the ``dequant`` scope into the
  op metadata of the lowered ``model_step``.
"""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models import LM
from repro.quant.policy import QuantPolicy
from repro.serve import FrontEnd, ServeEngine, paged_kv
from repro.models.transformer import POS_SENTINEL
from repro.serve.step_loop import SPANS

KEY = jax.random.PRNGKey(0)
MIXED = [(3, 5), (7, 4), (5, 6), (9, 3), (2, 5), (6, 4)]
CHILDREN = ("serve.admit", "serve.plan", "serve.scrub", "serve.draft",
            "serve.dispatch", "serve.finish")


def _requests(vocab, shapes, seed=3):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, vocab, size=s).astype(np.int32), n)
            for s, n in shapes]


def _engine(**kw):
    cfg = ARCHS["internlm2-20b"].smoke
    model = LM(cfg)
    return cfg, ServeEngine(model, model.init(KEY), max_len=32,
                            attn_impl="ref", **kw)


class TickClock:
    """Virtual clock: every reading advances a tick, ``sleep`` jumps."""

    def __init__(self, tick=1e-3):
        self.t = 0.0
        self.tick = tick

    def __call__(self):
        self.t += self.tick
        return self.t

    def sleep(self, dt):
        self.t += max(dt, self.tick)


def _serve_spans(tmp_path, **serve_kw):
    """Serve staggered arrivals under the profiler, the last one after the
    loop has drained (so it waits); return the stats and the ``serve.*``
    host events as (name, start, end, args)."""
    cfg, eng = _engine()
    clk = TickClock()
    fe = FrontEnd(clock=clk, sleep=clk.sleep)
    reqs = _requests(cfg.vocab, MIXED, seed=11)
    for i, r in enumerate(reqs):
        fe.submit(r, at=0.004 * i if i < len(reqs) - 1 else 0.5)
    with jax.profiler.trace(str(tmp_path)):
        res = eng.serve(fe, page_size=4, max_slots=4, **serve_kw)
    path = next(pathlib.Path(tmp_path).rglob("*.xplane.pb"))
    prof = jax.profiler.ProfileData.from_file(str(path))
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
              dict(e.stats))
             for p in prof.planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events
             if e.name.startswith("serve.")]
    return res["stats"], spans


@pytest.mark.parametrize("serve_kw", [
    pytest.param({}, id="overlapped"),
    pytest.param({"overlap": False}, id="synchronous"),
    pytest.param({"speculative": True, "draft_k": 3}, id="speculative"),
])
def test_step_spans_add_up_to_serve_stats(tmp_path, serve_kw):
    stats, spans = _serve_spans(tmp_path, **serve_kw)
    names = {n for n, *_ in spans}
    assert names <= set(SPANS)
    assert names >= set(SPANS) - {"serve.draft"}
    assert ("serve.draft" in names) == bool(serve_kw.get("speculative"))
    steps = [a for n, _, _, a in spans if n == "serve.step"]
    ran = [a for a in steps if a["slots"] > 0]
    assert len(ran) == stats.steps > 0
    for name in ("serve.dispatch", "serve.finish"):
        assert sum(1 for n, *_ in spans if n == name) == stats.steps, name
    assert sorted(a["step"] for a in ran) == list(range(stats.steps))
    assert sum(a["prompt_tokens"] for a in steps) == \
        stats.chunk_prefill_tokens == sum(s for s, _ in MIXED)
    assert sum(a["slots"] for a in steps) == stats.step_slots
    assert sum(a["prompt_tokens"] + a["decode_tokens"] for a in steps) == \
        stats.step_tokens
    assert sum(a["requeued"] for a in steps) == stats.requeues
    assert all(a["slots"] == a["rows"] * a["width"] for a in steps)
    assert max(a["pages_in_use"] for a in steps) == stats.peak_pages
    assert sum(a["kv_pages"] for a in steps) == stats.kv_pages_walked > 0
    assert sum(a["kv_pages_table"] for a in steps) == stats.kv_pages_table
    def inside(name, parents):
        iv = [(s, e) for n, s, e, _ in spans if n in parents]
        return all(any(ps <= s and e <= pe for ps, pe in iv)
                   for n, s, e, _ in spans if n == name)

    assert all(inside(name, {"serve.step"}) for name in CHILDREN)
    # device calls are apart from the host work around them: two enqueues
    # per dispatched step (model_step + sampler; the rng-key gather)
    assert inside("serve.enqueue", {"serve.dispatch", "serve.finish"})
    assert sum(1 for n, *_ in spans if n == "serve.enqueue") == \
        2 * stats.steps
    # the blocking token sync of a step: one per dispatched step
    assert sum(1 for n, *_ in spans if n == "serve.retire") == stats.steps


def test_step_shape_counters_for_a_known_plan():
    """One 5-token prompt, chunk 4, 3 new tokens, 2 lanes: a 4-token chunk
    and a 1-token chunk at width 4, then two decode steps at width 1 --
    7 real tokens in 2*4 + 2*4 + 2*1 + 2*1 = 20 slots."""
    cfg, eng = _engine()
    (prompt, _), = _requests(cfg.vocab, [(5, 3)])
    stats = eng.run([(prompt, 3)], page_size=4, max_slots=2,
                    chunk_tokens=4)["stats"]
    assert stats.steps == 4
    assert (stats.step_slots, stats.step_tokens) == (20, 7)
    assert stats.step_fill == pytest.approx(7 / 20)
    assert stats.pages_in_use == 0 and stats.peak_pages == 2
    # the kernel walks page 0 (positions 0..3), then pages 0-1 (4, 5, 6);
    # the idle lane walks nothing.  The table is 2 rows x 8 blocks (32 / 4)
    assert stats.kv_pages_walked == 1 + 2 + 2 + 2
    assert stats.kv_pages_table == 4 * 2 * 8


def test_kv_pages_are_the_walk_of_each_dispatched_step(tmp_path,
                                                       monkeypatch):
    """Each ``serve.step``'s ``kv_pages`` is the pages the paged kernel
    walks, recomputed from the positions ``model_step`` was given: a lane
    walks its table up to the page of its highest real position, an idle
    lane nothing."""
    cfg, eng = _engine()
    calls = []
    step = eng._model_step

    def spy(params, tokens, positions, slot_map, cache, tables, *a, **kw):
        calls.append((np.asarray(positions), np.asarray(tables).shape[1]))
        return step(params, tokens, positions, slot_map, cache, tables, *a,
                    **kw)

    monkeypatch.setattr(eng, "_model_step", spy)
    with jax.profiler.trace(str(tmp_path)):
        stats = eng.run(_requests(cfg.vocab, MIXED), page_size=4,
                        max_slots=4, chunk_tokens=4)["stats"]
    prof = jax.profiler.ProfileData.from_file(
        str(next(pathlib.Path(tmp_path).rglob("*.xplane.pb"))))
    steps = sorted((dict(e.stats) for p in prof.planes
                    if p.name.startswith("/host:") for line in p.lines
                    for e in line.events if e.name == "serve.step"),
                   key=lambda a: a["step"])
    assert len(steps) == len(calls) == stats.steps
    for args, (pos, nb) in zip(steps, calls):
        walked = sum(int(row[row != POS_SENTINEL].max()) // 4 + 1
                     for row in pos if (row != POS_SENTINEL).any())
        assert args["kv_pages"] == walked
        assert args["kv_pages_table"] == pos.shape[0] * nb
    assert stats.kv_pages_walked == sum(a["kv_pages"] for a in steps)


class _PumpRaises(FrontEnd):
    """Ends the session from ``pump`` after ``after`` pumps, as a
    benchmark window does."""

    def __init__(self, after):
        super().__init__()
        self.after = after
        self.pumps = 0

    def pump(self, sched):
        self.pumps += 1
        if self.pumps > self.after:
            raise RuntimeError("window closed")
        return super().pump(sched)


def test_stats_survive_an_exception_from_pump():
    cfg, eng = _engine()
    fe = _PumpRaises(after=4)
    for r in _requests(cfg.vocab, MIXED[:3]):
        fe.submit(r)
    with pytest.raises(RuntimeError, match="window closed"):
        eng.serve(fe, page_size=4, max_slots=4)
    stats = eng.stats
    assert stats.steps > 0
    assert 0 < stats.step_tokens <= stats.step_slots
    assert stats.n_requests == 3


@pytest.mark.parametrize("store", ["packed", "int8"])
def test_model_step_hlo_carries_the_dequant_scope(store):
    """The scope names the dequant ops of a packed (bucketed sub-byte)
    and of a uniform int8 tree in the lowered step's op metadata."""
    cfg = ARCHS["internlm2-20b"].smoke
    model = LM(cfg)
    params = model.init(KEY)
    if store == "packed":
        graph = model.graph(seq_len=1, batch=1)
        eng = ServeEngine(model, params, weight_store="packed", max_len=32,
                          policy=QuantPolicy.uniform(graph, 4.0))
    else:
        eng = ServeEngine(model, model.quantize_params_int8(params),
                          max_len=32)
    R, W, ps = 2, 4, 4
    blocks = paged_kv.pages_needed(32, ps)
    cache = model.init_paged_cache(R, R * blocks + 1, ps)
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    text = eng._model_step.lower(
        eng.params, i32(R, W), i32(R, W), i32(R), cache, i32(R, blocks),
        i32(R), eng.act_bits, attn_impl="ref").as_text(
            dialect="hlo", debug_info=True)
    scoped = [l for l in text.splitlines() if "/dequant/" in l]
    assert scoped, "no op of the step carries the dequant scope"
