"""The serve path's spans and scopes read back from a trace: synthetic
planes for each reading and its ``None`` cases, and one real CPU trace of
the program at smoke size."""
import types

import pytest

from bench import spans as sp


def ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def plane(name, **lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=k.replace("_", " "), events=v)
        for k, v in lines.items()])


def step_args(prompt, decode, rows, width):
    return dict(step=0, rows=rows, width=width, prompt_tokens=prompt,
                decode_tokens=decode, slots=rows * width, fresh_pages=0,
                pages_in_use=0, requeued=0)


def synthetic():
    """Window [1000, 11000).  Device idle: [2800, 7000] (mid 4900, under
    serve.dispatch, itself under a runtime event), [8000, 10500] (mid
    9250, under no serve span), [0, 1000) lies outside the window."""
    host = plane("/host:CPU", python=[
        ev("bench_window", 1000, 10000),
        ev("serve.step", 500, 3000, **step_args(0, 4, 4, 1)),  # starts early
        ev("serve.step", 3600, 4000, **step_args(700, 2, 4, 256)),
        ev("serve.plan", 3700, 500),
        ev("serve.dispatch", 4300, 3000),
        ev("DeferredTpuAllocator::Allocate", 4800, 200),
        ev("serve.retire", 7400, 150),
        ev("serve.step", 11500, 500, **step_args(8, 0, 4, 256)),  # after
    ])
    dev = plane("/device:TPU:0",
                XLA_Ops=[ev("fusion.1", 500, 2300),
                         ev("%concatenate.2 = bf16[8,4] concatenate(...)",
                            7000, 600),
                         ev("%fusion.3 = bf16[2,4] fusion(...)", 7600, 400),
                         ev("%fusion.4 = bf16[8,4] fusion(...)", 10500,
                            1000)],
                XLA_Modules=[ev("jit_model_step(1)", 1200, 2000),
                             ev("jit_model_step(1)", 7000, 1000),
                             ev("jit_model_step(1)", 10400, 1200)])
    return [host, dev]


# the op metadata of the program's optimized HLO, as program_scopes reads it
SCOPES = {"jit_model_step(1)": {
    "concatenate.2": "jit(model_step)/while/body/dequant/concatenate",
    "fusion.3": "jit(model_step)/while/body/dot_general",
    "fusion.4": "jit(model_step)/dequant/convert_element_type",
    "fusion.1": "jit(model_step)/dequantize_logits"}}


def test_spans_are_the_serve_events_that_overlap_the_window():
    red = sp.reduce_spans(synthetic())
    assert red["window_ns"] == (1000, 11000)
    names = [s["name"] for s in red["spans"]]
    assert names == ["serve.step", "serve.step", "serve.plan",
                     "serve.dispatch", "serve.retire"]
    assert red["spans"][1]["args"]["prompt_tokens"] == 700


def test_idle_goes_to_the_innermost_serve_span():
    idle = sp.reduce_spans(synthetic())["idle_by_span"]
    # the runtime event under serve.dispatch does not take the gap
    assert idle["serve.dispatch"] == pytest.approx(4200 / 1e9)
    assert idle[sp.NO_SPAN] == pytest.approx(2500 / 1e9)
    assert set(idle) == {"serve.dispatch", sp.NO_SPAN}


def test_step_fill_counts_the_steps_that_start_in_the_window():
    red = sp.reduce_spans(synthetic())
    assert sp.step_fill_pct(red) == pytest.approx(100 * 702 / 1024)


def test_host_idle_counts_host_work_spans_over_the_window():
    red = sp.reduce_spans(synthetic())
    assert sp.host_idle_pct(red, 10000 / 1e9) == pytest.approx(42.0)


def test_dequant_is_the_scoped_op_time_per_model_step():
    red = sp.reduce_spans(synthetic(), SCOPES)
    # concatenate.2 (600) + fusion.4 clipped at the window's end (500);
    # fusion.1 runs outside any program execution, and "dequantize_logits"
    # is no path segment "dequant"
    assert red["scoped_ops"] == {"dequant": pytest.approx(1100 / 1e9)}
    assert sp.dequant_ms(red, [2e-6, 1e-6]) == pytest.approx(1100 / 1e9
                                                             / 2 * 1e3)
    assert sp.reduce_spans(synthetic())["scoped_ops"] == {}   # no HLO


def test_idle_under_a_device_enqueue_is_not_host_work():
    """A dispatch that waits on the runtime inside ``serve.enqueue`` takes
    the gap from ``serve.dispatch``, and host idle leaves it out."""
    host, dev = synthetic()
    host.lines[0].events.append(ev("serve.enqueue", 4700, 2500))
    red = sp.reduce_spans([host, dev])
    assert dict(red["idle_by_span"]) == {
        "serve.enqueue": pytest.approx(4200 / 1e9),
        sp.NO_SPAN: pytest.approx(2500 / 1e9)}
    assert sp.host_idle_pct(red, 10000 / 1e9) == 0.0


def test_a_trace_without_spans_or_device_reads_none():
    host = plane("/host:CPU", python=[ev("bench_window", 0, 1000)])
    dev = plane("/device:TPU:0", XLA_Ops=[ev("fusion.1", 100, 200)],
                XLA_Modules=[ev("jit_model_step(1)", 100, 200)])
    red = sp.reduce_spans([host, dev])          # the parent program
    assert red["spans"] == [] and red["scoped_ops"] == {}
    assert sp.step_fill_pct(red) is None
    assert sp.host_idle_pct(red, 1e-6) is None
    assert sp.dequant_ms(red, [2e-7]) is None
    red = sp.reduce_spans([plane("/host:CPU", python=[
        ev("bench_window", 0, 1000),
        ev("serve.step", 10, 100, **step_args(5, 0, 4, 16))])])
    assert red["idle_by_span"] == {}                # no device plane
    assert sp.host_idle_pct(red, 1e-6) is None
    assert sp.dequant_ms(red, []) is None
    with pytest.raises(ValueError):
        sp.reduce_spans([plane("/device:TPU:0", XLA_Ops=[])])


def test_program_scopes_read_the_hlo_kept_in_a_real_trace(tmp_path):
    """The profiler keeps each program's optimized HLO in the trace; the
    ``dequant`` scope reaches the op metadata of what it compiles."""
    import jax
    import jax.numpy as jnp

    def step(x):
        with jax.named_scope("dequant"):
            w = x.astype(jnp.float32) * 2.0
        return w @ w.T

    f = jax.jit(step)
    x = jnp.ones((8, 8), jnp.int8)
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        f(x).block_until_ready()
    scopes = sp.program_scopes(next(tmp_path.rglob("*.xplane.pb"))
                               .read_bytes())
    ops = next(v for k, v in scopes.items() if k.startswith("jit_step("))
    assert any(sp.SCOPE_PATH.search(o) for o in ops.values())
    assert any(o.endswith("dot_general") and not sp.SCOPE_PATH.search(o)
               for o in ops.values())


def test_program_spans_on_a_cpu_trace_agree_with_step_entries(tmp_path):
    """The program at smoke size, traced on the CPU (no device plane): the
    ``serve.step`` args count the tokens the pump snapshots recover."""
    from bench import serving, trace_reduce
    from bench.tests.smoke import smoke_cell
    cell = smoke_cell("smoke-closed.json")
    cfg, traffic, seed = cell["config"], cell["traffic"], 2 ** 33 + 5
    eng = serving.build_engine(cell["arch"], cfg, traffic, seed)
    serving.warm_up(eng, cfg, traffic, cfg["vocab_size"])
    win = serving.run_window(eng, cfg, traffic, seed, 2.0, tmp_path)
    red = sp.reduce_spans(trace_reduce.load(tmp_path).planes)
    steps = sp.window_steps(red)
    entries = serving.step_entries(win)
    assert len(steps) == len(entries) > 0
    assert sum(a["prompt_tokens"] + a["decode_tokens"] for a in steps) == \
        sum(n for st in entries for _, n, _ in st)
    assert 0 < sp.step_fill_pct(red) <= 100
    assert sp.host_idle_pct(red, 2.0) is None
