"""End-to-end arithmetic: rates over the whole window, tails over all
requests, censored TTFT."""
import pytest

from bench import e2e
from bench.tests.conftest import fixture_json


def rec(rid, due, times, prompt_len=10, n_new=None):
    return {"rid": rid, "due": due, "times": list(times),
            "prompt_len": prompt_len,
            "n_new": len(times) if n_new is None else n_new}


def test_out_tok_s_counts_every_token_of_the_window():
    reqs = [rec(0, 0.0, [0.5, 1.0, 1.5, 2.5]), rec(1, 1.0, [1.2, 3.5])]
    # window [1, 3]: tokens at 1.0, 1.5, 2.5, 1.2
    assert e2e.out_tok_s(reqs, 1.0, 3.0) == pytest.approx(4 / 2.0)


def test_prompt_tok_s_counts_prefills_finished_in_window():
    """Each prompt's tokens are spread over the span from its sending to
    its first token; the part inside the window counts."""
    reqs = [rec(0, 0.0, [0.5, 1.5], prompt_len=100),    # before: 0
            rec(1, 0.0, [1.5, 2.0], prompt_len=30),     # 30 * 0.5 / 1.5
            rec(2, 1.5, [2.5], prompt_len=40),          # whole: 40
            rec(3, 2.0, [4.0], prompt_len=80),          # 80 * 1 / 2
            rec(4, 0.0, [], prompt_len=1000)]           # no first token
    assert e2e.prompt_tok_s(reqs, 1.0, 3.0) == pytest.approx(
        (10 + 40 + 40) / 2.0)


def test_ttft_is_from_due_time_and_censored_at_close():
    reqs = [rec(0, 1.0, [1.25]),          # 0.25
            rec(1, 2.0, []),              # no token: censored 3 - 2 = 1
            rec(2, 2.5, [3.4]),           # token after close: censored 0.5
            rec(3, 0.5, [1.0])]           # due before window: not counted
    assert sorted(e2e.ttft_samples(reqs, 1.0, 3.0)) == \
        pytest.approx([0.25, 0.5, 1.0])


def test_tail_is_over_all_samples():
    reqs = [rec(i, 1.0, [1.0 + (i + 1) / 1000]) for i in range(100)]
    vals = sorted(e2e.ttft_samples(reqs, 1.0, 3.0))
    assert len(vals) == 100
    assert e2e.ttft_p95_ms(reqs, 1.0, 3.0) == pytest.approx(
        1e3 * (vals[94] + 0.05 * (vals[95] - vals[94])))


def test_itl_keeps_only_gaps_inside_the_window():
    reqs = [rec(0, 0.0, [0.9, 1.1, 1.4, 3.2]), rec(1, 0.0, [2.0, 2.2])]
    assert sorted(e2e.itl_samples(reqs, 1.0, 3.0)) == \
        pytest.approx([0.2, 0.3])


def test_counts_in_flight_requests_are_not_done():
    reqs = [rec(0, 1.0, [1.5, 2.0], n_new=2), rec(1, 1.5, [2.0], n_new=3)]
    assert e2e.counts(reqs, 1.0, 3.0) == {"attempted": 2, "done": 1}


def test_closed_loop_first_round_is_spread_and_the_window_follows_the_ramp(
        smoke_cfg, dense):
    """Client ``i`` of ``n`` first asks for ``(i + 1/2) / n`` of its
    output length; the window opens ``ramp_s`` after the first
    submissions."""
    from bench import serving, traffic as traffic_mod
    t = dict(fixture_json("smoke-closed.json"), ramp_s=1.0)
    eng = serving.build_engine(dense, smoke_cfg, t, 9)
    serving.warm_up(eng, smoke_cfg, t, smoke_cfg["vocab_size"])
    win = serving.run_window(eng, smoke_cfg, t, 9, 1.0)
    assert win["compiles_in_window"] == 0 and win["traces_in_window"] == 0
    n = t["clients"]
    pool = traffic_mod.requests(t, 9, smoke_cfg["vocab_size"])
    first = [next(pool)[1] for _ in range(n)]
    got = [r["n_new"] for r in sorted(win["reqs"], key=lambda r: r["rid"])]
    assert got[:n] == [max(1, -(-o * (2 * i + 1) // (2 * n)))
                       for i, o in enumerate(first)]
    assert all(r["due"] <= win["t0"] - 0.99 for r in win["reqs"][:n])
    assert abs(win["t1"] - win["t0"] - 1.0) < 1e-9


def test_drain_serves_past_the_window_until_every_prefill_streamed(
        smoke_cfg, dense):
    """With ``drain_s`` the session goes on past the window's close,
    sending nothing new, until every prompt sent has its first token."""
    from bench import serving
    t = dict(fixture_json("smoke-closed.json"), drain_s=30.0)
    eng = serving.build_engine(dense, smoke_cfg, t, 11)
    serving.warm_up(eng, smoke_cfg, t, smoke_cfg["vocab_size"])
    win = serving.run_window(eng, smoke_cfg, t, 11, 1.0)
    assert win["compiles_in_window"] == 0
    assert all(r["times"] for r in win["reqs"])
    assert all(r["due"] < win["t1"] for r in win["reqs"])
