"""End-to-end arithmetic over the client's record of one window.

The client stamps every streamed token on the host clock
(``time.monotonic``).  A request record holds ``due`` (when it was due
to be sent: its scheduled arrival in an open loop, its submission in a
closed one), ``prompt_len``, ``n_new`` and ``times`` (one stamp per
streamed token).  Rates are taken over all the work and all the time of
the window; tails are over all requests (or gaps) of the window.
A request in flight when the window closes is neither done nor failed.
A request due in the window with no first token by its close enters the
TTFT tail with its elapsed time, a lower bound, so a stall moves the
tail.
"""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (linear interpolation) of all values."""
    if len(values) == 0:
        raise ValueError("percentile of an empty sample")
    return float(np.percentile(np.asarray(values, np.float64), q))


def out_tok_s(reqs, t0: float, t1: float) -> float:
    """Output tokens streamed to clients in the window per second."""
    n = sum(1 for r in reqs for t in r["times"] if t0 <= t <= t1)
    return n / (t1 - t0)


def prompt_tok_s(reqs, t0: float, t1: float) -> float:
    """Prompt tokens prefilled in the window per second.

    A prompt is prefilled between the moment it was sent (``due``) and
    its first streamed token; its tokens are spread evenly over that
    span and the part of the span inside the window counts, so a long
    prompt that straddles an edge of the window counts in part, not
    whole or not at all.  The run serves past the window until every
    prompt sent in it has streamed its first token (``drain_s`` of the
    traffic file); a prompt with no first token even then counts
    nothing, a lower bound."""
    n = 0.0
    for r in reqs:
        if not r["times"]:
            continue
        due, first = r["due"], r["times"][0]
        lo, hi = max(due, t0), min(first, t1)
        if hi > lo:
            n += r["prompt_len"] * (hi - lo) / (first - due)
    return n / (t1 - t0)


def ttft_samples(reqs, t0: float, t1: float):
    """TTFT of every request due in the window, from its due time;
    censored at the close for those with no first token yet."""
    out = []
    for r in reqs:
        if not t0 <= r["due"] <= t1:
            continue
        first = r["times"][0] if r["times"] else None
        out.append((first if first is not None and first <= t1 else t1)
                   - r["due"])
    return out


def itl_samples(reqs, t0: float, t1: float):
    """Every gap between consecutive streamed tokens of one request, both
    inside the window."""
    out = []
    for r in reqs:
        ts = [t for t in r["times"] if t0 <= t <= t1]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def ttft_p95_ms(reqs, t0, t1) -> float:
    return 1e3 * percentile(ttft_samples(reqs, t0, t1), 95)


def itl_p95_ms(reqs, t0, t1) -> float:
    return 1e3 * percentile(itl_samples(reqs, t0, t1), 95)


METRICS = {
    "out_tok_s": out_tok_s,
    "prompt_tok_s": prompt_tok_s,
    "ttft_p95_ms": ttft_p95_ms,
    "itl_p95_ms": itl_p95_ms,
}


def counts(reqs, t0: float, t1: float) -> dict:
    """Requests attempted (due in the window), done (last token in it)."""
    due = [r for r in reqs if t0 <= r["due"] <= t1]
    done = [r for r in reqs if len(r["times"]) == r["n_new"]
            and r["times"][-1] <= t1]
    return {"attempted": len(due), "done": len(done)}
