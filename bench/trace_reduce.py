"""Reduce one profiler trace to what the per-layer metrics read.

The run wraps its window in a host ``TraceAnnotation`` named
``bench_window``; every reading is clipped to that span.  Device planes
are those named ``/device:TPU:<n>`` (one per chip).  On each:

* ``busy_s`` -- the union of the intervals of the events on its
  ``XLA Ops`` line, averaged over the chips;
* ``modules`` -- the events of its ``XLA Modules`` line (one per program
  execution: name, seconds), so a jitted entry point's device time per
  call is found by name;
* ``ops`` -- every op event (name, seconds), for kernel matching and the
  breakdown.

``idle_gaps`` attributes each stretch in which the first chip runs no op
to the innermost host event that covers the middle of it (the longest
``MAX_GAPS`` stretches).
"""
from __future__ import annotations

import collections
import pathlib
import re

import numpy as np

WINDOW = "bench_window"
DEVICE = re.compile(r"^/device:TPU:\d+$")
MAX_GAPS = 2000


def load(trace_dir):
    """The newest ``.xplane.pb`` under ``trace_dir``, read by JAX."""
    from jax.profiler import ProfileData
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(str(files[-1]))


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def union_ns(intervals, lo, hi) -> int:
    """Length of the union of (start, end) intervals clipped to [lo, hi]."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, lo, hi):
    """Stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def reduce_planes(planes) -> dict:
    """``planes``: iterable of objects with ``name`` and ``lines`` (each
    with ``name`` and ``events``), as ``ProfileData.planes`` gives."""
    host, devices = [], []
    for p in planes:
        lines = {l.name: _events(l) for l in p.lines}
        if DEVICE.match(p.name):
            devices.append(lines)
        elif p.name.startswith("/host:"):
            for evs in lines.values():
                host.extend(evs)
    win = [(s, e) for n, s, e in host if n == WINDOW]
    if not win:
        raise ValueError(f"trace has no {WINDOW!r} host span")
    lo, hi = win[0]
    ops, modules, busy = [], [], []
    for lines in devices:
        iv = [(s, e) for _, s, e in lines.get("XLA Ops", [])]
        busy.append(union_ns(iv, lo, hi))
        ops += [(n, (min(e, hi) - max(s, lo)) / 1e9)
                for n, s, e in lines.get("XLA Ops", []) if s < hi and e > lo]
        modules += [(n, (e - s) / 1e9) for n, s, e in
                    lines.get("XLA Modules", []) if lo <= s and e <= hi]
    idle = collections.Counter()
    if devices:
        iv = [(s, e) for _, s, e in devices[0].get("XLA Ops", [])]
        inner = [(n, s, e) for n, s, e in host if n != WINDOW]
        names = [n for n, _, _ in inner]
        st = np.array([s for _, s, _ in inner], np.float64)
        en = np.array([e for _, _, e in inner], np.float64)
        gaps = sorted(gaps_ns(iv, lo, hi), key=lambda g: g[0] - g[1])
        for s, e in gaps[:MAX_GAPS]:
            mid = (s + e) / 2
            hit = np.flatnonzero((st <= mid) & (en >= mid))
            name = names[hit[np.argmin(en[hit] - st[hit])]] if hit.size \
                else "(no host event)"
            idle[name] += (e - s) / 1e9
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy) / len(busy) / 1e9 if busy else 0.0,
            "chips": len(devices), "ops": ops, "modules": modules,
            "idle_gaps": idle}


def top(pairs, n=10):
    """[[name, seconds], ...] of the ``n`` names with the most seconds."""
    acc = collections.Counter()
    for name, sec in pairs:
        acc[name] += sec
    return [[k, v] for k, v in acc.most_common(n)]


def module_times(red: dict, pattern: str):
    """Device seconds of each execution of modules matching ``pattern``."""
    rx = re.compile(pattern)
    return [sec for name, sec in red["modules"] if rx.search(name)]


def op_seconds(red: dict, pattern: str) -> float:
    rx = re.compile(pattern)
    return sum(sec for name, sec in red["ops"] if rx.search(name))
