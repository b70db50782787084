"""Read the serve path's own spans and scopes from a profiler trace.

The program names its host work and its weight dequant in the trace:

* host spans ``serve.*`` (``repro.serve.step_loop.SPANS``), one
  ``serve.step`` per step with the step's counts as args (``rows``,
  ``width``, ``slots``, ``prompt_tokens``, ``decode_tokens``, ...);
* the name scope ``dequant`` (``repro.models.layers.deq``), which XLA
  keeps in the metadata of every op it compiles from a weight dequant.

:func:`reduce_spans` reduces one trace, inside the host span
``bench_window`` as ``bench.trace_reduce`` clips it, to:

* ``spans`` -- every ``serve.*`` host event that overlaps the window,
  with its args;
* ``idle_by_span`` -- each stretch in which the first chip runs no op,
  put down to the innermost ``serve.*`` span covering its middle, else
  ``(no serve span)`` (the longest ``MAX_GAPS`` stretches, as
  ``trace_reduce`` finds its ``idle_gaps``);
* ``scoped_ops`` -- device seconds of the ops whose scope path holds
  ``dequant``.  A TPU op event carries only its HLO instruction's text;
  the scope path is the instruction's ``op_name`` metadata, which the
  trace keeps in the optimized HLO of each program it ran (``Hlo Proto``
  stats of the ``/host:metadata`` plane, :func:`program_scopes`).  Each
  op is looked up in the program whose ``XLA Modules`` event holds it.

Three readings follow from it: :func:`step_fill_pct` (scheduler),
:func:`host_idle_pct` (host step loop) and :func:`dequant_ms` (weights).
``bench/run.py`` does not report them yet; a program without the spans
reads ``None`` where they would be.
"""
from __future__ import annotations

import bisect
import collections
import re

import numpy as np

from bench.trace_reduce import DEVICE, MAX_GAPS, WINDOW, gaps_ns

SERVE = "serve."
STEP = "serve.step"
NO_SPAN = "(no serve span)"
# host work of the step loop: a device gap under one of these is the host
# holding the chip back.  Not among them: serve.step's own time,
# serve.wait (no load), serve.draft, and serve.enqueue, the calls that
# hand work to the device and may wait on its runtime
HOST_WORK = ("serve.admit", "serve.plan", "serve.scrub", "serve.dispatch",
             "serve.finish", "serve.pump", "serve.retire")
SCOPE = "dequant"
SCOPE_PATH = re.compile(r"(^|/)" + SCOPE + r"(/|$)")
# a TPU op event's name is its instruction's text: "%fusion.12 = bf16[...]"
INSTRUCTION = re.compile(r"%?([^\s=]+)")


def _host_events(planes):
    return [e for p in planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events]


def _device_ops(planes):
    return [[e for line in p.lines if line.name == "XLA Ops"
             for e in line.events]
            for p in planes if DEVICE.match(p.name)]


def _fields(buf):
    """(field number, value) of one protobuf message: varints as ints,
    length-delimited values as bytes (fixed-width values skipped)."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        x = shift = 0
        while True:
            c = buf[i]
            i += 1
            x |= (c & 0x7F) << shift
            shift += 7
            if c < 0x80:
                return x

    while i < n:
        key = varint()
        kind = key & 7
        if kind == 0:
            yield key >> 3, varint()
        elif kind == 2:
            size = varint()
            yield key >> 3, buf[i:i + size]
            i += size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {kind}")


def _first(buf, number, default=b""):
    return next((v for f, v in _fields(buf) if f == number), default)


def program_scopes(xspace: bytes) -> dict:
    """``{program: {instruction: op_name}}`` from a trace file's bytes.

    The profiler keeps the optimized HLO of every program it saw as an
    ``HloProto`` stat of the program's event metadata in the
    ``/host:metadata`` plane (XSpace.planes 1, XPlane.name 2 /
    event_metadata 4; XEventMetadata.name 2 / stats 5; XStat.bytes 6;
    HloProto.hlo_module 1; HloModuleProto.computations 3;
    HloComputationProto.instructions 2; HloInstructionProto.name 1 /
    metadata 7; OpMetadata.op_name 2)."""
    out = {}
    for num, plane in _fields(xspace):
        if num != 1 or _first(plane, 2) != b"/host:metadata":
            continue
        for num, entry in _fields(plane):
            if num != 4:
                continue
            meta = _first(entry, 2)         # the map entry's value
            names = out.setdefault(_first(meta, 2).decode(), {})
            for hlo in (_first(st, 6) for n, st in _fields(meta) if n == 5):
                module = _first(hlo, 1) if hlo else b""
                for comp in (c for n, c in _fields(module) if n == 3):
                    for ins in (i for n, i in _fields(comp) if n == 2):
                        names[_first(ins, 1).decode()] = _first(
                            _first(ins, 7), 2).decode()
    return out


def _scoped_seconds(device, scopes, lo, hi) -> float:
    """Seconds in [lo, hi] of one chip's ops whose ``op_name`` holds the
    scope, each looked up in the program whose execution holds it."""
    mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for line in device.lines if line.name == "XLA Modules"
                  for e in line.events)
    starts = [m[0] for m in mods]
    total = 0.0
    for line in device.lines:
        if line.name != "XLA Ops":
            continue
        for e in line.events:
            s, t = e.start_ns, e.start_ns + e.duration_ns
            k = bisect.bisect_right(starts, s) - 1
            if t <= lo or s >= hi or k < 0 or s >= mods[k][1]:
                continue
            ins = INSTRUCTION.match(e.name)
            op = scopes.get(mods[k][2], {}).get(ins.group(1) if ins else "")
            if op and SCOPE_PATH.search(op):
                total += (min(t, hi) - max(s, lo)) / 1e9
    return total


def reduce_spans(planes, scopes=None) -> dict:
    """``planes`` as ``ProfileData.planes`` gives them (or look-alikes with
    ``name``, ``lines``, events with ``name``, ``start_ns``,
    ``duration_ns`` and ``stats``); ``scopes`` as :func:`program_scopes`
    reads them from the same trace (none: no ``scoped_ops``)."""
    planes = list(planes)
    host = _host_events(planes)
    win = [e for e in host if e.name == WINDOW]
    if not win:
        raise ValueError(f"trace has no {WINDOW!r} host span")
    lo, hi = win[0].start_ns, win[0].start_ns + win[0].duration_ns
    spans = [{"name": e.name, "start_ns": e.start_ns,
              "end_ns": e.start_ns + e.duration_ns, "args": dict(e.stats)}
             for e in host if e.name.startswith(SERVE)
             and e.start_ns < hi and e.start_ns + e.duration_ns > lo]
    devices = _device_ops(planes)
    idle = collections.Counter()
    if devices:
        st = np.array([s["start_ns"] for s in spans], np.float64)
        en = np.array([s["end_ns"] for s in spans], np.float64)
        iv = [(e.start_ns, e.start_ns + e.duration_ns) for e in devices[0]]
        gaps = sorted(gaps_ns(iv, lo, hi), key=lambda g: g[0] - g[1])
        for s, e in gaps[:MAX_GAPS]:
            mid = (s + e) / 2
            hit = np.flatnonzero((st <= mid) & (en >= mid))
            name = spans[hit[np.argmin(en[hit] - st[hit])]]["name"] \
                if hit.size else NO_SPAN
            idle[name] += (e - s) / 1e9
    scoped = sum(_scoped_seconds(p, scopes, lo, hi) for p in planes
                 if DEVICE.match(p.name)) if scopes else 0.0
    return {"window_ns": (lo, hi), "spans": spans, "idle_by_span": idle,
            "scoped_ops": {SCOPE: scoped} if scoped else {}}


def window_steps(red: dict):
    """Args of the ``serve.step`` spans that start in the window."""
    lo, hi = red["window_ns"]
    return [s["args"] for s in red["spans"]
            if s["name"] == STEP and lo <= s["start_ns"] < hi]


def step_fill_pct(red: dict):
    """Real prompt and decode tokens over the slots dispatched (rows x
    width), over the steps that start in the window, in percent."""
    steps = window_steps(red)
    slots = sum(a.get("slots", 0) for a in steps)
    if not slots:
        return None
    tokens = sum(a["prompt_tokens"] + a["decode_tokens"] for a in steps)
    return 100.0 * tokens / slots


def host_idle_pct(red: dict, window_s: float):
    """Device-idle seconds under the step loop's host work (``HOST_WORK``)
    over the window, in percent; ``None`` with no device or no spans."""
    if not red["idle_by_span"] or not red["spans"]:
        return None
    idle = sum(v for k, v in red["idle_by_span"].items() if k in HOST_WORK)
    return 100.0 * idle / window_s


def dequant_ms(red: dict, model_step_times):
    """Device seconds of the ops in the ``dequant`` scope per execution of
    ``model_step``, in ms.  A dequant that XLA fuses into its matmul is
    counted with the matmul, whose scope the fused op keeps."""
    t = red["scoped_ops"].get(SCOPE)
    if not t or not model_step_times:
        return None
    return 1e3 * t / len(model_step_times)
