"""Reduction of a profiler trace to device busy time, per-program and
per-kernel device time, and idle gaps labelled by what the host was doing.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
small plain structure (the device's op and module events, the harness's
own host spans); :func:`reduce` turns that into numbers.  The two are apart
so that the reduction can be checked on a small recorded trace
(``chipbench/tests/data``) without a chip.
"""

from __future__ import annotations

import re
from pathlib import Path

SPAN_PREFIX = "chipbench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(path: Path) -> dict:
    """The device's op and program events and the harness's host spans.

    Times are the trace's nanoseconds (device and host share the base).
    An op is ``[kind, start, duration, program]``: ``kind`` is the HLO
    opcode, with a custom call's target (``custom-call:tpu_custom_call``
    is a Pallas kernel); ``program`` is the XLA module it ran in, without
    its fingerprint.  A program is ``[name, start, duration]``.
    """
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out = {"ops": [], "modules": [], "spans": []}
    names = [p.name for p in pd.planes]
    device = next((n for n in names if n.startswith("/device:TPU")),
                  next((n for n in names if n.startswith("/device:")), None))
    raw_ops = []
    for plane in pd.planes:
        if plane.name == device:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    raw_ops = [(e.name, e.start_ns, e.duration_ns)
                               for e in line.events]
                elif line.name == MODULES_LINE:
                    out["modules"] = [[_program(e.name), e.start_ns,
                                       e.duration_ns] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        out["spans"].append([e.name[len(SPAN_PREFIX):],
                                             e.start_ns, e.duration_ns])
    mods = sorted(out["modules"], key=lambda m: m[1])
    k = 0
    for name, s, d in sorted(raw_ops, key=lambda o: o[1]):
        while k + 1 < len(mods) and mods[k + 1][1] <= s:
            k += 1
        prog = mods[k][0] if mods and mods[k][1] <= s <= mods[k][1] \
            + mods[k][2] else ""
        out["ops"].append([op_kind(name), s, d, prog])
    out["device_plane"] = device
    return out


_OPCODE = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_FINGERPRINT = re.compile(r"\(\d+\)$")


def op_kind(hlo: str) -> str:
    """``fusion``, ``copy-start``, ``custom-call:tpu_custom_call``, ... from
    the HLO text the trace names an op by."""
    rhs = hlo.split(" = ", 1)[-1]
    m = _OPCODE.search(" " + rhs)
    kind = m.group(1) if m else rhs.split("(", 1)[0]
    if kind == "custom-call":
        t = _TARGET.search(rhs)
        kind += ":" + (t.group(1) if t else "?")
    return kind


def _program(name: str) -> str:
    return _FINGERPRINT.sub("", name)


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _offset(trace_spans, host_spans) -> float:
    """Trace time minus perf_counter time, from the harness's spans of the
    traced part, seen on both clocks and matched in order by name."""
    diffs = []
    for name in {s[0] for s in trace_spans}:
        ts = sorted(t0 for nm, t0, _ in trace_spans if nm == name)
        hs = sorted(t0 for nm, t0, _ in host_spans if nm == name)
        diffs += [a - b for a, b in zip(ts, hs)]
    if not diffs:
        raise ValueError("no harness span is in the trace")
    diffs.sort()
    return diffs[len(diffs) // 2]


def reduce(events: dict, host_spans, compiles, t0_ns: int, t1_ns: int,
           top: int = 10) -> dict:
    """Busy and idle time of the device over the traced window.

    ``host_spans`` are the harness's spans and ``compiles`` JAX's compile
    intervals, both on ``perf_counter_ns``, as are ``t0_ns``/``t1_ns``, the
    traced window.  Returns seconds throughout.
    """
    spans = [s for s in host_spans if s[2] >= t0_ns and s[1] <= t1_ns]
    off = _offset(events["spans"], spans)
    w0, w1 = t0_ns + off, t1_ns + off
    ops = [(s, s + d, name, mod) for name, s, d, mod in events["ops"]
           if s + d > w0 and s < w1]
    busy = sum(min(e, w1) - max(s, w0)
               for s, e in _union([(s, e) for s, e, _, _ in ops]))
    by_op: dict = {}
    for s, e, kind, prog in ops:
        key = f"{prog}/{kind}"
        n, t = by_op.get(key, (0, 0))
        by_op[key] = (n + 1, t + (e - s))
    by_mod: dict = {}
    for name, s, d in events["modules"]:
        if s + d > w0 and s < w1:
            n, t = by_mod.get(name, (0, 0))
            by_mod[name] = (n + 1, t + d)
    # idle gaps, each labelled by the innermost host span (or compile)
    # that covers its middle
    labelled = [(s + off, e + off, name) for name, s, e in spans]
    labelled += [(s + off, e + off, "compile") for s, e in compiles]
    gaps = []
    prev = w0
    for s, e in _union([(max(s, w0), min(e, w1)) for s, e, _, _ in ops]) \
            + [[w1, w1]]:
        if s > prev:
            mid = (prev + s) / 2
            cover = [(b - a, name) for a, b, name in labelled
                     if a <= mid <= b]
            gaps.append((min(cover)[1] if cover else "other", s - prev))
        prev = max(prev, e)
    gaps.sort(key=lambda g: -g[1])
    ranked = sorted(by_op.items(), key=lambda kv: -kv[1][1])
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy / 1e9,
        "ops": {k: [n, t / 1e9] for k, (n, t) in by_op.items()},
        "modules": {k: [n, t / 1e9] for k, (n, t) in by_mod.items()},
        "top_ops": [[k, t / 1e9] for k, (n, t) in ranked[:top]],
        "idle_gaps": _sum_by(gaps)[:top],
        "longest_gaps": [[name, d / 1e9] for name, d in gaps[:top]],
        "device_plane": events["device_plane"],
    }


def _sum_by(gaps):
    """Idle seconds by what the host was doing, the most first."""
    out: dict = {}
    for name, d in gaps:
        out[name] = out.get(name, 0.0) + d / 1e9
    return sorted(([k, v] for k, v in out.items()), key=lambda kv: -kv[1])
