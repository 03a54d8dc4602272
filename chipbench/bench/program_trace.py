"""The program's own spans (``repro.obs``) in a traced run, and the device's
idle gaps labelled by them on the host's clock.

``repro.obs.span`` writes each span into the profiler's trace as a
``TraceAnnotation`` named ``repro.<name>``.  :func:`load` reads those, the
harness's spans and the device's programs from the ``.xplane.pb``;
:func:`device_skew_ns` estimates how far the device's timestamps lag the
host's; :func:`idle_gaps` labels each idle gap of the device by the
innermost host span that covers its middle once moved by that estimate.
The per-layer readers in ``chipbench/metrics`` read the spans of the run's
trace through :func:`mean_ms`.  None of this changes what
:mod:`chipbench.bench.trace` reads: window, busy time, ops and programs.

    python3 -m chipbench.bench.program_trace <trace.xplane.pb>

prints the estimate, the labelled idle gaps and each span's mean.
"""

from __future__ import annotations

import functools
import json
import sys
from pathlib import Path

from chipbench.bench import trace

ROOT = Path(__file__).resolve().parents[2]
#: where the harness's ``Tracer`` writes each cell's trace
TRACES = ROOT / "chipbench" / "out" / "trace"
PROGRAM_PREFIX = "repro."
#: the stat that ties a device program to the host event that launched it
RUN_ID = "run_id"
#: the arena program and the span that dispatches it (``repro.core.executor``)
ARENA_PROGRAM = "jit__program"
DISPATCH = "repro.execute.dispatch"


def _stat(event, key):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def load(path: Path) -> dict:
    """The program's and the harness's host spans (full names), the device's
    programs with their run ids, and the first host event of each run id.

    A span is ``[name, start, duration]``, a program ``[name, start,
    duration, run_id]`` (``run_id`` ``None`` where the trace has none);
    ``launches`` maps a run id to the start of the earliest host event
    that carries it.  Times are the trace's nanoseconds.
    """
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    names = [p.name for p in pd.planes]
    device = next((n for n in names if n.startswith("/device:TPU")),
                  next((n for n in names if n.startswith("/device:")), None))
    out = {"spans": [], "modules": [], "launches": {}}
    launches = out["launches"]
    for plane in pd.planes:
        if plane.name == device:
            for line in plane.lines:
                if line.name == trace.MODULES_LINE:
                    out["modules"] = [
                        [trace._program(e.name), e.start_ns, e.duration_ns,
                         _stat(e, RUN_ID)] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith((PROGRAM_PREFIX,
                                          trace.SPAN_PREFIX)):
                        out["spans"].append([e.name, e.start_ns,
                                             e.duration_ns])
                        continue
                    rid = _stat(e, RUN_ID)
                    if rid is not None and (rid not in launches
                                            or e.start_ns < launches[rid]):
                        launches[rid] = e.start_ns
    return out


def device_skew_ns(ev: dict) -> tuple[float, str | None]:
    """Nanoseconds to add to a device time to put it on the host's clock,
    and what bounded it (``"run_id"``, ``"dispatch"`` or ``None``).

    A program starts on the device no earlier than the host began to launch
    it, so each launch bounds the shift from below; the estimate is the
    tightest bound, never below 0.  Launches come from the host events that
    share a program's run id; where the trace carries none, from the
    ``repro.execute.dispatch`` spans, matched in order to the arena
    programs (one of each per step), a looser bound.
    """
    pairs = [(ev["launches"][rid], s) for _, s, _, rid in ev["modules"]
             if rid is not None and rid in ev["launches"]]
    how = RUN_ID
    if not pairs:
        how = "dispatch"
        pairs = list(zip(
            sorted(s for n, s, _ in ev["spans"] if n == DISPATCH),
            sorted(s for n, s, _, _ in ev["modules"] if n == ARENA_PROGRAM)))
    if not pairs:
        return 0.0, None
    return max(0.0, max(h - d for h, d in pairs)), how


def _label(name: str) -> str:
    # the harness's spans keep the short names its breakdown gives them
    return name[len(trace.SPAN_PREFIX):] \
        if name.startswith(trace.SPAN_PREFIX) else name


def idle_gaps(ops, spans, w0: float, w1: float, skew_ns: float,
              top: int = 10) -> list:
    """Idle seconds of the device in ``[w0, w1]`` by the innermost host span
    covering each gap's middle, the gap moved by ``skew_ns`` onto the
    host's clock; ``"other"`` where none covers it.  ``ops`` are
    :func:`chipbench.bench.trace.load`'s, ``spans`` :func:`load`'s, and
    the window is on the trace's clock as :func:`trace.reduce` has it: the
    gaps are the ones it finds, only their labels differ."""
    gaps = []
    prev = w0
    busy = trace._union([(max(s, w0), min(s + d, w1))
                         for _, s, d, _ in ops if s + d > w0 and s < w1])
    for s, e in busy + [[w1, w1]]:
        if s > prev:
            mid = (prev + s) / 2 + skew_ns
            cover = [(d, _label(name)) for name, a, d in spans
                     if a <= mid <= a + d]
            gaps.append((min(cover)[1] if cover else "other", s - prev))
        prev = max(prev, e)
    return trace._sum_by(gaps)[:top]


@functools.lru_cache(maxsize=1)
def _spans_of(path: str, mtime_ns: int) -> dict:
    by: dict = {}
    for name, _, d in load(Path(path))["spans"]:
        if name.startswith(PROGRAM_PREFIX):
            by.setdefault(name, []).append(d)
    return by


def run_trace() -> Path | None:
    """The newest trace the harness wrote: the run's own, since each traced
    run clears its cell's directory before it starts."""
    found = sorted(TRACES.glob("*/plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


def mean_ms(run: dict, name: str) -> float | None:
    """Mean milliseconds of the program span ``name`` over the traced
    steps of ``run``; ``None`` without a trace or without such spans (a
    program that has no ``repro.obs``)."""
    if not run.get("trace") or not run.get("traced_steps"):
        return None
    path = run_trace()
    if path is None:
        return None
    ds = _spans_of(str(path), path.stat().st_mtime_ns).get(name)
    return 1e-6 * sum(ds) / len(ds) if ds else None


def main(argv: list[str]) -> int:
    path = Path(argv[0])
    ev = load(path)
    base = trace.load(path)
    skew, how = device_skew_ns(ev)
    harness = [(s, s + d) for n, s, d in ev["spans"]
               if n.startswith(trace.SPAN_PREFIX)]
    w0 = min(s for s, _ in harness)
    w1 = max(e for _, e in harness)
    means: dict = {}
    for name, _, d in ev["spans"]:
        means.setdefault(name, []).append(d)
    print(json.dumps({
        "device_skew_ms": skew / 1e6, "skew_from": how,
        "dispatch_bound_ms": device_skew_ns(
            dict(ev, launches={}))[0] / 1e6,
        "window_s": (w1 - w0) / 1e9,
        "idle_gaps": idle_gaps(base["ops"], ev["spans"], w0, w1, skew),
        "idle_gaps_unshifted": idle_gaps(base["ops"], ev["spans"], w0, w1,
                                         0.0),
        "span_ms": {n: [len(v), 1e-6 * sum(v) / len(v)]
                    for n, v in sorted(means.items())},
        "programs": {n: sum(1 for m in ev["modules"] if m[0] == n)
                     for n in sorted({m[0] for m in ev["modules"]})},
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
