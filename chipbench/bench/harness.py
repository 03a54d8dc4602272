"""One run of one cell: set-up, a measured window, the check, the result.

``chipbench/run.py`` calls :func:`main`.  Everything a cell needs is found
by name: the cell in ``BENCHMARK.json``, its parameters in
``chipbench/workloads/<cell>.json``, its configuration in the file that
``BENCHMARK.json`` names, its traffic in ``chipbench/traffic/<mix>.json``,
and each metric's reader in ``chipbench/metrics/<metric>.py``.  The
configuration's ``kind`` picks the runner (:mod:`exec_cell` for a planned
graph run in its arena).
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "chipbench"


class NoChip(SystemExit):
    """JAX found no accelerator fit for the cell: exit non-zero, no result."""


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict           # the cell's entry in BENCHMARK.json
    config: dict          # configs/<config>.json
    mix: dict             # traffic/<mix>.json
    params: dict          # workloads/<cell>.json
    bench: dict           # all of BENCHMARK.json

    def metrics(self, kind: str) -> list[dict]:
        """The cell's end_to_end or per_layer metrics."""
        return [m for m in self.bench[kind]
                if self.name in m.get("workloads", [self.name])]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    from chipbench.bench.traffic import load_mix

    bench = json.loads((root / "BENCHMARK.json").read_text())
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[entry["config"]]["file"]).read_text())
    mix = load_mix(root / "chipbench" / "traffic" / f"{entry['traffic']}.json")
    params = json.loads(
        (root / "chipbench" / "workloads" / f"{name}.json").read_text())
    return Cell(name, entry, config, mix, params, bench)


# --------------------------------------------------------------------------
# device, clocks, spans
# --------------------------------------------------------------------------


def device_info(chips: int, require_tpu: bool = True) -> tuple[dict, dict]:
    """(device record, peaks of its kind); raises :class:`NoChip`."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and d0.platform != "tpu":
        raise NoChip(f"chipbench: needs a TPU; JAX found {d0.platform!r} "
                     f"({d0.device_kind})")
    if len(devs) < chips:
        raise NoChip(f"chipbench: the cell needs {chips} chips, JAX found "
                     f"{len(devs)}")
    table = json.loads((BENCH / "peaks.json").read_text())
    kind = d0.device_kind if require_tpu else "TPU v5 lite"
    if kind not in table:
        raise NoChip(f"chipbench: no peaks for device kind {kind!r} in "
                     f"chipbench/peaks.json")
    return ({"platform": d0.platform, "kind": d0.device_kind,
             "count": len(devs)}, table[kind])


def peak_bytes_in_use(n_devices: int) -> int:
    """The highest ``peak_bytes_in_use`` over the chips the cell uses."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:n_devices]]
    return int(max(peaks))


class CompileClock:
    """Counts JAX's backend compiles (persistent-cache reads included: JAX
    times both under the one event) and its persistent-cache hits, and
    keeps each compile's host interval for labelling idle gaps."""

    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.intervals: list[tuple[int, int]] = []   # perf_counter_ns
        self._m = monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration
            end = time.perf_counter_ns()
            self.intervals.append((end - int(duration * 1e9), end))

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, float, int]:
        return self.compiles, self.compile_s, self.cache_hits

    def close(self):
        self._m.unregister_event_duration_listener(self._on_duration)
        self._m.unregister_event_listener(self._on_event)


class Spans:
    """The harness's own host spans, written into the profiler's trace as
    ``TraceAnnotation``s and kept in memory on ``perf_counter_ns``."""

    def __init__(self):
        self.records: list[tuple[str, int, int]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter_ns()
        with TraceAnnotation(f"chipbench.{name}"):
            yield
        self.records.append((name, t0, time.perf_counter_ns()))


class Tracer:
    """Profiles the first ``seconds`` of the window, then stops."""

    def __init__(self, out_dir: Path, seconds: float):
        self.dir = out_dir
        self.seconds = seconds
        self.t0 = self.t1 = None          # perf_counter_ns
        self.steps = 0
        self.active = False

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.t0 = time.perf_counter_ns()
        self.active = True

    def poll(self, steps: int) -> float:
        """Call after each step of the window; stops once time is up.
        Returns the seconds the stop took (the profiler collects and
        writes the trace), which the window leaves out."""
        if self.active and time.perf_counter_ns() - self.t0 \
                >= self.seconds * 1e9:
            t = time.perf_counter()
            self.stop(steps)
            return time.perf_counter() - t
        return 0.0

    def stop(self, steps: int) -> None:
        import jax

        if not self.active:
            return
        self.t1 = time.perf_counter_ns()
        self.steps = steps
        jax.profiler.stop_trace()
        self.active = False

    def xplane(self) -> Path:
        found = sorted(self.dir.glob("plugins/profile/*/*.xplane.pb"))
        if not found:
            raise RuntimeError(f"no trace written under {self.dir}")
        return found[-1]


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------


def _runner(kind: str):
    if kind == "graph_exec":
        from chipbench.bench.exec_cell import ExecCell
        return ExecCell
    raise ValueError(f"unknown configuration kind {kind!r}")


def _reader(name: str, root: Path):
    path = root / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             root: Path = ROOT, control: bool = False) -> dict:
    """Set up, measure, check; returns the result object to print."""
    device, peaks = device_info(cell.entry["chips"], require_tpu)
    clock = CompileClock()
    spans = Spans()
    try:
        drv = _runner(cell.config["kind"])(cell, seed, spans, peaks)
        c0 = clock.snapshot()
        t_setup = time.perf_counter()
        drv.setup()
        c1 = clock.snapshot()
        t_window = time.perf_counter()
        phases = {"start": t_setup - t_start}
        for name, a, b in spans.records:
            phases[name] = phases.get(name, 0.0) + (b - a) / 1e9
        tracer = None
        if trace:
            tracer = Tracer(root / "chipbench" / "out" / "trace" / cell.name,
                            float(cell.params["trace_seconds"]))
        rec = drv.window(seconds, tracer)
        if tracer is not None:
            tracer.stop(rec["steps"])
        c2 = clock.snapshot()
        rec.update(
            setup_s=t_window - t_start, setup_phases=phases,
            setup_compiles=c1[0] - c0[0], setup_compile_s=c1[1] - c0[1],
            setup_cache_hits=c1[2] - c0[2],
            window_compiles=c2[0] - c1[0], window_cache_hits=c2[2] - c1[2],
            peak_bytes=peak_bytes_in_use(device["count"]),
            peaks=peaks)
        device["memory_peak_bytes"] = rec["peak_bytes"]
        drv.free()
        gc.collect()
        t_check = time.perf_counter()
        checks = drv.check(control=control)
        rec["check_s"] = time.perf_counter() - t_check
    finally:
        clock.close()
    breakdown = None
    if tracer is not None:
        from chipbench.bench import trace as tr

        events = tr.load(tracer.xplane())
        red = tr.reduce(events, spans.records, clock.intervals,
                        tracer.t0, tracer.t1)
        rec["trace"] = red
        rec["traced_steps"] = tracer.steps
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["top_ops"],
                     "idle_gaps": red["idle_gaps"]}
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics(kind):
        v = _reader(m["name"], root)(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    failed = int(drv.failed)
    result = {
        # a control's reading sits beside the check, never in it
        "correct": all(c.ok for c in checks
                       if not c.name.startswith("control_")) and failed == 0,
        "attempted": int(rec["attempted"]),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    result["_checks"] = checks
    result["_record"] = rec
    return result


def main(args, t_start: float) -> int:
    cell = load_cell(args.workload)
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    try:
        res = run_cell(cell, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace), t_start=t_start)
    except NoChip as e:
        print(e, file=sys.stderr)
        return 3
    checks = res.pop("_checks")
    res.pop("_record")
    for c in checks:
        print(f"check {c.name} = {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0
