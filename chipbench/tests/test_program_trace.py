"""The program's spans in a trace: the clock skew, the idle gaps they label,
and the readers of ``execute_host_ms.exec``, ``resolve_ms.exec`` and
``dispatch_ms.exec``."""

import json
from pathlib import Path

import pytest

from chipbench.bench import program_trace, readers, trace

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000
US = 1_000
READERS = {"execute_host_ms.exec": "repro.execute",
           "resolve_ms.exec": "repro.execute.resolve_inputs",
           "dispatch_ms.exec": "repro.execute.dispatch"}


def _reader(name):
    from chipbench.bench.harness import ROOT, _reader as load

    return load(name, ROOT)


def _shifted(skew):
    """Two steps 10 ms apart on the host's clock: a harness span around a
    program ``execute`` that resolves its input (launching a ramp at its
    end), allocates the arena (launching a fill) and dispatches the arena
    program.  Each device program starts 0.2 ms after its launch; the
    device's timestamps are ``skew`` ns behind the host's."""
    spans, ops, modules, launches = [], [], [], {}
    for k, t in enumerate((0, 10 * MS)):
        spans += [["chipbench.execute", t, 9.5 * MS],
                  ["repro.execute", t + 0.5 * MS, 6.7 * MS],
                  ["repro.execute.resolve_inputs", t + 0.5 * MS, 3 * MS],
                  ["repro.execute.alloc_arena", t + 3.5 * MS, 1 * MS],
                  ["repro.execute.dispatch", t + 4.5 * MS, 2.5 * MS]]
        for i, (name, launch, dur) in enumerate(
                (("jit_iota", t + 3.0 * MS, 0.2 * MS),
                 ("jit_broadcast_in_dim", t + 4.3 * MS, 0.1 * MS),
                 ("jit__program", t + 6.8 * MS, 2 * MS))):
            rid = 10 * k + i
            launches[rid] = launch
            s = launch + 0.2 * MS - skew
            modules.append([name, s, dur, rid])
            ops.append(["fusion", s, dur, name])
    return ({"spans": spans, "modules": modules, "launches": launches},
            {"ops": ops, "modules": [m[:3] for m in modules], "spans": [],
             "device_plane": "/device:TPU:0"})


@pytest.mark.parametrize("skew_ms", [0.0, 0.7, 2.5])
def test_skew_recovered_and_gaps_labelled_by_program_spans(skew_ms):
    skew = skew_ms * MS
    ev, base = _shifted(skew)
    # each program starts 0.2 ms after its launch: the tightest bound
    got, how = program_trace.device_skew_ns(ev)
    assert how == "run_id"
    assert got == pytest.approx(max(0.0, skew - 0.2 * MS))
    # without run ids the dispatch spans bound it, 2.5 ms more loosely
    loose, how = program_trace.device_skew_ns(dict(ev, launches={}))
    assert how == "dispatch"
    assert loose == pytest.approx(max(0.0, skew - 2.5 * MS))
    # the window as the trace reduction has it (the device's raw times):
    # the gaps are the same intervals whatever the shift, only their
    # labels move
    w0, w1 = 0, 20 * MS
    busy = sum(d for _, _, d, _ in base["ops"])
    for shift in (0.0, got):
        gaps = program_trace.idle_gaps(base["ops"], ev["spans"], w0, w1,
                                       shift)
        assert sum(v for _, v in gaps) == pytest.approx(
            (w1 - w0 - busy) / 1e9)
    if skew_ms == 2.5:
        # shifted by 2.3 ms each gap lands in the span whose work keeps
        # the device waiting; the tail of the window is past every span
        assert dict(gaps) == {
            "repro.execute.resolve_inputs": pytest.approx(4.9e-3),
            "repro.execute.alloc_arena": pytest.approx(2.2e-3),
            "repro.execute.dispatch": pytest.approx(4.8e-3),
            "other": pytest.approx(3.5e-3)}
        unshifted = program_trace.idle_gaps(base["ops"], ev["spans"], w0,
                                            w1, 0.0)
        assert "execute" in dict(unshifted)


def test_no_launches_and_no_program_spans_leave_the_clock_alone():
    events = json.loads((DATA / "randwire274_exec_first_step.json")
                        .read_text())
    ev = {"spans": [["chipbench." + n, s, d] for n, s, d in events["spans"]],
          "modules": [m + [None] for m in events["modules"]],
          "launches": {}}
    assert program_trace.device_skew_ns(ev) == (0.0, None)


def test_recorded_trace_reads_as_before():
    """On the recorded step (a program with no spans of its own) the trace
    reduction, its readers and the relabelled gaps read what they read
    before the program's spans existed."""
    events = json.loads((DATA / "randwire274_exec_first_step.json")
                        .read_text())
    (name, s0, d0), = events["spans"]
    host = [(name, s0 - 10**9, s0 + d0 - 10**9)]
    red = trace.reduce(events, host, [], host[0][1], host[0][2])
    # the readings of the reduction as it stood before the program had
    # spans (the recorded excerpt holds 6 of the step's 892 launches)
    assert red["window_s"] == pytest.approx(0.00530231, rel=1e-12)
    assert red["busy_s"] == pytest.approx(1.6987e-05, rel=1e-12)
    assert red["idle_gaps"] == [["execute",
                                 pytest.approx(0.005285323, rel=1e-12)]]
    run = {"trace": red, "traced_steps": 1, "slice_calls": 892,
           "slice_bytes": 142_173_408, "peaks": {"hbm_bytes_per_s": 819e9}}
    assert readers.arena_kernels(run) == (6, pytest.approx(7.398e-06,
                                                           rel=1e-12))
    assert _reader("arena_ms.exec")(run) == pytest.approx(0.007398,
                                                           rel=1e-12)
    assert _reader("idle_share.exec")(run) == pytest.approx(
        99.6796301989133, rel=1e-12)
    assert _reader("arena_roofline.exec")(run) is None
    spans = [["chipbench." + n, s, d] for n, s, d in events["spans"]]
    gaps = program_trace.idle_gaps(events["ops"], spans, s0, s0 + d0, 0.0)
    assert gaps == [["execute", pytest.approx(red["idle_gaps"][0][1],
                                              rel=1e-9)]]


def _profile(out: Path, steps: int, program_spans: bool = True):
    """A real trace, on the CPU, of ``steps`` executes of a tiny jitted
    program; returns the spans' host durations by name."""
    import jax
    import jax.numpy as jnp

    from repro import obs

    f = jax.jit(lambda x: jnp.sin(x) * 2)
    x = jnp.ones(256)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        with obs.recording() as rec:
            for _ in range(steps):
                if not program_spans:
                    f(x).block_until_ready()
                    continue
                with obs.span("execute"):
                    with obs.span("execute.resolve_inputs"):
                        y = x + 1
                    with obs.span("execute.dispatch"):
                        y = f(y)
                y.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    by: dict = {}
    for s in rec.spans:
        by.setdefault("repro." + s.name, []).append(s.t1_ns - s.t0_ns)
    return by


def test_load_reads_program_spans_and_launches(tmp_path, monkeypatch):
    monkeypatch.setattr(program_trace, "TRACES", tmp_path)
    by = _profile(tmp_path / "cell", 3)
    path = program_trace.run_trace()
    ev = program_trace.load(path)
    names = [n for n, _, _ in ev["spans"]]
    for name in READERS.values():
        assert names.count(name) == 3
    # the CPU client tags each launch with its run id
    assert ev["launches"]
    # a span in the trace lasts about what the recording measured
    (d,) = {sum(d for n, _, d in ev["spans"] if n == "repro.execute")}
    assert d == pytest.approx(sum(by["repro.execute"]), rel=0.5)


def test_readers_read_the_runs_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(program_trace, "TRACES", tmp_path)
    run = {"trace": {"window_s": 0.01}, "traced_steps": 4}
    # no trace written yet
    for name in READERS:
        assert _reader(name)(run) is None
    _profile(tmp_path / "cell", 4)
    ev = program_trace.load(program_trace.run_trace())
    values = {name: _reader(name)(run) for name in READERS}
    for name, span in READERS.items():
        ds = [d for n, _, d in ev["spans"] if n == span]
        assert len(ds) == 4
        assert values[name] == pytest.approx(1e-6 * sum(ds) / 4)
    assert values["resolve_ms.exec"] + values["dispatch_ms.exec"] \
        <= values["execute_host_ms.exec"]
    # an untraced run reads nothing
    for name in READERS:
        assert _reader(name)({"steps": 4}) is None


def test_readers_read_nothing_without_program_spans(tmp_path, monkeypatch):
    """A program without ``repro.obs`` leaves no spans in its trace."""
    monkeypatch.setattr(program_trace, "TRACES", tmp_path)
    _profile(tmp_path / "cell", 2, program_spans=False)
    run = {"trace": {"window_s": 0.01}, "traced_steps": 2}
    for name in READERS:
        assert _reader(name)(run) is None
