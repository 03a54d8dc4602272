"""The trace reduction, on a hand-made trace and on a small recorded one."""

import json
from pathlib import Path

import pytest

from chipbench.bench import readers, trace

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000


def test_op_kinds_from_hlo_text():
    assert trace.op_kind(
        '%_program.892 = f32[299520]{0:T(1024)} custom-call(f32[32768]'
        '{0:T(1024)S(1)} %pad.0, f32[299520]{0:T(1024)} %arena.1), '
        'custom_call_target="tpu_custom_call"') == "custom-call:tpu_custom_call"
    assert trace.op_kind(
        "%copy-start = (f32[8]{0:T(1024)S(1)}, f32[8]{0:T(1024)}, u32[]"
        "{:S(2)}) copy-start(f32[8]{0:T(1024)} %p)") == "copy-start"
    assert trace.op_kind("%iota.1 = f32[8]{0} iota(), iota_dimension=0") \
        == "iota"


def _hand_made():
    # trace clock = host perf_counter clock + 5 ms
    events = {
        "device_plane": "/device:TPU:0",
        "modules": [["jit_step", 10 * MS, 4 * MS], ["jit_step", 20 * MS,
                                                     4 * MS]],
        "ops": [["fusion", 10 * MS, 2 * MS, "jit_step"],
                # overlaps the fusion: counted once in the busy union
                ["custom-call:tpu_custom_call", 11 * MS, 3 * MS, "jit_step"],
                ["custom-call:tpu_custom_call", 20 * MS, 4 * MS, "jit_step"],
                # outside the traced window
                ["fusion", 40 * MS, 1 * MS, "jit_step"]],
        "spans": [["step", 9 * MS, 6 * MS], ["step", 19 * MS, 6 * MS]],
    }
    host = [("step", 4 * MS, 10 * MS), ("step", 14 * MS, 20 * MS)]
    compiles = [(10 * MS, 12 * MS)]        # host clock: 15-17 ms traced
    return events, host, compiles


def test_reduce_hand_made():
    events, host, compiles = _hand_made()
    red = trace.reduce(events, host, compiles, 4 * MS, 24 * MS)
    assert red["window_s"] == pytest.approx(0.020)
    # busy: [10, 14) and [20, 24) ms
    assert red["busy_s"] == pytest.approx(0.008)
    assert red["ops"]["jit_step/custom-call:tpu_custom_call"] == \
        [2, pytest.approx(0.007)]
    assert red["modules"]["jit_step"] == [2, pytest.approx(0.008)]
    # idle: [9, 10) step, [14, 20) mostly compile then step, [24, 29) none
    labels = dict(red["idle_gaps"])
    assert labels["step"] == pytest.approx(0.001)
    assert labels["compile"] == pytest.approx(0.006)
    assert labels["other"] == pytest.approx(0.005)
    assert sum(labels.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    run = {"trace": red, "traced_steps": 2}
    assert readers.arena_kernels(run) == (2, pytest.approx(0.007))
    assert readers.idle_share(run) == pytest.approx(60.0)


def test_no_device_plane_reads_nothing():
    events, host, compiles = _hand_made()
    events = dict(events, device_plane=None, ops=[], modules=[])
    red = trace.reduce(events, host, compiles, 4 * MS, 24 * MS)
    run = {"trace": red, "traced_steps": 2}
    assert readers.idle_share(run) is None
    assert readers.arena_kernels(run) is None


def _union_ns(intervals):
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def test_recorded_randwire_trace():
    """The start of one traced step of the RandWire-274 cell on one TPU v5
    lite: the eight small eager programs `execute` dispatches, then the
    first ops of the arena program (`jit__program`): pads, arena kernels
    (`tpu_custom_call`), slices."""
    events = json.loads((DATA / "randwire274_exec_first_step.json")
                        .read_text())
    (name, s0, d0), = events["spans"]
    # the recorded run's host clock is not kept: put it 1 s behind
    host = [(name, s0 - 10**9, s0 + d0 - 10**9)]
    red = trace.reduce(events, host, [], host[0][1], host[0][2])
    assert red["window_s"] == pytest.approx(d0 / 1e9)
    inside = [(max(s, s0), min(s + d, s0 + d0))
              for _, s, d, _ in events["ops"] if s + d > s0 and s < s0 + d0]
    assert red["busy_s"] == pytest.approx(_union_ns(inside) / 1e9)
    assert red["busy_s"] < red["window_s"]
    kernels = [o for o in events["ops"]
               if o[0] == "custom-call:tpu_custom_call"]
    assert kernels and all(o[3] == "jit__program" for o in kernels)
    assert red["ops"]["jit__program/custom-call:tpu_custom_call"][0] == \
        len([o for o in kernels if o[1] + o[2] > s0 and o[1] < s0 + d0])
    assert red["modules"]["jit__program"][0] == 1
    assert dict(red["idle_gaps"])["execute"] == pytest.approx(
        red["window_s"] - red["busy_s"])
    run = {"trace": red, "traced_steps": 1, "slice_calls": 892,
           "slice_bytes": 142_173_408, "peaks": {"hbm_bytes_per_s": 819e9}}
    # the excerpt holds a few of the step's 892 launches: the roofline
    # share is not read from a trace that does not hold them all
    assert readers.arena_kernels(run)[0] == len(kernels)
    assert _reader("arena_roofline.exec")(run) is None


def _reader(name):
    from chipbench.bench.harness import ROOT, _reader as load

    return load(name, ROOT)
