"""Committed cells cut to sizes the CPU runs in seconds."""
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def small_cell(name: str):
    """The cell as committed, cut to a size the CPU runs in seconds."""
    from chipbench.bench import harness

    cell = harness.load_cell(name)
    cell.config["graph"]["args"] = {"n_cells": 2, "n": 8}
    cell.params["check_steps"] = 8
    return cell


def run_small(cell, seed, *, seconds=2.0, trace=False, control=False):
    from chipbench.bench import harness

    return harness.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                            t_start=time.perf_counter(), require_tpu=False,
                            control=control)
