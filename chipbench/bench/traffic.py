"""Traffic mixes: parameter files that the cell runners read.

A mix file (``chipbench/traffic/<mix>.json``) holds only parameters.  A
mix for an executed graph (``{"loop": "closed", "clients": 1,
"inputs": 4}``) gives the number of distinct seeded inputs the window
cycles through, one step in flight.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

SEED_MOD = 1 << 63


def rng_for(seed: int, salt: int = 0) -> np.random.Generator:
    """A numpy generator for any whole-number seed (negative or > 2**32)."""
    return np.random.default_rng([seed % SEED_MOD, salt])


def load_mix(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    if mix.get("loop") != "closed":
        raise ValueError(f"{path}: loop must be 'closed'")
    return mix
