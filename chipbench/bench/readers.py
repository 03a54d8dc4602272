"""Shared arithmetic of the per-layer metric readers (``chipbench/metrics``).

Each reader returns ``None`` where its trace holds nothing for it to read,
and the harness then leaves the metric out of the result line.
"""

from __future__ import annotations

import re

#: the arena slice kernels: the only Pallas calls (``tpu_custom_call``) on
#: an executed graph's path (``repro/kernels/arena/kernel.py``)
ARENA_KERNEL = re.compile(r"/custom-call:tpu_custom_call$")

def _matching(table: dict, pattern) -> tuple[int, float]:
    n = t = 0
    for name, (k, s) in table.items():
        if pattern.search(name):
            n += k
            t += s
    return n, t


def arena_kernels(run) -> tuple[int, float] | None:
    """(events, device seconds) of the arena kernels in the traced part."""
    tr = run.get("trace")
    if not tr:
        return None
    n, t = _matching(tr["ops"], ARENA_KERNEL)
    return (n, t) if n else None


def idle_share(run) -> float | None:
    tr = run.get("trace")
    if not tr or not tr["device_plane"] or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
