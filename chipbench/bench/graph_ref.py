"""Plain reference of an executed graph's surrogate numerics, on the host.

Benchmark graphs carry byte sizes, not tensor semantics, so the executor
computes a deterministic surrogate: every tensor is a flat float32 vector
of ``size_bytes / 4`` elements and every op a fixed value- and
position-sensitive function of its inputs (``repro.core.executor``'s
module documentation states the contract).  This module evaluates the same
functions in numpy, node by node in topological order with every tensor in
its own array: no arena, no schedule, no offsets, nothing of the program.
A wrong offset, a premature overwrite or a wrong kernel shows up as a
numeric mismatch.

``dtype=bfloat16`` is the control: every tensor rounded to bfloat16 as it
is produced.
"""

from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np

f32 = np.float32
BF16 = np.dtype(ml_dtypes.bfloat16)


@dataclasses.dataclass(frozen=True)
class RefNode:
    id: int
    op: str
    size_bytes: int
    preds: tuple[int, ...]
    alias_preds: frozenset
    sig: int                 # id keying the positional ramp


def _sigmoid(x):
    return f32(1) / (f32(1) + np.exp(-x))


def _gelu(x):
    c = f32(np.sqrt(2.0 / np.pi))
    return f32(0.5) * x * (f32(1) + np.tanh(c * (x + f32(0.044715) * x * x
                                                   * x)))


ELEMWISE = {
    "relu": lambda x: np.maximum(x, f32(0)),
    "relu6": lambda x: np.clip(x, f32(0), f32(6)),
    "bn": lambda x: f32(1.05) * x - f32(0.02),
    "batchnorm": lambda x: f32(1.05) * x - f32(0.02),
    "sigmoid": _sigmoid,
    "tanh": np.tanh,
    "gelu": _gelu,
    "silu": lambda x: x * _sigmoid(x),
    "bias_add": lambda x: x + f32(0.05),
    "scale": lambda x: f32(0.9) * x,
    "dropout": lambda x: x,
    "identity": lambda x: x,
    "cast_inplace": lambda x: x,
}


def _fit(x, n):
    m = x.shape[0]
    if m == n:
        return x
    if m == 0:
        return np.zeros(n, f32)
    if m > n:
        return x[:n]
    return np.tile(x, -(-n // m))[:n]


def _concat_pad(xs, n):
    cc = np.concatenate(xs) if xs else np.zeros(0, f32)
    if cc.shape[0] >= n:
        return cc[:n]
    return np.concatenate([cc, np.zeros(n - cc.shape[0], f32)])


def _ramp(sig, n):
    k = f32(0.37 + 0.013 * (sig % 29))
    return f32(0.05) * np.cos(np.arange(n, dtype=f32) * k)


def _blend(xs, n):
    if not xs:
        return np.zeros(n, f32)
    acc = _fit(xs[0], n)
    for x in xs[1:]:
        acc = acc + _fit(x, n)
    return acc / f32(len(xs))


def _mix(t):
    return np.tanh(t + f32(0.25) * np.roll(t, 1))


def node_value(nd: RefNode, invals, n):
    if nd.op in ("concat", "concat_view"):
        return _concat_pad(invals, n)
    if nd.op == "partial_conv":
        acc, branches = None, []
        for p, v in zip(nd.preds, invals):
            if p in nd.alias_preds and acc is None:
                acc = v
            else:
                branches.append(v)
        contrib = f32(0.4) * _mix(_blend(branches, n)) \
            + f32(0.1) * _ramp(nd.sig, n)
        return contrib if acc is None else acc + contrib
    if nd.op == "add":
        return _blend(invals, n)
    if nd.op in ELEMWISE and len(invals) == 1:
        return ELEMWISE[nd.op](_fit(invals[0], n))
    return f32(0.9) * _mix(_blend(invals, n)) + _ramp(nd.sig, n)


def _topological(nodes: list[RefNode]) -> list[RefNode]:
    by_id = {nd.id: nd for nd in nodes}
    waiting = {nd.id: len(set(nd.preds)) for nd in nodes}
    succs: dict[int, list[int]] = {nd.id: [] for nd in nodes}
    for nd in nodes:
        for p in set(nd.preds):
            succs[p].append(nd.id)
    ready = sorted(u for u, k in waiting.items() if k == 0)
    out = []
    while ready:
        u = ready.pop(0)
        out.append(by_id[u])
        for s in succs[u]:
            waiting[s] -= 1
            if waiting[s] == 0:
                ready.append(s)
    if len(out) != len(nodes):
        raise ValueError("graph has a cycle")
    return out


def run(nodes: list[RefNode], inputs: dict[int, np.ndarray],
        dtype=f32) -> dict[int, np.ndarray]:
    """Values of the graph's outputs (nodes nothing consumes), keyed by id.

    ``inputs`` maps each input node id to its flat value."""
    consumed = {p for nd in nodes for p in nd.preds}
    env: dict[int, np.ndarray] = {}
    for nd in _topological(nodes):
        n = nd.size_bytes // 4
        if nd.op == "input":
            v = _fit(np.asarray(inputs[nd.id], f32).reshape(-1), n)
        else:
            v = node_value(nd, [env[p] for p in nd.preds], n)
        env[nd.id] = v.astype(dtype).astype(f32)
    return {nd.id: env[nd.id] for nd in nodes if nd.id not in consumed}
