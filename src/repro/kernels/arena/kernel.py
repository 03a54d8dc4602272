"""Pallas arena slice kernels (TPU target; interpret-mode validated on CPU).

One linear arena buffer holds every intermediate activation of a scheduled
graph at the byte offsets chosen by the offset allocator (DESIGN.md §6).
Four kernels move tensors in and out of it:

  arena_write_pallas        -- copy a tensor into ``arena[offset : offset+n]``
  arena_read_pallas         -- materialize ``arena[offset : offset+n]``
  arena_accum_pallas        -- ``arena[offset : offset+n] += x`` (the
                               rewriter's accumulating partial-conv step,
                               done in place)
  arena_chain_write_pallas  -- apply a whole unary elementwise alias chain
                               (relu -> bn -> ...) to ``x`` *inside the
                               kernel* and write the result once — the fused
                               execution of an in-place chain in one launch
                               instead of one write per member
                               (DESIGN.md §11)

The arena stays in HBM.  Each kernel runs a grid over only the blocks of the
arena that ``[offset, offset+n)`` touches: the Pallas pipeline DMAs those
blocks into VMEM and back, and the write kernels alias the arena input to
the output (``input_output_aliases``), so every block outside the grid keeps
its contents without being read or copied — the arena is a true in-place
buffer, and its size is bounded by HBM, not VMEM.

A planned offset need not sit on the chip's DMA tiling (1-D HBM arrays are
tiled in runs of :data:`_TILE` elements, and a DMA window must start and end
on that tiling), so the grid starts at the block boundary below ``offset``:
``x`` is zero-padded in XLA to line up with those blocks, and a lane mask
(``offset <= index < offset+n``) selects which elements of each block take
the new value.  Elements outside the slice are written back unchanged.
Mosaic has no 8-bit vector select, so 1-byte integer blocks are widened to
int32 for the select only; the uint8 serving arena keeps its byte offsets.

Every kernel also accepts arenas with leading (row) axes — ``(..., A)`` with
the slice taken on the last axis — and its ``jax.vmap`` rule runs the kernel
once on the stacked operands with the rows whole in every block.  This is
how the batched decode step (``step_mode='vmap'``) packs a ``(bucket,
extent)`` arena matrix; Pallas's own batching rule would give each block a
single row, which Mosaic refuses.

Offsets are *static* (schedule-time constants from the ``ArenaPlan``), so
each call site compiles to a fixed grid — no scatter/gather machinery.

Units: ``offset``/lengths here are *elements* of the arena's dtype, not
bytes — callers (``repro.core.executor``) convert plan byte offsets by the
element size before dispatching.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.arena.elemwise import ELEMWISE_FNS

#: DMA tiling of a 1-D TPU array in HBM, in elements: a block's start and
#: length must be multiples of it.
_TILE = 1024
#: Cap on the elements of one block (all rows together): 256 KiB at 32 bits,
#: the width every select runs at, so the double-buffered operands and the
#: kernel's temporaries stay well inside the default VMEM limit.
_MAX_BLOCK = 1 << 16


def _blocking(size: int, offset: int, n: int,
              rows: int) -> tuple[int, int, int]:
    """``(block, first_block, n_blocks)`` of the grid over the blocks of an
    arena of ``size`` elements per row that ``[offset, offset+n)`` touches.
    The block is a power-of-two multiple of :data:`_TILE`, at least ``n``
    where the cap allows, so a slice spans at most two blocks unless it is
    longer than the cap.  An arena no longer than that block is one block
    of its full length (XLA tiles arrays of at most 512 elements more
    finely than :data:`_TILE`)."""
    cap = max(_TILE, _MAX_BLOCK // rows)
    cap = _TILE << ((cap // _TILE).bit_length() - 1)
    blk = min(cap, _TILE << max(0, (-(-n // _TILE) - 1).bit_length()))
    if size <= blk:
        return size, 0, 1
    b0 = offset // blk
    return blk, b0, -(-(offset + n) // blk) - b0


def _specs(lead: tuple[int, ...], blk: int, first: int):
    """BlockSpec of block ``first + i`` on the last axis, rows whole."""
    zeros = (0,) * len(lead)
    return pl.BlockSpec((*lead, blk), lambda i: (*zeros, first + i))


def _select(mask, new, old):
    if jnp.issubdtype(old.dtype, jnp.integer) and old.dtype.itemsize == 1:
        return jnp.where(mask, new.astype(jnp.int32),
                         old.astype(jnp.int32)).astype(old.dtype)
    return jnp.where(mask, new, old)


def _update(arena, x, *, offset: int, combine, interpret: bool):
    """``arena[..., offset:offset+n] = combine(x, that slice)``, in place."""
    n = x.shape[-1]
    lead = arena.shape[:-1]
    blk, b0, nb = _blocking(arena.shape[-1], offset, n,
                             math.prod(lead))
    head = offset - b0 * blk
    x = jnp.pad(x, [(0, 0)] * len(lead) + [(head, nb * blk - head - n)])

    def kernel(x_ref, arena_ref, out_ref):
        idx = (b0 + pl.program_id(0)) * blk + jax.lax.broadcasted_iota(
            jnp.int32, (*lead, blk), len(lead))
        old = arena_ref[...]
        out_ref[...] = _select((idx >= offset) & (idx < offset + n),
                               combine(x_ref[...], old), old)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(arena.shape, arena.dtype),
        grid=(nb,),
        in_specs=[_specs(lead, blk, 0), _specs(lead, blk, b0)],
        out_specs=_specs(lead, blk, b0),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(x, arena)


def _read(arena, *, offset: int, n: int, interpret: bool):
    lead = arena.shape[:-1]
    blk, b0, nb = _blocking(arena.shape[-1], offset, n,
                             math.prod(lead))

    def kernel(arena_ref, out_ref):
        out_ref[...] = arena_ref[...]

    window = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((*lead, nb * blk), arena.dtype),
        grid=(nb,),
        in_specs=[_specs(lead, blk, b0)],
        out_specs=_specs(lead, blk, 0),
        interpret=interpret,
    )(arena)
    head = offset - b0 * blk
    return window[..., head:head + n]


def _stacked(fn, *args):
    """Call ``fn(*args)`` with a vmap rule that calls it once more on the
    stacked operands (batch axis first, unbatched ones broadcast)."""
    f = jax.custom_batching.custom_vmap(fn)

    @f.def_vmap
    def _rule(axis_size, in_batched, *xs):
        xs = [x if b else jnp.broadcast_to(x, (axis_size, *x.shape))
              for x, b in zip(xs, in_batched)]
        return _stacked(fn, *xs), True

    return f(*args)


def _add(x, old):
    return old + x


def arena_write_pallas(arena, x, offset: int, *, interpret: bool = False):
    """Return ``arena`` with ``x`` written at element ``offset``."""
    return arena_chain_write_pallas(arena, x, offset, (), interpret=interpret)


def arena_accum_pallas(arena, x, offset: int, *, interpret: bool = False):
    """Return ``arena`` with ``x`` added into ``arena[offset : offset+n]``."""
    if x.shape[-1] == 0:
        return arena
    return _stacked(functools.partial(_update, offset=offset, combine=_add,
                                      interpret=interpret), arena, x)


def arena_read_pallas(arena, offset: int, n: int, *, interpret: bool = False):
    """Materialize ``arena[offset : offset+n]`` as a fresh ``(n,)`` tensor."""
    if n == 0:
        return jnp.zeros((*arena.shape[:-1], 0), arena.dtype)
    return _stacked(functools.partial(_read, offset=offset, n=n,
                                      interpret=interpret), arena)


def arena_chain_write_pallas(arena, x, offset: int, ops=(), *,
                             interpret: bool = False):
    """Apply the elementwise chain ``ops`` to ``x`` and write it at
    element ``offset`` — one launch for a whole in-place alias chain.

    ``ops`` are names from :data:`~repro.kernels.arena.elemwise.ELEMWISE_FNS`
    (unknown names raise ``KeyError`` at trace time); the chain composes in
    kernel registers, so the launch count of a fused region is 1 regardless
    of chain length.
    """
    fns = tuple(ELEMWISE_FNS[op] for op in ops)
    if x.shape[-1] == 0:          # an empty slice has no grid
        return arena

    def chain(x, old):
        del old
        for fn in fns:
            x = fn(x)
        return x

    return _stacked(functools.partial(_update, offset=offset, combine=chain,
                                      interpret=interpret), arena, x)
