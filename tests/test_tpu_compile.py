"""Compile the arena kernels for a described TPU v5e chip, without a chip.

The chip's compiler is installed here and compiles for a topology that is
described, not attached: these tests lower and compile (never run) the
Pallas arena kernels at the widths the main path uses, so a kernel that
Mosaic would refuse — a slice off the DMA tiling, a whole arena staged in
VMEM — fails here at no chip time.  Interpret mode cannot show either.

Widths: a float32 arena past the old whole-arena-in-VMEM ceiling (32 MiB)
at odd element offsets (the paper cells' offsets are 4-byte aligned, so odd
in float32 elements), the paper network's own arena, and the uint8 serving
arena of a full-width llama3.2-1b request (~10 MB) at byte offsets that are
multiples of 4, alone and stacked as the batched decode step stacks it.
"""

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.kernels.arena.kernel import (  # noqa: E402
    arena_accum_pallas,
    arena_chain_write_pallas,
    arena_read_pallas,
    arena_write_pallas,
)

F32_ARENA = 8 << 20            # elements: 32 MiB of float32
U8_ARENA = 10_000_000          # bytes: one full-width llama3.2-1b request
PAPER_ARENA = 1_198_080 // 4   # elements: the 274-node RandWire network
CHAIN = ("relu", "bn", "sigmoid", "gelu", "silu", "tanh", "relu6")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # any failure to describe it means: no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes, **jit_kw):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn, **jit_kw).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


KERNELS = {
    "write": lambda a, x, o: arena_write_pallas(a, x, o),
    "accum": lambda a, x, o: arena_accum_pallas(a, x, o),
    "chain_write": lambda a, x, o: arena_chain_write_pallas(a, x, o, CHAIN),
    "read": lambda a, x, o: arena_read_pallas(a, o, x.shape[-1]),
}


@pytest.mark.parametrize("kernel", sorted(KERNELS))
@pytest.mark.parametrize("arena,offset,n", [
    (F32_ARENA, 37, 1001),                     # odd offset, short slice
    (F32_ARENA, F32_ARENA - 1001, 1001),       # ragged end of the arena
    (F32_ARENA, 5, 3 << 20),                   # 12 MiB slice, many blocks
    (PAPER_ARENA, 299_321, 199),
    (32, 7, 5),                                # arena shorter than a tile
], ids=["f32-32MiB-odd", "f32-32MiB-end", "f32-32MiB-long", "f32-paper",
        "f32-tiny"])
def test_f32_arena_kernel_compiles(one_chip, kernel, arena, offset, n):
    fn = KERNELS[kernel]
    _compile(lambda a, x: fn(a, x, offset), one_chip,
             ((arena,), jnp.float32), ((n,), jnp.float32))


@pytest.mark.parametrize("kernel", ["write", "read"])
@pytest.mark.parametrize("rows", [None, 4], ids=["one", "stacked4"])
@pytest.mark.parametrize("offset,n", [
    (4, 32_768),
    (4_718_596, 4_718_592),                    # a full-width KV leaf
    (U8_ARENA - 8, 8),
], ids=["head", "kv-leaf", "end"])
def test_u8_serving_arena_kernel_compiles(one_chip, kernel, rows, offset, n):
    fn = KERNELS[kernel]
    lead = () if rows is None else (rows,)
    call = lambda a, x: fn(a, x, offset)  # noqa: E731
    if rows is not None:
        call = jax.vmap(call)
    _compile(call, one_chip, ((*lead, U8_ARENA), jnp.uint8),
             ((*lead, n), jnp.uint8))


def test_full_width_decode_state_pack_and_unpack_compile(one_chip):
    import repro.configs as configs
    from repro.core.executor import pack_buffers
    from repro.launch.serve import plan_decode_arena
    from repro.models.params import ParamDef
    from repro.models.zoo import build_model

    model = build_model(configs.get("llama3.2-1b"))
    dplan = plan_decode_arena(model, 1, 288)
    apl = dplan["plan"]
    leaves = jax.tree.leaves(model.make_cache_defs(1, 288),
                             is_leaf=lambda d: isinstance(d, ParamDef))
    shapes = [(d.shape, d.dtype) for d in leaves]
    extent = dplan["resident_extent"]
    assert extent % 4 == 0 and all(apl.offset_of(i) % 4 == 0
                                   for i in range(len(leaves)))

    def pack(arena, *xs):
        return pack_buffers(apl, dict(enumerate(xs)), arena=arena,
                            impl="pallas", jit=False)

    _compile(pack, one_chip, ((extent,), jnp.uint8), *shapes,
             donate_argnums=(0,))

    from repro.core.executor import unpack_buffer

    def unpack(arena):
        return [unpack_buffer(arena, apl, i, s, d, impl="pallas")
                for i, (s, d) in enumerate(shapes)]

    _compile(unpack, one_chip, ((extent,), jnp.uint8))


@pytest.mark.parametrize("fuse", [False, True], ids=["slice", "fused"])
def test_paper_cell_arena_program_compiles(one_chip, fuse):
    from repro.core import PlanConfig, compile_plan, plan
    from repro.graphs import darts_normal_cell

    res = plan(darts_normal_cell(), PlanConfig(rewrite=True))
    prog = compile_plan(res.graph, res.order, res.arena, fuse=fuse,
                        impl="pallas")
    ext = prog.resolve_ext(None)
    _compile(lambda a, *e: prog._program(a, e)[0], one_chip,
             ((prog.arena_elems,), jnp.float32),
             *[(e.shape, e.dtype) for e in ext])
