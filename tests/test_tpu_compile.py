"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode (every other kernel test here) runs the kernel body in
Python and accepts what Mosaic refuses: a vector gather, a lane slice at a
dynamic index, a block that does not tile (8, 128).  These tests hand each
kernel to the TPU compiler that libtpu ships, for a ``v5e:2x2`` topology
that is described and not attached, at the shapes ``chip_smoke.py`` trains:
4 agents of the 156.5M-parameter tiny LM (flat D = 156,519,168, a ragged
last tile), ring graph (ELL max degree 2), f32, each kernel at the D tile
``kernels.ops`` sizes for it from its VMEM budget; and the Qwen1.5-4B
benchmark cell's fused update+mix at its own D = 255,864,320, to show
that the budget's tile fits VMEM; and ``FlatSpec.flatten``/``unflatten`` of
a leaf whose rows are not lane-aligned, to show that no per-agent loop is
left.  Nothing runs; a compile that passes is not a chip run.

The topology is described inside a module-scoped fixture, never at import:
only one process may load libtpu, and under pytest-xdist every worker
imports this file while only the worker given it runs these tests.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import compress_mix as cm
from repro.kernels import gossip_mix as gm
from repro.kernels import ops
from repro.kernels import update_mix as um

N, DEG, R = 4, 2, 2
D = 156_519_168          # tiny_lm_config() flat size (d_model 768, 12 layers)
D_CELL = 255_864_320     # the Qwen1.5-4B benchmark cell's flat size


def _bd(streams, f32_streams=0, scratch=0):
    """The D tile kernels.ops gives a call of ``streams`` f32 operands
    (``f32_streams`` more for a momentum slot, ``scratch`` ELL tiles)."""
    return ops.autotune_block_d(N, (jnp.float32,) * (streams + f32_streams),
                                scratch=scratch)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile is written to a persistent cache but cannot
    # be read back without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _s(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _f32(*shape):
    return (shape, jnp.float32)


_ELL = ((N, DEG), jnp.int32)
_ELL_R = ((R, N, DEG), jnp.int32)
_ELL_S = dict(scratch=gm.ELL_SCRATCH)
_MOM = dict(beta=0.9)

# name -> (kernel, its static keywords, argument (shape, dtype)s, block_d);
# every kernel but dequant_mix takes the D tile the VMEM budget gives it
CASES = {
    "gossip_mix": (
        gm.gossip_mix_pallas, {}, [_f32(N, N), _f32(N, D)], _bd(2)),
    "gossip_mix_batched": (
        gm.gossip_mix_batched_pallas, {},
        [_f32(R, N, N), _f32(R, N, D)], _bd(2)),
    "update_mix_sgd": (
        um.update_mix_pallas, {},
        [_f32(N, N), _f32(N, D), _f32(N, D), _f32(1, 1)], _bd(3)),
    # the benchmark cell's kernel at its own D: its VMEM fits at compile
    "update_mix_sgd_cell": (
        um.update_mix_pallas, {},
        [_f32(N, N), _f32(N, D_CELL), _f32(N, D_CELL), _f32(1, 1)], _bd(3)),
    "update_mix_momentum": (
        um.update_mix_pallas, _MOM,
        [_f32(N, N), _f32(N, D), _f32(N, D), _f32(1, 1), _f32(N, D)],
        _bd(3, 2)),
    "update_mix_batched_momentum": (
        um.update_mix_batched_pallas, _MOM,
        [_f32(R, N, N), _f32(R, N, D), _f32(R, N, D), _f32(R, 1),
         _f32(R, N, D)], _bd(3, 2)),
    "ef_mix": (
        um.ef_mix_pallas, {},
        [_f32(N, N), _f32(N), _f32(N, D), _f32(N, D), _f32(N, D)], _bd(5)),
    "ef_mix_batched": (
        um.ef_mix_batched_pallas, {},
        [_f32(R, N, N), _f32(R, N), _f32(R, N, D), _f32(R, N, D),
         _f32(R, N, D)], _bd(5)),
    "dequant_mix": (
        cm.dequant_mix_pallas, {},
        [_f32(N, N), _f32(N), _f32(N), ((N, D), jnp.int8), _f32(N, D)],
        cm.BLOCK_D),
    "gossip_mix_sparse": (
        gm.gossip_mix_sparse_pallas, {},
        [_ELL, _f32(N, DEG), _f32(N), _f32(N, D)], _bd(2, **_ELL_S)),
    "gossip_mix_sparse_batched": (
        gm.gossip_mix_sparse_batched_pallas, {},
        [_ELL_R, _f32(R, N, DEG), _f32(R, N), _f32(R, N, D)],
        _bd(2, **_ELL_S)),
    "update_mix_sparse_sgd": (
        um.update_mix_sparse_pallas, {},
        [_ELL, _f32(N, DEG), _f32(N), _f32(N, D), _f32(N, D), _f32(1, 1)],
        _bd(3, **_ELL_S)),
    "update_mix_sparse_momentum": (
        um.update_mix_sparse_pallas, dict(_MOM, nesterov=True),
        [_ELL, _f32(N, DEG), _f32(N), _f32(N, D), _f32(N, D), _f32(1, 1),
         _f32(N, D)], _bd(3, 2, **_ELL_S)),
    "update_mix_sparse_batched_sgd": (
        um.update_mix_sparse_batched_pallas, {},
        [_ELL_R, _f32(R, N, DEG), _f32(R, N), _f32(R, N, D),
         _f32(R, N, D), _f32(R, 1)], _bd(3, **_ELL_S)),
    "update_mix_sparse_batched_momentum": (
        um.update_mix_sparse_batched_pallas, _MOM,
        [_ELL_R, _f32(R, N, DEG), _f32(R, N), _f32(R, N, D),
         _f32(R, N, D), _f32(R, 1), _f32(R, N, D)], _bd(3, 2, **_ELL_S)),
    "ef_mix_sparse": (
        um.ef_mix_sparse_pallas, {},
        [_ELL, _f32(N, DEG), _f32(N), _f32(N, D), _f32(N, D), _f32(N, D)],
        _bd(5, **_ELL_S)),
    "ef_mix_sparse_batched": (
        um.ef_mix_sparse_batched_pallas, {},
        [_ELL_R, _f32(R, N, DEG), _f32(R, N), _f32(R, N, D), _f32(R, N, D),
         _f32(R, N, D)], _bd(5, **_ELL_S)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, static, arg_specs, bd = CASES[name]
    args = [_s(one_chip, shape, dtype) for shape, dtype in arg_specs]
    compiled = jax.jit(functools.partial(fn, block_d=bd, **static)).lower(
        *args).compile()
    text = compiled.as_text()
    # the Mosaic kernel is in the program, not an interpret-mode expansion
    assert "tpu_custom_call" in text, name
    # ... under its public op name, whatever wraps the call
    kernel = re.sub(r"_(sgd|momentum)(_cell)?$", "", name)
    calls = re.findall(r"%?([\w.\-]+) = [^\n]*custom_call_target="
                       r"\"tpu_custom_call\"", text)
    assert calls and all(re.fullmatch(rf"{kernel}(\.\d+)?", c)
                         for c in calls), (name, calls)
    # the kernel streams the buffers in place: no padded (n, D) copy
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < N * bd * 64, (name, mem)


@pytest.mark.parametrize("direction", ["flatten", "unflatten"])
def test_flat_spec_moves_unaligned_rows_in_one_pass(one_chip, direction):
    """A leaf whose rows (1,000 columns) are not lane-aligned but whose
    per-agent size is moves between its shape and the (n, D) buffer with
    no ``while`` loop over agents: XLA:TPU moves such a leaf, reshaped in
    place, into the buffer's (n, 128) tiles and back one agent at a time
    (from a few million elements an agent: 2,560 rows do, 256 do not), and
    FlatSpec's swap behind an optimization barrier keeps the loop out."""
    from repro.core import flat as flat_lib

    spec = flat_lib.make_flat_spec(
        {"w": jax.ShapeDtypeStruct((2560, 1000), jnp.float32),
         "b": jax.ShapeDtypeStruct((1000,), jnp.float32)})
    assert spec.lane_dense_leaves == (1,)      # "w", after "b"
    if direction == "flatten":
        fn, arg = spec.flatten, {"w": _s(one_chip, (N, 2560, 1000)),
                                 "b": _s(one_chip, (N, 1000))}
    else:
        fn, arg = spec.unflatten, _s(one_chip, (N, spec.d))
    text = jax.jit(fn).lower(arg).compile().as_text()
    assert f"feddec.{direction}" in text
    assert not re.search(r"\bwhile\(", text), direction
