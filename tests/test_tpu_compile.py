"""Compile the main-path Pallas kernels for a TPU v5e, without a chip.

Interpret mode (the rest of the suite) checks values; it cannot see what
the TPU's Mosaic compiler refuses — unaligned blocks, in-kernel
``dynamic_slice``, more VMEM than a kernel may use.  Each test here lowers
and compiles one kernel at real widths for a described ``v5e:2x2``
topology and asserts the compiled program holds the kernel
(``tpu_custom_call``).  Nothing runs, so these say nothing about results
or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU runtime, and every xdist worker imports every
test file.  The fixture skips where no topology can be described.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

N = 16384          # the largest single-chip f32 cold solve the smoke runs
BLOCK = 256        # blocked FW's default pivot block


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip cannot be read back without one:
    keep it out of the persistent cache (and the cache's warnings)."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_names(hlo: str) -> set:
    """Instruction names of the compiled program's Pallas kernels, without
    the numeric suffix: what a device trace names each kernel by."""
    return set(re.findall(
        r"%([\w-]+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        hlo))


def _kernels():
    from repro.kernels.fw_block import fw_block_pred_pallas
    from repro.kernels.fw_round import fw_round_pallas, fw_round_pred_pallas
    from repro.kernels.minplus import minplus_argmin_pallas, minplus_pallas
    from repro.kernels.row_close import row_close_pallas

    f32, i32 = jnp.float32, jnp.int32
    # every pivot block the fw_round tuner may offer, at n=16384 and batched
    rounds = {
        f"fw_round_b{b}": (
            lambda d, o, b=b: fw_round_pallas(d, o, block_size=b),
            [((N, N), f32), ((), i32)])
        for b in (32, 64)
    } | {
        f"fw_round_batched_b{b}": (
            lambda d, o, b=b: fw_round_pallas(d, o, block_size=b),
            [((256, 128, 128), f32), ((), i32)])
        for b in (32, 64)
    }
    return rounds | {
        # (fn, [(shape, dtype), ...])
        "minplus": (lambda x, y: minplus_pallas(x, y),
                    [((4096, 4096), f32), ((4096, 4096), f32)]),
        "minplus_accumulate_panel": (
            lambda x, y, a: minplus_pallas(x, y, a, accumulate=True),
            [((N, BLOCK), f32), ((BLOCK, N), f32), ((N, N), f32)]),
        "minplus_argmin": (
            lambda x, y, a: minplus_argmin_pallas(x, y, a, accumulate=True),
            [((8192, BLOCK), f32), ((BLOCK, 8192), f32), ((8192, 8192), f32)]),
        "fw_round": (
            lambda d, o: fw_round_pallas(d, o, block_size=BLOCK),
            [((N, N), f32), ((), i32)]),
        # road8k.cold's own round: 1024-lane tiles, 32-row bands
        "fw_round_n8192": (
            lambda d, o: fw_round_pallas(d, o, block_size=BLOCK),
            [((8192, 8192), f32), ((), i32)]),
        "fw_round_batched": (
            lambda d, o: fw_round_pallas(d, o, block_size=128),
            [((256, 128, 128), f32), ((), i32)]),
        "fw_round_pred": (
            lambda d, p, o: fw_round_pred_pallas(d, p, o, block_size=BLOCK),
            [((N, N), f32), ((N, N), i32), ((), i32)]),
        "fw_round_pred_batched": (
            lambda d, p, o: fw_round_pred_pallas(d, p, o, block_size=128),
            [((256, 128, 128), f32), ((256, 128, 128), i32), ((), i32)]),
        "fw_block_pred": (
            lambda d, p: fw_block_pred_pallas(d, p),
            [((BLOCK, BLOCK), f32), ((BLOCK, BLOCK), i32)]),
        "row_close": (lambda d, r: row_close_pallas(d, r),
                      [((N, N), f32), ((64,), i32)]),
        "row_close_track": (lambda d, r: row_close_pallas(d, r, track=True),
                            [((N, N), f32), ((64,), i32)]),
    }


@pytest.mark.parametrize("name", sorted(_kernels()))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, specs = _kernels()[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    assert "tpu_custom_call" in _compile(fn, *args)


def test_distributed_fw_compiles_for_v5e_2x2(topo, no_compile_cache,
                                             monkeypatch):
    # the four-chip path: SUMMA-style blocked FW over shard_map, Pallas
    # kernels inside each shard, a quarter of the matrix per device
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    from repro.core.distributed import fw_distributed

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    x = jax.ShapeDtypeStruct((N, N), jnp.float32,
                             sharding=NamedSharding(mesh, P("data", "model")))
    compiled = fw_distributed.lower(x, mesh=mesh, block_size=512).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().argument_size_in_bytes == N * N * 4 // 4


def test_blocked_solve_names_its_kernels(one_chip, no_compile_cache,
                                         monkeypatch):
    # the pivot closure and the stripe grid of one fused round carry their
    # own names, not the name of the function that encloses them
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    from repro.core.blocked_fw import blocked_fw

    x = jax.ShapeDtypeStruct((2048, 2048), jnp.float32, sharding=one_chip)
    hlo = _compile(lambda d: blocked_fw(d, block_size=BLOCK,
                                        round_mode="fused")[0], x)
    assert _kernel_names(hlo) == {"fw_block", "fw_round"}


def test_blocked_route_solve_names_its_kernels(one_chip, no_compile_cache,
                                               monkeypatch):
    # a solve with predecessors runs the predecessor round's own two kernels
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    from repro.core.blocked_fw import blocked_fw

    x = jax.ShapeDtypeStruct((2048, 2048), jnp.float32, sharding=one_chip)
    hlo = _compile(lambda d: blocked_fw(d, block_size=BLOCK, with_pred=True), x)
    assert _kernel_names(hlo) == {"fw_block_pred", "fw_round_pred"}


def test_minplus_variants_have_distinct_names(one_chip, no_compile_cache):
    from repro.kernels.minplus import minplus_argmin_pallas, minplus_pallas

    a = jax.ShapeDtypeStruct((512, 512), jnp.float32, sharding=one_chip)
    names = [_kernel_names(_compile(
        lambda x, y, z, f=f, acc=acc: f(x, y, z, accumulate=acc), a, a, a))
        for f in (minplus_pallas, minplus_argmin_pallas) for acc in (False, True)]
    assert names == [{"minplus"}, {"minplus_acc"}, {"minplus_argmin"},
                     {"minplus_argmin_acc"}]
