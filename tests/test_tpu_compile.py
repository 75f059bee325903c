"""Compile rehearsals: the main-path Pallas kernels, compiled for one
described TPU v5e chip at the production blocks (128, 128, 512) and the
shapes ``chip_smoke.py`` drives.

Nothing runs — the TPU compiler refuses here what it would refuse on the
chip (VMEM overruns, tiling-misaligned slices, SMEM sizes), at no chip
time.  The topology is described inside a fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  The tests skip where no topology can be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import butterfly, butterfly_sparse, butterfly_tiled

BLOCKS = (128, 128, 512)
N_U, N_V, PEEL = 16384, 8192, 4096      # chip_smoke.py dense CD shapes
G, MM, W = 8, 2048, 256                 # an FD level-peel stack
N_SLOTS, N_CT = 2048, 16                # tiled: 128 row bands x 16 cols
F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_dense_support_compiles(one_chip):
    _compile(lambda a, b, s, ia, ib: butterfly.butterfly_support_pallas(
        a, b, s, ia, ib, blocks=BLOCKS), one_chip,
        ((N_U, N_V), F32), ((PEEL, N_V), F32), ((PEEL,), F32),
        ((N_U,), I32), ((PEEL,), I32))


def test_dense_batched_compiles(one_chip):
    _compile(lambda a, b, s, ia, ib:
             butterfly.butterfly_update_pallas_batched(
                 a, b, s, ia, ib, blocks=BLOCKS), one_chip,
             ((G, MM, N_V), F32), ((G, W, N_V), F32), ((G, W), F32),
             ((G, MM), I32), ((G, W), I32))


def test_sparse_update_compiles(one_chip):
    _compile(lambda a, b, s, ia, ib, ka, kb:
             butterfly_sparse.butterfly_update_pallas_sparse(
                 a, b, s, ia, ib, ka, kb, blocks=BLOCKS), one_chip,
             ((N_U, N_V), F32), ((PEEL, N_V), F32), ((PEEL,), F32),
             ((N_U,), I32), ((PEEL,), I32), ((N_U // 128,), I32),
             ((PEEL // 128,), I32))


def test_sparse_batched_compiles(one_chip):
    _compile(lambda a, b, s, ia, ib, ka, kb:
             butterfly_sparse.butterfly_update_pallas_sparse_batched(
                 a, b, s, ia, ib, ka, kb, blocks=BLOCKS), one_chip,
             ((G, MM, N_V), F32), ((G, W, N_V), F32), ((G, W), F32),
             ((G, MM), I32), ((G, W), I32), ((G, MM // 128), I32),
             ((G, W // 128), I32))


def test_b2_stack_compiles(one_chip):
    _compile(lambda a, k: butterfly_sparse.b2_stack_pallas_sparse(
        a, k, blocks=BLOCKS), one_chip,
        ((4, 1024, N_V), F32), ((4, 1024 // 128), I32))


def test_tiled_update_compiles(one_chip):
    n_rt = N_U // BLOCKS[0]
    _compile(lambda td, sr, sc, sp, po, sl, s:
             butterfly_tiled.butterfly_update_pallas_tiled(
                 td, sr, sc, sp, po, sl, s), one_chip,
             ((N_SLOTS, BLOCKS[0], BLOCKS[2]), F32), ((N_SLOTS,), I32),
             ((N_SLOTS,), I32), ((n_rt + 1,), I32), ((n_rt, N_CT), I32),
             ((N_SLOTS,), I32), ((N_U,), F32))


def _largest(fits, lo):
    """The largest multiple of 8 at or above ``lo`` that ``fits`` admits."""
    hi = lo
    while fits(hi * 2):
        hi *= 2
    step = hi
    while step >= 8:
        if fits(hi + step):
            hi += step
        step //= 2
    return hi


@pytest.mark.parametrize("edge", ["smem", "vmem"])
def test_tiled_update_compiles_at_chip_limits(one_chip, edge):
    # the largest tables the Planner admits for the compiled tiled kernel
    # (butterfly_tiled.tiled_kernel_fits) compile for one chip: SMEM
    # binds the reverse map and slot tables, VMEM the resident output
    bi, bk = BLOCKS[0], BLOCKS[2]

    def fits(n_rt, n_ct, n_slots):
        return butterfly_tiled.tiled_kernel_fits(n_rt, n_ct, n_slots, bi, bk)

    if edge == "smem":
        n_rt, n_ct = 8192, N_CT
        n_slots = _largest(lambda n: fits(n_rt, n_ct, n), n_rt)
        assert not fits(n_rt, n_ct, n_slots + 1024)
    else:
        n_ct = 1
        n_rt = n_slots = _largest(lambda n: fits(n, n_ct, n), 8)
        assert not fits(n_rt + 8, n_ct, n_slots)
    _compile(lambda td, sr, sc, sp, po, sl, s:
             butterfly_tiled.butterfly_update_pallas_tiled(
                 td, sr, sc, sp, po, sl, s), one_chip,
             ((n_slots, bi, bk), F32), ((n_slots,), I32), ((n_slots,), I32),
             ((n_rt + 1,), I32), ((n_rt, n_ct), I32), ((n_slots,), I32),
             ((n_rt * bi,), F32))
