"""The autoscaler's Pallas kernels compile for a TPU v5e at deployment N.

Nothing here runs on a chip: each kernel is lowered with
``interpret=False`` against a *described* v5e topology and compiled by
the installed TPU compiler, which refuses what interpreter mode accepts
(block shapes off the (8, 128) tiling, 1-D vector layouts, VMEM
overflow).  Shapes are the lag twin's: N = 100 partitions, a 64-stream
fleet, the annealer's 2N + 2 bin names, and N = 14 for the megakernel
(``FUSED_MAX_PARTITIONS``).

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and test workers
import every test module.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.binpack_select import select_slot_grid
from repro.kernels.lag_update import lag_update_batch, lag_update_single
from repro.kernels.loop_fused import loop_fused_batch
from repro.kernels.move_eval import move_delta_batch

B, N, N_FUSED, T = 64, 100, 14, 40
F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to an enabled persistent
    # cache but can never be read back here; keep the cache off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler installed / usable here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _select(loads, w, k, cap, act):
    return select_slot_grid(loads, w, k, cap, active=act, strategy="best",
                            interpret=False)


def _lag_batch(lag, prod, assign, readable, cap, act):
    return lag_update_batch(lag, prod, assign, readable, cap, active=act,
                            interpret=False)


def _lag_single(lag, prod, assign, readable, cap):
    return lag_update_single(lag, prod, assign, readable, cap,
                             interpret=False)


def _lag_single_vmapped(lag, prod, assign, readable, cap, act):
    """The lag engine's drain: the rank-1 entry under the scan's vmap."""
    return jax.vmap(lambda *a: lag_update_single(
        *a[:5], active=a[5], interpret=False),
        in_axes=(0, 0, 0, 0, None, 0))(lag, prod, assign, readable, cap, act)


def _moves(loads, counts, assign, speeds, prev, lam, cap, act):
    return move_delta_batch(loads, counts, assign, speeds, prev, lam, cap,
                            active=act, interpret=False)


def _fused(decreasing):
    def run(rates, act):
        return loop_fused_batch(rates, strategy="best", decreasing=decreasing,
                                active=act, interpret=False)
    return run


M_SEL, M_LAG = N + 1, 2 * N + 2
CASES = {
    "select_slot_grid": (_select, [((B, N, M_SEL), F32), ((B, N), F32),
                                   ((B, N), I32), ((B, N), F32),
                                   ((B, N), I32)]),
    "lag_update_batch": (_lag_batch, [((B, N), F32), ((B, N), F32),
                                      ((B, N), I32), ((B, N), I32),
                                      ((B, M_LAG), F32), ((B, N), I32)]),
    "lag_update_single": (_lag_single, [((N,), F32), ((N,), F32),
                                        ((N,), I32), ((N,), I32),
                                        ((M_LAG,), F32)]),
    "lag_update_single_vmapped": (_lag_single_vmapped, [
        ((B, N), F32), ((B, N), F32), ((B, N), I32), ((B, N), I32),
        ((M_LAG,), F32), ((B, N), I32)]),
    "move_delta_batch": (_moves, [((B, M_LAG), F32), ((B, M_LAG), I32),
                                  ((B, N), I32), ((B, N), F32),
                                  ((B, N), I32), ((B,), F32), ((B,), F32),
                                  ((B, N), I32)]),
    "loop_fused_batch_increasing": (_fused(False), [
        ((B, T, N_FUSED), F32), ((B, T, N_FUSED), I32)]),
    "loop_fused_batch_decreasing": (_fused(True), [
        ((B, T, N_FUSED), F32), ((B, T, N_FUSED), I32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
