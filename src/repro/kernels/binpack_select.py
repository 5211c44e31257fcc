"""Fit-strategy bin selection as a Pallas TPU kernel, batched over streams.

The packer's inner operation -- "given bin loads and an item, pick the
first/best/worst bin it fits in" -- is a masked argmin/argmax reduction.
Evaluating algorithm sweeps (12 algorithms x 6 deltas x 500 iterations x
batches of streams) on device makes this the hot loop, so the kernel grid
carries an explicit *batch* dimension: each program instance reduces
``(rows, M)`` tiles of (loads, item) instances for up to 8 streams of the
batch, with the loads tiles resident in VMEM.  Both grid dimensions are
parallel, so one launch covers the entire ``f32[B, N, M]`` sweep.

Semantics match ``repro.core.jaxpack._select_slot``: ties break to the
lowest slot, an item "fits" iff load + w <= capacity and slot < k.
Returns slot = M (out of range) when nothing fits.

Masking (variable-N fleets): pass ``active`` (i32/bool per instance) and
inactive instances -- partitions that do not currently exist -- return
slot = ``NEG`` (-1): they select no bin at all, distinct from "exists but
nothing fits" (= M).  ``active=None`` keeps the exact unmasked program.

On hosts without a TPU the wrappers fall back to Pallas interpreter mode
automatically, so the same call sites work in CI and on device.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._compat import default_interpret as _default_interpret
from ._compat import pad_rows as _pad_rows
from ._compat import row_tile as _row_tile

_BIG = 3.4e38  # python literal: jnp scalars would be captured as consts

DEFAULT_ROW_TILE = 256
NEG = -1       # "inactive instance": the item does not exist, no slot at all


def _select_tile_kernel(loads_ref, w_ref, k_ref, cap_ref, *rest, strategy: str,
                        m: int, rows: int, streams: int, masked: bool):
    """``streams`` x ``(rows, M)`` tiles: row-wise masked argmin/argmax
    along the M axis, ties to the lowest slot (a double min).  The
    per-instance rows are transposed once into ``(rows, streams)`` so
    each stream's instances are a column against its load tile."""
    slot_ref = rest[-1]
    w_t, k_t, cap_t = w_ref[...].T, k_ref[...].T, cap_ref[...].T
    if masked:
        act_t = rest[0][...].T
    idx = jax.lax.broadcasted_iota(jnp.int32, (rows, m), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, streams), 1)
    plane = lambda x: jnp.broadcast_to(x, (rows, m))
    slots = jnp.zeros((rows, streams), jnp.int32)
    for r in range(streams):
        col = slice(r, r + 1)
        loads = loads_ref[r]                              # (rows, M)
        fits = ((idx < plane(k_t[:, col]))
                & (loads + plane(w_t[:, col]) <= plane(cap_t[:, col])))
        if strategy == "first":
            score = jnp.where(fits, idx.astype(jnp.float32), _BIG)
        elif strategy == "best":      # tightest fit = max load; first on tie
            score = jnp.where(fits, -loads, _BIG)
        elif strategy == "worst":     # most slack = min load; first on tie
            score = jnp.where(fits, loads, _BIG)
        else:
            raise ValueError(strategy)
        low = jnp.min(score, axis=1, keepdims=True)
        best = jnp.min(jnp.where(score == low, idx, m), axis=1,
                       keepdims=True)
        found = jnp.max(fits.astype(jnp.int32), axis=1, keepdims=True) > 0
        slot = jnp.where(found, best, jnp.int32(m))       # (rows, 1)
        if masked:
            slot = jnp.where(act_t[:, col] > 0, slot, jnp.int32(NEG))
        slots = jnp.where(lane == r, slot, slots)
    slot_ref[...] = slots.T


def select_slot_grid(loads, w, k, capacity, *, active=None,
                     strategy: str = "best",
                     row_tile: int = DEFAULT_ROW_TILE,
                     interpret: bool | None = None):
    """Batched fit-selection over a grid of streams.

    loads: (B, N, M) f32 bin loads; w, capacity: (B, N) f32; k: (B, N) i32
    (bins created); active: optional (B, N) i32/bool -- 0 marks an
    instance whose item does not exist.  Returns (B, N) i32 chosen slot
    per instance (M when nothing fits, ``NEG`` when inactive).  One kernel
    launch; ``grid = (ceil(B / streams), ceil(N / rows))`` with
    ``streams = row_tile(B)`` and ``rows`` the whole N or ``row_tile``
    rounded up to a lane multiple (128).
    """
    if interpret is None:
        interpret = _default_interpret()
    masked = active is not None
    b, n, m = loads.shape
    lane_tile = -(-row_tile // 128) * 128
    rows = n if n <= lane_tile else lane_tile
    streams = _row_tile(b)
    pad = (-n) % rows
    # padded instances see k=0 -> nothing fits; their output is sliced off
    args = [loads.astype(jnp.float32), w.astype(jnp.float32),
            k.astype(jnp.int32), capacity.astype(jnp.float32)]
    if masked:
        args.append(active.astype(jnp.int32))
    pad_n = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
    args = [_pad_rows(pad_n(a), streams) for a in args]
    b_pad, n_pad = args[1].shape
    kernel = functools.partial(_select_tile_kernel, strategy=strategy, m=m,
                               rows=rows, streams=streams, masked=masked)
    row_spec = pl.BlockSpec((streams, rows), lambda i, j: (i, j))
    in_specs = [pl.BlockSpec((streams, rows, m), lambda i, j: (i, j, 0))]
    in_specs += [row_spec] * (len(args) - 1)
    call = pl.pallas_call(
        kernel,
        grid=(b_pad // streams, n_pad // rows),
        in_specs=in_specs,
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((b_pad, n_pad), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )
    return call(*args)[:b, :n]


def select_slot_batch(loads, w, k, capacity, *, active=None,
                      strategy: str = "best",
                      interpret: bool | None = None):
    """loads: (N, M) f32; w, capacity: (N,) f32; k: (N,) i32 (bins created);
    active: optional (N,) i32/bool instance mask.

    Returns (N,) i32 chosen slot per instance (M = nothing fits, ``NEG`` =
    inactive).  Thin wrapper over ``select_slot_grid`` with a singleton
    batch dimension.
    """
    return select_slot_grid(loads[None], w[None], k[None], capacity[None],
                            active=None if active is None else active[None],
                            strategy=strategy, interpret=interpret)[0]
