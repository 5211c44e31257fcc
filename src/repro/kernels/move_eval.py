"""Delta-cost evaluation of all (partition, target-bin) moves as a Pallas
TPU kernel, batched over annealing chains.

The stochastic packing optimizer (``repro.opt.anneal``) runs thousands of
simulated-annealing chains in parallel; each step every chain must know the
cost change of *every* single-item relocation -- move partition ``p`` from
its current bin to bin ``b`` -- under the objective

    cost = bins_used + (lam / C) * sum_{moved p} speed(p)

(the paper's consumer count plus the Eq. 10 R-score weighted by ``lam``).
That is an ``f32[K, N, M]`` plane per step and the optimizer's hot inner
loop, so the kernel fuses the whole evaluation into one VMEM pass per
chain: each program instance holds an 8-chain tile of bin state
(loads/counts over ``M`` name slots) plus the item data and emits each
chain's full ``(N, M)`` delta tile.  Moves that would violate capacity are
masked to ``MOVE_BLOCKED`` (a large finite sentinel); a move is allowed iff

    b != assign[p]  and  (loads[b] + w <= C   or
                          counts[b] == 0 and w > C)

-- the same oversized-item exception as ``binpack.py`` (an item wider than
a bin may sit alone in a dedicated overflow bin, nothing ever joins it).

Masking (variable-N fleets): pass ``active`` and every move of an
inactive item is additionally masked to ``MOVE_BLOCKED`` -- a partition
that does not exist can never be relocated.  Callers are responsible for
excluding inactive items from ``counts`` (the annealer does), so bins
holding only inactive items already read as empty here.  ``active=None``
keeps the exact unmasked program.

Semantics are pinned to the pure-jnp oracle ``move_delta_reference`` below
(tests/test_kernels.py); on hosts without a TPU the wrapper falls back to
Pallas interpreter mode automatically, like ``binpack_select`` and
``lag_update``.
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

# Large finite sentinel for masked (infeasible) moves.  Finite so that
# downstream softmax/Gumbel selection arithmetic (-MOVE_BLOCKED / T) stays
# inside the float32 range for any sane temperature.
MOVE_BLOCKED = 1e30


def move_delta_reference(loads, counts, assign, speeds, prev, lam, capacity,
                         *, active=None):
    """Pure-jnp oracle over ``(..., M)`` bin state and ``(..., N)`` items.

    loads:  f32[..., M] current load per bin name slot;
    counts: i32[..., M] items per bin name slot (bins with only zero-speed
            items still count as open);
    assign: i32[..., N] current bin name per item (always >= 0);
    speeds: f32[..., N] item sizes;
    prev:   i32[..., N] previous bin name per item, -1 = unassigned
            (the R-score only prices moves of previously-assigned items);
    lam:    f32[...] R-score weight, broadcast over the (N, M) plane;
    capacity: f32[...] bin size C, broadcast likewise;
    active: optional bool/i32[..., N] item mask -- every move of an item
            with ``active == 0`` is masked to ``MOVE_BLOCKED``.

    Returns f32[..., N, M]: ``delta[..., p, b]`` is the cost change of
    relocating item ``p`` to bin ``b``, or ``MOVE_BLOCKED`` when the move
    is a no-op (``b == assign[p]``) or infeasible.
    """
    loads = loads.astype(jnp.float32)
    counts = counts.astype(jnp.int32)
    assign = assign.astype(jnp.int32)
    speeds = speeds.astype(jnp.float32)
    prev = prev.astype(jnp.int32)
    m = loads.shape[-1]
    lam = jnp.asarray(lam, jnp.float32)[..., None, None]
    cap = jnp.asarray(capacity, jnp.float32)[..., None, None]

    count_a = jnp.take_along_axis(counts, assign, axis=-1)       # (..., N)
    names = jnp.arange(m, dtype=jnp.int32)                       # (M,)
    w = speeds[..., :, None]                                     # (..., N, 1)
    d_bins = ((counts[..., None, :] == 0).astype(jnp.float32)
              - (count_a[..., :, None] == 1).astype(jnp.float32))
    sticky = prev >= 0
    was_moved = ((assign != prev) & sticky).astype(jnp.float32)  # (..., N)
    now_moved = ((names != prev[..., :, None])
                 & sticky[..., :, None]).astype(jnp.float32)     # (..., N, M)
    d_r = (now_moved - was_moved[..., :, None]) * w * (lam / cap)
    allowed = ((assign[..., :, None] != names)
               & ((loads[..., None, :] + w <= cap)
                  | ((counts[..., None, :] == 0) & (w > cap))))
    if active is not None:
        allowed = allowed & active.astype(bool)[..., :, None]
    return jnp.where(allowed, d_bins + d_r, MOVE_BLOCKED)


def _move_eval_kernel(loads_ref, counts_ref, assign_ref, speeds_ref,
                      prev_ref, ratio_ref, cap_ref, *rest, rows: int, n: int,
                      m: int, masked: bool):
    """``rows`` chains: each chain's full (N, M) delta plane in one VMEM
    pass.  Item rows are transposed once per grid step into ``(N, rows)``
    so that each chain's items are an ``(N, 1)`` column against its
    ``(1, M)`` bin rows."""
    out_ref = rest[-1]
    assign_t = assign_ref[...].T                          # (N, rows)
    speeds_t = speeds_ref[...].T
    prev_t = prev_ref[...].T
    if masked:
        active_t = rest[0][...].T
    names = jax.lax.broadcasted_iota(jnp.int32, (n, m), 1)
    # Mosaic broadcasts a value along sublanes or lanes but not both in
    # one op, so every (1, M) row and (N, 1) column is widened first
    plane = lambda x: jnp.broadcast_to(x, (n, m))
    for r in range(rows):
        row = slice(r, r + 1)
        loads = plane(loads_ref[row, :])
        counts = plane(counts_ref[row, :])
        assign = assign_t[:, row]                         # (N, 1)
        prev = prev_t[:, row]
        w = speeds_t[:, row]
        cap = cap_ref[r, 0]                               # SMEM scalars
        scale = plane(w * ratio_ref[r, 0])                # w * lam / cap
        oversized = plane(w > cap)
        cur = assign == names                             # (N, M) one-hot
        count_a = jnp.sum(jnp.where(cur, counts, 0), axis=1, keepdims=True)
        d_bins = ((counts == 0).astype(jnp.float32)
                  - plane((count_a == 1).astype(jnp.float32)))
        sticky = prev >= 0                                # (N, 1)
        was_moved = plane(((assign != prev) & sticky).astype(jnp.float32))
        now_moved = ((names != prev) & plane(sticky)).astype(jnp.float32)
        d_r = (now_moved - was_moved) * scale
        allowed = (~cur) & ((loads + plane(w) <= cap)
                            | ((counts == 0) & oversized))
        if masked:
            allowed = allowed & plane(active_t[:, row] > 0)
        out_ref[r] = jnp.where(allowed, d_bins + d_r, MOVE_BLOCKED)


def move_delta_batch(loads, counts, assign, speeds, prev, lam, cap, *,
                     active=None, interpret: bool | None = None):
    """Fused move evaluation over a batch of chains in one kernel launch.

    loads: f32[K, M]; counts: i32[K, M]; assign: i32[K, N];
    speeds: f32[K, N]; prev: i32[K, N]; lam, cap: f32[K]; active:
    optional i32/bool[K, N] item mask (0 = item does not exist, all of
    its moves are blocked).
    Returns f32[K, N, M] move deltas (``MOVE_BLOCKED`` where masked).
    ``grid = (ceil(K / rows),)`` with ``rows = row_tile(K)``; each
    program instance owns ``rows`` chains' bin state and their (N, M)
    delta tiles.
    """
    if interpret is None:
        interpret = _default_interpret()
    masked = active is not None
    k, m = loads.shape
    n = assign.shape[1]
    rows = _row_tile(k)
    kernel = functools.partial(_move_eval_kernel, rows=rows, n=n, m=m,
                               masked=masked)
    m_spec = pl.BlockSpec((rows, m), lambda i: (i, 0))
    n_spec = pl.BlockSpec((rows, n), lambda i: (i, 0))
    s_spec = pl.BlockSpec((rows, 1), lambda i: (i, 0),
                          memory_space=pltpu.SMEM)
    in_specs = [m_spec, m_spec, n_spec, n_spec, n_spec, s_spec, s_spec]
    args = [loads.astype(jnp.float32), counts.astype(jnp.int32),
            assign.astype(jnp.int32), speeds.astype(jnp.float32),
            prev.astype(jnp.int32),
            (lam.astype(jnp.float32) / cap.astype(jnp.float32)).reshape(k, 1),
            cap.astype(jnp.float32).reshape(k, 1)]
    if masked:
        in_specs.append(n_spec)
        args.append(active.astype(jnp.int32))
    args = [_pad_rows(a, rows) for a in args]
    k_pad = args[0].shape[0]
    call = pl.pallas_call(
        kernel,
        grid=(k_pad // rows,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rows, n, m), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((k_pad, n, m), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )
    return call(*args)[:k]
