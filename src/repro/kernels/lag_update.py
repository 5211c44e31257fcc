"""Fused closed-loop lag update as a Pallas TPU kernel, batched over streams.

One simulated step of the lag digital twin (``repro.lagsim``) is:

  1. production:  avail_i = lag_i + produced_i
  2. segment-sum: L_c = sum of avail_i over *readable* partitions of bin c
  3. drain:       every readable partition of bin c sheds the fraction
                  min(1, cap_c / L_c) of its backlog, so each consumer
                  drains exactly min(L_c, cap_c) bytes in aggregate
                  (proportional water-filling of a shared budget)

Steps 2-3 are a one-hot segment reduction plus a gather -- the hot inner
loop when the twin sweeps hundreds of scenarios -- so the kernel fuses all
three into a single VMEM pass: each program instance owns an 8-stream
tile of ``(N,)`` states and reduces over each stream's ``(M, N)``
one-hot plane in registers.  Partitions that are unreadable (mid-migration
downtime, ``readable == 0``) or unassigned (``assign < 0``) keep their
backlog untouched.

Masking (variable-N fleets): pass ``active`` and partitions with
``active == 0`` -- topics that do not currently exist -- produce no
backlog, join no per-bin sum (they drain no budget), and end the step
with exactly zero lag ("unreadable and empty").  ``active=None`` keeps
the exact unmasked program, so all-active runs stay bit-identical.

Semantics are pinned to the pure-jnp oracle ``lag_update_reference`` below
(tests/test_lagsim.py); on hosts without a TPU the wrapper falls back to
Pallas interpreter mode automatically, like ``binpack_select``.
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

_TINY = 1e-30   # python literal so it is not captured as a traced const


def lag_update_reference(lag, produced, assign, readable, cap, *, m: int,
                         active=None):
    """Pure-jnp oracle over ``(..., N)`` state arrays.

    lag, produced: f32[..., N] backlog and this step's production (bytes);
    assign: i32[..., N] bin name per partition (< ``m``; -1 = unassigned);
    readable: bool/i32[..., N] -- 0 while a partition is in migration
    downtime; cap: per-consumer drain budget for the step, a scalar or any
    shape broadcastable to the per-bin sums f32[..., M]; active: optional
    bool/i32[..., N] -- 0 marks a partition that does not exist this step
    (no production, no drain, post-step lag exactly 0).  Returns the
    post-drain backlog f32[..., N].
    """
    if active is not None:
        act = active.astype(bool)
        produced = jnp.where(act, produced, 0.0)
        readable = readable.astype(bool) & act
    avail = lag + produced
    names = jnp.arange(m, dtype=jnp.int32)
    live = (readable.astype(bool)) & (assign >= 0)
    onehot = (assign[..., :, None] == names) & live[..., :, None]   # (..., N, M)
    per_bin = jnp.sum(jnp.where(onehot, avail[..., :, None], 0.0), axis=-2)
    ratio = jnp.minimum(1.0, cap / jnp.maximum(per_bin, _TINY))
    frac = jnp.sum(jnp.where(onehot, ratio[..., None, :], 0.0), axis=-1)
    out = jnp.maximum(avail * (1.0 - frac), 0.0)
    if active is not None:
        out = jnp.where(act, out, 0.0)
    return out


def _lag_update_kernel(lag_ref, prod_ref, assign_ref, readable_ref, cap_ref,
                       *rest, rows: int, n: int, m: int, masked: bool):
    """``rows`` streams: fused produce + one-hot segment drain.

    Each stream's one-hot plane is laid out ``(M, N)`` -- bins on
    sublanes, partitions on lanes -- so the ``(1, N)`` state rows
    broadcast into it directly; only the per-bin budget is turned into a
    ``(M, 1)`` column (one block transpose per grid step)."""
    out_ref = rest[-1]
    names = jax.lax.broadcasted_iota(jnp.int32, (m, n), 0)
    cap_t = cap_ref[...].T                                  # (M, rows)
    for r in range(rows):
        row = slice(r, r + 1)
        live = readable_ref[row, :] > 0                     # (1, N)
        produced = prod_ref[row, :]
        if masked:
            act = rest[0][row, :] > 0
            produced = jnp.where(act, produced, 0.0)
            live = live & act
        avail = lag_ref[row, :] + produced
        assign = assign_ref[row, :]
        onehot = (assign == names) & live                   # (M, N)
        per_bin = jnp.sum(jnp.where(onehot, avail, 0.0), axis=1,
                          keepdims=True)                    # (M, 1)
        ratio = jnp.minimum(1.0, cap_t[:, row] / jnp.maximum(per_bin, _TINY))
        frac = jnp.sum(jnp.where(onehot, ratio, 0.0), axis=0,
                       keepdims=True)                       # (1, N)
        out = jnp.maximum(avail * (1.0 - frac), 0.0)
        if masked:
            out = jnp.where(act, out, 0.0)
        out_ref[row, :] = out


def lag_update_batch(lag, produced, assign, readable, cap, *, active=None,
                     interpret: bool | None = None):
    """Fused lag update over a batch of streams in one kernel launch.

    lag, produced: f32[B, N]; assign: i32[B, N] (-1 = unassigned);
    readable: i32[B, N] (0 = migration downtime); cap: f32[B, M] per-bin
    drain budget for the step; active: optional i32/bool[B, N] partition
    mask (0 = the partition does not exist: no production, no drain, lag
    forced to 0).  Returns f32[B, N] post-drain backlog.
    ``grid = (ceil(B / rows),)`` with ``rows = row_tile(B)``: each
    instance holds ``rows`` streams' state and builds one stream's
    ``(M, N)`` one-hot plane at a time in VMEM.
    """
    if interpret is None:
        interpret = _default_interpret()
    masked = active is not None
    b, n = lag.shape
    m = cap.shape[1]
    rows = _row_tile(b)
    kernel = functools.partial(_lag_update_kernel, rows=rows, n=n, m=m,
                               masked=masked)
    n_spec = pl.BlockSpec((rows, n), lambda i: (i, 0))
    in_specs = [n_spec, n_spec, n_spec, n_spec,
                pl.BlockSpec((rows, m), lambda i: (i, 0))]
    args = [lag.astype(jnp.float32), produced.astype(jnp.float32),
            assign.astype(jnp.int32), readable.astype(jnp.int32),
            cap.astype(jnp.float32)]
    if masked:
        in_specs.append(n_spec)
        args.append(active.astype(jnp.int32))
    args = [_pad_rows(a, rows) for a in args]
    b_pad = args[0].shape[0]
    call = pl.pallas_call(
        kernel,
        grid=(b_pad // rows,),
        in_specs=in_specs,
        out_specs=n_spec,
        out_shape=jax.ShapeDtypeStruct((b_pad, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )
    return call(*args)[:b]


def lag_update_single(lag, produced, assign, readable, cap, *, active=None,
                      interpret: bool | None = None):
    """Rank-1 fused lag update: one stream, no batch axis.

    lag, produced: f32[N]; assign: i32[N]; readable: i32[N]; cap: f32[M];
    active: optional i32/bool[N].  Returns f32[N]: row 0 of a one-stream
    ``lag_update_batch`` (the lag engine's per-step ``drain`` inside its
    vmapped ``lax.scan``, where the batching rule adds the stream axis).
    """
    return lag_update_batch(
        lag[None], produced[None], assign[None], readable[None], cap[None],
        active=None if active is None else active[None],
        interpret=interpret)[0]
