"""Multi-step closed-loop lag megakernel: K simulated steps per launch.

``kernels/lag_update.py`` fuses ONE step's produce + drain; the scan
around it still pays a dispatch per simulated step.  This kernel hoists
the whole loop: ``grid = (ceil(B / 8), ceil(T / K))`` with the time
dimension marked ``"arbitrary"`` (sequential), and each program instance
advances ``K = fused_steps`` steps of an 8-stream tile -- streams on
sublanes, partitions on lanes, every value a 2-D plane -- while the
entire carry (the per-partition backlog, the previous assignment, and
the migration downtime counters) stays resident in VMEM scratch across
grid steps.  The time-major ``[K, 8, N]`` rate (and active-mask) slabs
are streamed per grid step through Pallas' pipelined block fetches, so
the next block's DMA overlaps the current block's compute (double
buffering); K tunes slab size against pipeline depth.

Each in-kernel step replays the heuristic policy families exactly:

  1. traversal order: identity, or ``pack_jax``'s stable non-increasing
     sort for Decreasing variants (pairwise rank, no sort primitive);
  2. slot selection per item with the same select logic as
     ``binpack_select`` (next/first/best/worst as a masked double-min);
  3. the Sec. IV-C sticky renaming of creation slots to bin names,
     with the name universe packed into int32 bitmasks;
  4. migration-downtime masking (a moved partition is unreadable for
     ``migration_steps`` steps);
  5. the produce + proportional-drain update of ``lag_update``.

Its oracle is the per-step scan of ``repro.lagsim.engine``: decisions
exact, lag to rounding (``repro.lagsim.metrics.agrees``; the kernel sums
in its own order), asserted in tests/test_fused_loop.py and the CI fused
smoke.  Like the other three kernels, hosts without a TPU run Pallas
interpreter mode automatically.

The int32 name bitmask bounds the kernel to ``n <= 14`` partitions
(``2n + 1 < 31`` bits) -- the engine falls back to the unfused scan
above that (``repro.lagsim.fused.FUSED_MAX_PARTITIONS``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._compat import default_interpret as _default_interpret
from ._compat import pad_rows as _pad_rows
from ._compat import row_tile as _row_tile

NEG = -1
_TINY = 1e-30   # python literal so it is not captured as a traced const
_STRATEGIES = ("next", "first", "best", "worst")


def _one_step(speeds, act, lag, prev, down, *, strategy: str,
              decreasing: bool, capacity: float, dt: float, mig: int,
              n: int):
    """One simulated step of ``rows`` streams at once: every value is a
    2-D ``(rows, N)`` / ``(rows, M)`` plane or a ``(rows, 1)`` column
    (streams on sublanes), and the per-item loops below read columns by
    static lane slices -- see the module docstring for the phases.
    ``act`` is the i32 active mask (``None`` when unmasked)."""
    rows = speeds.shape[0]
    m = n + 1
    inf = jnp.float32(jnp.inf)
    one = jnp.int32(1)
    iota_n = lax.broadcasted_iota(jnp.int32, (rows, n), 1)
    iota_m = lax.broadcasted_iota(jnp.int32, (rows, m), 1)
    col = lambda x, i: x[:, i:i + 1]                      # (rows, 1)
    cap = jnp.float32(capacity)
    cap_step = jnp.float32(capacity * dt)

    produced = speeds * jnp.float32(dt)
    if act is not None:
        produced = jnp.where(act > 0, produced, 0.0)

    # phase 1: traversal order (stable non-increasing sort as a pairwise
    # rank: strictly-greater plus equal-with-lower-index counts)
    if decreasing:
        rank = jnp.zeros((rows, n), jnp.int32)
        for j in range(n):
            sj = col(speeds, j)
            rank = rank + ((sj > speeds)
                           | ((sj == speeds) & (iota_n > j))).astype(jnp.int32)
        order = jnp.zeros((rows, n), jnp.int32)
        sp_ord = jnp.zeros((rows, n), jnp.float32)
        act_ord = None if act is None else jnp.zeros((rows, n), jnp.int32)
        for i in range(n):
            at = col(rank, i) == iota_n             # item i's sorted position
            order = jnp.where(at, jnp.int32(i), order)
            sp_ord = jnp.where(at, col(speeds, i), sp_ord)
            if act is not None:
                act_ord = jnp.where(at, col(act, i), act_ord)
    else:
        order = iota_n
        sp_ord = speeds
        act_ord = act

    # phase 2: slot selection (binpack_select logic, double-min tie-break)
    loads = jnp.full((rows, m), inf, jnp.float32)
    creator = jnp.full((rows, m), NEG, jnp.int32)
    slot_of = jnp.full((rows, n), NEG, jnp.int32)
    k = jnp.zeros((rows, 1), jnp.int32)
    lastload = jnp.zeros((rows, 1), jnp.float32)
    for i in range(n):
        w = col(sp_ord, i)
        j = col(order, i)
        d = loads + w
        fits = d <= cap
        if strategy == "next":
            found = (k > 0) & (lastload + w <= cap)
            slot = jnp.where(found, k - 1, k)
        else:
            if strategy == "first":
                score = jnp.where(fits, iota_m.astype(jnp.float32), inf)
            elif strategy == "best":
                score = jnp.where(fits, -loads, inf)
            else:
                score = jnp.where(fits, loads, inf)
            mn = jnp.min(score, axis=1, keepdims=True)
            s_sel = jnp.min(jnp.where(score == mn, iota_m, jnp.int32(127)),
                            axis=1, keepdims=True)
            found = mn < inf
            slot = jnp.where(found, s_sel, k)
        upd = iota_m == slot
        if act_ord is not None:
            a = col(act_ord, i) > 0
            upd = upd & a
        loads = jnp.where(upd, jnp.where(found, d, w), loads)
        creator = jnp.where(upd & ~found, j, creator)
        new_last = jnp.where(found & (slot == k - 1), lastload + w,
                             jnp.where(~found, w, lastload))
        if act_ord is None:
            lastload = new_last
            k = k + (~found).astype(jnp.int32)
            slot_of = jnp.where(iota_n == j, slot, slot_of)
        else:
            lastload = jnp.where(a, new_last, lastload)
            k = k + (a & ~found).astype(jnp.int32)
            slot_of = jnp.where((iota_n == j) & a, slot, slot_of)

    # phase 3: sticky naming over creation slots (int32 name bitmasks)
    claimed = jnp.zeros((rows, 1), jnp.int32)
    seen = jnp.zeros((rows, 1), jnp.int32)
    q = jnp.zeros((rows, 1), jnp.int32)
    new_assign = jnp.full((rows, n), NEG, jnp.int32)
    for s in range(n):
        c = col(creator, s)                 # item that created slot s
        pv = jnp.sum(jnp.where(c == iota_n, prev, 0), axis=1, keepdims=True)
        v = jnp.where(c >= 0, pv, NEG)      # its previous bin name
        vbit = one << jnp.maximum(v, 0)
        live = s < k
        cand = (v >= 0) & ((seen & vbit) == 0)
        seen = jnp.where(v >= 0, seen | vbit, seen)
        win = cand & (v >= q) & live
        fall = live & ~win
        nm = jnp.where(win, v, q)
        new_assign = jnp.where((slot_of == s) & live, nm, new_assign)
        claimed = jnp.where(win, claimed | vbit, claimed)
        adv = fall | (win & (v == q))
        mask = claimed | ((one << (q + 1)) - 1)
        low = (~mask) & (mask + 1)
        q = jnp.where(adv, lax.population_count(low - 1), q)

    # phases 4-5: downtime masking + produce/drain (lag_update, in slot
    # space: slot <-> name is a bijection per step so per-bin sums match)
    moved = (prev >= 0) & (new_assign >= 0) & (new_assign != prev)
    new_down = jnp.where(moved, jnp.int32(mig), jnp.maximum(down - 1, 0))
    readable = (new_down == 0) & (new_assign >= 0)
    avail = lag + produced
    slot_live = jnp.where(readable & (slot_of >= 0), slot_of, NEG)
    per_bin = jnp.zeros((rows, m), jnp.float32)
    for i in range(n):
        per_bin = per_bin + jnp.where(col(slot_live, i) == iota_m,
                                      col(avail, i), 0.0)
    ratio = jnp.minimum(1.0, cap_step / jnp.maximum(per_bin, _TINY))
    frac = jnp.zeros((rows, n), jnp.float32)
    for i in range(n):
        f_i = jnp.sum(jnp.where(col(slot_live, i) == iota_m, ratio, 0.0),
                      axis=1, keepdims=True)
        frac = jnp.where(iota_n == i, f_i, frac)
    new_lag = jnp.maximum(avail * (1.0 - frac), 0.0)
    unread = new_down > 0
    if act is not None:
        new_lag = jnp.where(act > 0, new_lag, 0.0)
        unread = unread & (act > 0)
    return new_lag, new_assign, new_down, k, moved, unread


def _loop_fused_kernel(*refs, k_blk: int, n: int, masked: bool,
                       strategy: str, decreasing: bool, capacity: float,
                       dt: float, mig: int):
    """Advance ``k_blk`` steps of a tile of streams; the carry lives in
    VMEM scratch across the sequential (``"arbitrary"``) time-block grid
    dimension.  Time-major refs: ``rates_ref[kk]`` is step ``kk``'s
    ``(rows, N)`` slab, ``tot_ref[kk]`` its ``(rows, 1)`` column."""
    if masked:
        (rates_ref, active_ref, lag0_ref, tot_ref, mx_ref, cons_ref,
         migs_ref, unread_ref, asg_ref, lag_s, prev_s, down_s) = refs
    else:
        (rates_ref, lag0_ref, tot_ref, mx_ref, cons_ref, migs_ref,
         unread_ref, asg_ref, lag_s, prev_s, down_s) = refs
        active_ref = None

    @pl.when(pl.program_id(1) == 0)
    def _init():
        lag_s[...] = lag0_ref[...]
        prev_s[...] = jnp.full(prev_s.shape, NEG, jnp.int32)
        down_s[...] = jnp.zeros(down_s.shape, jnp.int32)

    lag = lag_s[...]
    prev = prev_s[...]
    down = down_s[...]
    for kk in range(k_blk):
        act = None if active_ref is None else active_ref[kk]
        lag, prev, down, k, moved, unread = _one_step(
            rates_ref[kk], act, lag, prev, down, strategy=strategy,
            decreasing=decreasing, capacity=capacity, dt=dt, mig=mig, n=n)
        tot_ref[kk] = jnp.sum(lag, axis=1, keepdims=True)
        mx_ref[kk] = jnp.max(lag, axis=1, keepdims=True)
        cons_ref[kk] = k
        migs_ref[kk] = jnp.sum(moved.astype(jnp.int32), axis=1, keepdims=True)
        unread_ref[kk] = jnp.sum(unread.astype(jnp.int32), axis=1,
                                 keepdims=True)
        asg_ref[kk] = prev
    lag_s[...] = lag
    prev_s[...] = prev
    down_s[...] = down


def loop_fused_batch(rates, *, strategy: str, decreasing: bool,
                     capacity: float = 1.0, dt: float = 1.0,
                     migration_steps: int = 2, fused_steps: int = 8,
                     active=None, initial_lag=None,
                     interpret: bool | None = None):
    """Run a heuristic policy's whole closed loop in one kernel launch.

    rates: f32[B, T, N] per-partition production rates; active: optional
    bool/i32[B, T, N] partition-existence mask; initial_lag: optional
    f32[B, N] backlog seed (zeros by default).  ``strategy`` in
    ``("next", "first", "best", "worst")`` with ``decreasing`` selects
    the heuristic family member (NF..WFD).  Returns
    ``(lag_total f32[B, T], lag_max f32[B, T], consumers i32[B, T],
    migrations i32[B, T], unreadable i32[B, T], assigns i32[B, T, N])``.

    ``fused_steps`` (K) is the block size: steps advanced per grid step
    while the carry stays in VMEM.  T is padded up to a multiple of K
    internally (padded steps never feed back into real ones: time is
    causal) and outputs are sliced back to T.
    """
    if strategy not in _STRATEGIES:
        raise ValueError(
            f"strategy must be one of {_STRATEGIES}, got {strategy!r}")
    b, t, n = rates.shape
    if n > 14:
        raise ValueError(
            f"loop_fused_batch packs bin names into int32 bitmasks and "
            f"supports n <= 14 partitions; got n = {n} (the lag engine "
            f"falls back to the unfused scan above the limit)")
    k_blk = int(fused_steps)
    if k_blk <= 0:
        raise ValueError(f"fused_steps must be >= 1, got {fused_steps}")
    if interpret is None:
        interpret = _default_interpret()
    masked = active is not None
    t_blocks = -(-t // k_blk)
    t_pad = t_blocks * k_blk
    rows = _row_tile(b)

    def time_major(x):
        """[B, T, ...] -> [T_pad, B_pad, ...], zero-padded: padded steps
        come after every real one (time is causal) and padded streams
        are independent rows; both are sliced off the outputs."""
        x = _pad_rows(x, rows)
        x = jnp.pad(x, [(0, 0), (0, t_pad - t)] + [(0, 0)] * (x.ndim - 2))
        return jnp.swapaxes(x, 0, 1)

    args = [time_major(jnp.asarray(rates, jnp.float32))]
    if masked:
        args.append(time_major(jnp.asarray(active).astype(jnp.int32)))
    if initial_lag is None:
        initial_lag = jnp.zeros((b, n), jnp.float32)
    args.append(_pad_rows(jnp.asarray(initial_lag, jnp.float32), rows))
    b_pad = args[-1].shape[0]

    kernel = functools.partial(
        _loop_fused_kernel, k_blk=k_blk, n=n, masked=masked,
        strategy=strategy, decreasing=bool(decreasing),
        capacity=float(capacity), dt=float(dt), mig=int(migration_steps))
    slab = pl.BlockSpec((k_blk, rows, n), lambda i, j: (j, i, 0))
    step_spec = pl.BlockSpec((k_blk, rows, 1), lambda i, j: (j, i, 0))
    step_shape = lambda dtype: jax.ShapeDtypeStruct((t_pad, b_pad, 1), dtype)
    call = pl.pallas_call(
        kernel,
        grid=(b_pad // rows, t_blocks),
        in_specs=[slab] * (len(args) - 1)
        + [pl.BlockSpec((rows, n), lambda i, j: (i, 0))],
        out_specs=[step_spec] * 5 + [slab],
        out_shape=[step_shape(jnp.float32), step_shape(jnp.float32),
                   step_shape(jnp.int32), step_shape(jnp.int32),
                   step_shape(jnp.int32),
                   jax.ShapeDtypeStruct((t_pad, b_pad, n), jnp.int32)],
        scratch_shapes=[
            pltpu.VMEM((rows, n), jnp.float32),   # lag carry
            pltpu.VMEM((rows, n), jnp.int32),     # previous assignment
            pltpu.VMEM((rows, n), jnp.int32),     # migration downtime
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )

    *steps, asg = call(*args)
    steps = [o[:t, :b, 0].T for o in steps]
    return tuple(steps) + (jnp.swapaxes(asg[:t, :b], 0, 1),)
