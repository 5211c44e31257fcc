"""Fused multi-step path of the lag twin for the heuristic packer family.

The unfused engine pays per-step dispatch inside ``lax.scan``: pack ->
migrate -> drain as separate XLA ops, ~a hundred microseconds per step
at paper shapes (N~10, B~2) where the math itself is nanoseconds
(``packer_latency``'s dispatch-only column).  This module removes the
sequential bottleneck by splitting one simulated step into what is truly
carry-dependent and what is not:

* the heuristic bin STRUCTURE of a step -- which creation slot each item
  lands in (``slot_of``), which item created each slot (``creator``) and
  the bin count ``k`` -- depends only on that step's speeds, never on
  the previous assignment, so it is precomputed WIDE over all ``T``
  steps and all ``R = policies x streams`` rows in a handful of fused
  tensor ops (the same select logic as ``kernels/binpack_select``,
  vectorized with a masked double-min instead of argmin);
* only the Sec. IV-C sticky NAMING and the lag/downtime carry are
  sequential.  They run in one lean ``lax.scan`` whose body is a few
  dozen elementwise ops on ``[R, N]`` rows, with the bin-name universe
  (``2n+2`` names) packed into int32 bitmasks -- hence the
  ``FUSED_MAX_PARTITIONS`` gate (``2n+1 <= 30`` bits).

The decomposition changes no decision: ``fused`` agrees with
``unfused`` (``repro.lagsim.metrics.agrees``: integer fields exact, lag
to rounding) for every trajectory field, every scenario family
(``topic_lifecycle`` masking included), direct and fleet-padded
(tests/test_fused_loop.py; the ``python -m repro.lagsim.fused`` smoke
asserts it in CI).

Routing (``LagSimConfig.fused_steps > 0``):

=====================  ==========================================
policy / config        fused path behavior
=====================  ==========================================
heuristic family       fused (this module; ``fused_kernel=True``
                       launches ``kernels/loop_fused`` instead)
sticky family          falls back to the unfused scan (the Modified
                       Any Fit schedule is carry-dependent)
reactive (idealized)   falls back to the unfused scan
reactive (REAL)        raises :class:`FusedPathError` (control-plane
                       wrapped: host-visible scaler state)
optimizer (ANNEAL*)    raises :class:`FusedPathError` (PRNG carry)
control_plane set      raises :class:`FusedPathError`
telemetry frames/ring  falls back (O(T) frame recording is
                       unfused-only; sketch/alert aggregates are
                       emitted by the fused path, bit-equal)
crash schedule         falls back (expulsion and orphan windows
                       are per-step carry)
n > 14 partitions      falls back (int32 name-bitmask limit)
use_kernel=True        falls back (per-step drain-kernel bits
                       differ from the reference drain's fusion)
=====================  ==========================================

``fused_steps``/K is the megakernel's steps-per-launch block size
(``kernels/loop_fused``); the XLA fused engine below computes the whole
trace in one program, so its results are K-invariant by construction
(T not divisible by K included).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.registry import get_spec

NEG = -1
_TINY = 1e-30          # python literal: never a traced const (matches lag_update)
_BIG_SLOT = 127        # > any slot index; the tie-break filler of the min-select

#: the sticky-naming bitmask packs the ``2n+2`` bin-name universe into an
#: int32 (bit ``2n+1`` must stay below the sign bit), so the fused path
#: covers ``n <= 14`` partitions and falls back above
FUSED_MAX_PARTITIONS = 14

_STRAT_CODE = {"next": 0, "first": 1, "best": 2, "worst": 3}


class FusedPathError(ValueError):
    """``fused_steps`` was combined with a policy or config whose state
    cannot live inside the fused loop (ANNEAL* PRNG carry, control-plane
    wrapped scalers).  Drop ``fused_steps`` or the offending piece."""


def _controlplane_wrapped(spec) -> bool:
    """True for self-wrapped REAL scaler families: their hyperparams carry
    the control-plane knob set (``ControlPlaneConfig.knobs()``)."""
    from repro.lagsim.controlplane import ControlPlaneConfig

    return bool(set(ControlPlaneConfig().knobs()) & set(spec.hyperparams))


def fused_mode(policy: str, cfg, n: int, crash: bool = False) -> str:
    """Route one policy under ``cfg.fused_steps > 0``: ``"fused"`` or
    ``"unfused"`` (documented fallback).  Raises :class:`FusedPathError`
    for the combinations the fused path refuses (see the module table).
    ``crash``: the run carries a crash schedule.
    """
    spec = get_spec(policy, backend="jax")
    if spec.family == "optimizer":
        raise FusedPathError(
            f"fused_steps is incompatible with optimizer policy "
            f"{spec.name!r}: its PRNG-carrying anneal state cannot run "
            f"inside the fused loop; drop fused_steps or the policy")
    if cfg.control_plane is not None:
        raise FusedPathError(
            "fused_steps is incompatible with control_plane: scaler "
            "friction (polling/delay/cooldown/rebalance storm) wraps every "
            "policy in state the fused loop does not model; drop "
            "fused_steps or control_plane")
    if spec.family == "reactive" and _controlplane_wrapped(spec):
        raise FusedPathError(
            f"fused_steps is incompatible with control-plane-wrapped "
            f"policy {spec.name!r}; drop fused_steps or use the idealized "
            f"variant of the scaler")
    if spec.family != "heuristic":
        return "unfused"
    if crash:
        # expulsion and orphan windows live in the per-step scan's carry
        return "unfused"
    if n > FUSED_MAX_PARTITIONS:
        return "unfused"
    tele = cfg.telemetry
    if tele is not None and tele.enabled and tele.record_frames:
        # O(T) frame recording (ring mode included) is unfused-only
        return "unfused"
    if getattr(cfg, "use_kernel", False):
        # the per-step drain kernel and the inlined reference drain agree
        # in value but not always in bits (XLA fuses the reference path
        # with its surroundings); the fused path computes reference-drain
        # bits, so use_kernel runs stay on the per-step scan
        return "unfused"
    return "fused"


def _heuristic_consts(policies: Sequence[str], b: int):
    """Per-row select constants for a family-batched run of ``P`` heuristic
    policies over ``b`` streams (row ``r = p * b + stream``)."""
    strategies, decreasing = [], []
    for name in policies:
        hyper = get_spec(name, backend="jax").hyperparams
        strategies.append(hyper["strategy"])
        decreasing.append(bool(hyper["decreasing"]))
    strat = jnp.asarray([_STRAT_CODE[s] for s in strategies],
                        jnp.int32).repeat(b)
    is_next = (strat == 0)[None, :]                       # [1, R]
    # one score per strategy, minimized with lowest-slot tie-break:
    #   first: slot index            best: -load (tightest fit wins)
    #   worst: +load (most slack)    next: handled by the is_next branch
    a_sgn = jnp.where(strat == 2, -1.0,
                      jnp.where(strat == 3, 1.0, 0.0))[None, :, None]
    b_is_first = jnp.where(strat == 1, 1.0, 0.0)[None, :, None]
    return decreasing, is_next, a_sgn, b_is_first


def _prep(traces: jax.Array, dec_flags: Sequence[bool],
          active: Optional[jax.Array]):
    """Sorted per-step views for every (policy, stream) row.

    For a Decreasing policy the item order is ``pack_jax``'s stable
    non-increasing sort ``lexsort((arange(n), -speeds))``, computed here
    as a pairwise rank (strictly-greater plus equal-with-lower-index)
    scattered through a one-hot -- no sort primitive, fully batched.
    Returns ``(sp_ord, order, pos, act_ord)`` each ``[R, T, N]``:
    speeds/item-index/rank in traversal order, plus the active mask in
    traversal order (``None`` when unmasked).
    """
    b, t, n = traces.shape
    p = len(dec_flags)
    iota_n = jnp.arange(n, dtype=jnp.int32)
    s = traces
    gt = s[..., :, None] < s[..., None, :]
    eq_lo = ((s[..., :, None] == s[..., None, :])
             & (iota_n[None, :] > iota_n[:, None]).T)
    rank_d = jnp.sum(gt | eq_lo, axis=-1).astype(jnp.int32)      # [B, T, N]
    oh = rank_d[..., :, None] == iota_n[None, None, None, :]
    order_d = jnp.sum(jnp.where(oh, iota_n[:, None], 0), -2).astype(jnp.int32)
    sp_d = jnp.sum(jnp.where(oh, s[..., None], 0.0), axis=-2)
    idn = jnp.broadcast_to(iota_n, (b, t, n))
    dec = jnp.asarray(dec_flags, bool)[:, None, None, None]
    ex = lambda a: jnp.broadcast_to(a, (p,) + a.shape)
    order = jnp.where(dec, ex(order_d), ex(idn)).reshape(p * b, t, n)
    pos = jnp.where(dec, ex(rank_d), ex(idn)).reshape(p * b, t, n)
    sp_ord = jnp.where(dec, ex(sp_d), ex(traces)).reshape(p * b, t, n)
    act_ord = None
    if active is not None:
        act_r = ex(active).reshape(p * b, t, n)
        act_ord = jnp.take_along_axis(act_r, order, axis=-1)
    return sp_ord, order, pos, act_ord


def _struct(sp_ord, ord_idx, act_ord, capacity, is_next, a_sgn, b_is_first):
    """Carry-free pack structure, wide over the leading ``(T, R)`` axes.

    Mirrors ``pack_jax``'s item scan minus the naming: ``slot_ord[i]`` is
    the creation slot of the i-th item in traversal order (``NEG`` for an
    inactive item, which leaves every piece of state untouched --
    ``pack_jax``'s mask contract), ``creator[s]`` the item that created
    slot ``s`` and ``k`` the bin count.
    """
    n = sp_ord.shape[-1]
    m = n + 1
    lead = sp_ord.shape[:-1]
    iota_m = jnp.arange(m, dtype=jnp.int32)
    iota_mf = iota_m.astype(jnp.float32)
    inf = jnp.float32(jnp.inf)
    big = jnp.int32(_BIG_SLOT)
    b_off = b_is_first * iota_mf
    loads = jnp.full(lead + (m,), inf, jnp.float32)
    creator = jnp.full(lead + (m,), NEG, jnp.int32)
    k = jnp.zeros(lead, jnp.int32)
    lastload = jnp.zeros(lead, jnp.float32)
    slot_ord = []
    for i in range(n):
        w = sp_ord[..., i]
        j = ord_idx[..., i]
        d = loads + w[..., None]
        fits = d <= capacity
        score = jnp.where(fits, a_sgn * loads + b_off, inf)
        mn = jnp.min(score, axis=-1)
        s_sel = jnp.min(jnp.where(score == mn[..., None], iota_m, big), -1)
        found_sel = mn < inf
        ok_next = (k > 0) & (lastload + w <= capacity)
        slot = jnp.where(is_next, k - 1, s_sel)
        found = jnp.where(is_next, ok_next, found_sel)
        slot = jnp.where(found, slot, k)
        coh = iota_m == slot[..., None]
        if act_ord is None:
            upd = coh
            act_i = None
        else:
            act_i = act_ord[..., i]
            upd = coh & act_i[..., None]
        loads = jnp.where(
            upd, jnp.where(found[..., None], d, w[..., None]), loads)
        creator = jnp.where(upd & ~found[..., None], j[..., None], creator)
        new_lastload = jnp.where(found & (slot == k - 1), lastload + w,
                                 jnp.where(~found, w, lastload))
        if act_i is None:
            lastload = new_lastload
            k = k + (~found).astype(jnp.int32)
            slot_ord.append(slot)
        else:
            lastload = jnp.where(act_i, new_lastload, lastload)
            k = k + (act_i & ~found).astype(jnp.int32)
            slot_ord.append(jnp.where(act_i, slot, jnp.int32(NEG)))
    return jnp.stack(slot_ord, -1), creator, k


def _fused_wide(policies: Tuple[str, ...], traces: jax.Array, cfg,
                active: Optional[jax.Array],
                initial_lag: Optional[jax.Array]):
    """The fused run itself: structure precompute + one lean scan.

    Returns wide per-step arrays ``(lag_t, asg_t, down_t)`` each
    ``[T, R, N]`` plus the bin counts ``kk [T, R]`` (R-rows ordered
    ``policy-major``: row ``p * B + stream``).
    """
    b, t, n = traces.shape
    m = n + 1
    p = len(policies)
    r = p * b
    dec_flags, is_next, a_sgn, b_is_first = _heuristic_consts(policies, b)
    capacity = jnp.float32(cfg.capacity)
    cap_step = jnp.float32(cfg.capacity * cfg.dt)
    dt = jnp.float32(cfg.dt)
    mig = jnp.int32(cfg.migration_steps)
    iota_n = jnp.arange(n, dtype=jnp.int32)
    one = jnp.int32(1)

    sp_ord, order, pos, act_ord = _prep(traces, dec_flags, active)
    tw = lambda a: jnp.moveaxis(a, 0, 1)        # [R, T, ...] -> [T, R, ...]
    slot_ord, creator, kk = _struct(
        tw(sp_ord), tw(order), None if act_ord is None else tw(act_ord),
        capacity, is_next, a_sgn, b_is_first)
    slot_of = jnp.take_along_axis(slot_ord, tw(pos), axis=-1)   # [T, R, N]

    rates_tn = jnp.moveaxis(traces, 0, 1)                       # [T, B, N]
    act_tn = None if active is None else jnp.moveaxis(active, 0, 1)

    def one_step(carry, xs):
        lag, prev, down = carry
        if act_tn is None:
            rate_b, slot_t, creator_t, k_t = xs
            act_r = None
        else:
            rate_b, slot_t, creator_t, k_t, act_b = xs
            act_r = jnp.broadcast_to(act_b[None], (p, b, n)).reshape(r, n)
        rate_r = jnp.broadcast_to(rate_b[None], (p, b, n)).reshape(r, n)
        produced = (rate_r * dt if act_r is None
                    else jnp.where(act_r, rate_r * dt, 0.0))
        # sticky naming (Sec. IV-C): slots in creation order; a slot keeps
        # its creator's previous bin name when still unclaimed, else takes
        # the lowest unused name.  ``claimed``/``seen`` track name bits,
        # ``q`` the lowest-unused pointer, advanced by bit tricks.
        p_all = jnp.sum(jnp.where(creator_t[:, :, None] == iota_n[None, None],
                                  prev[:, None, :], 0), axis=-1)
        p_all = jnp.where(creator_t >= 0, p_all, NEG)
        claimed = jnp.zeros((r,), jnp.int32)
        seen = jnp.zeros((r,), jnp.int32)
        q = jnp.zeros((r,), jnp.int32)
        new_assign = jnp.full((r, n), NEG, jnp.int32)
        for s in range(n):
            v = p_all[:, s]
            vbit = one << jnp.maximum(v, 0)
            live = s < k_t
            cand = (v >= 0) & ((seen & vbit) == 0)
            seen = jnp.where(v >= 0, seen | vbit, seen)
            win = cand & (v >= q) & live
            fall = live & ~win
            nm = jnp.where(win, v, q)
            new_assign = jnp.where((slot_t == s) & live[:, None],
                                   nm[:, None], new_assign)
            claimed = jnp.where(win, claimed | vbit, claimed)
            adv = fall | (win & (v == q))
            mask = claimed | ((one << (q + 1)) - 1)
            low = (~mask) & (mask + 1)
            q = jnp.where(adv, lax.population_count(low - 1), q)
        moved = (prev >= 0) & (new_assign >= 0) & (new_assign != prev)
        down = jnp.where(moved, mig, jnp.maximum(down - 1, 0))
        readable = (down == 0) & (new_assign >= 0)
        # drain in slot space (slot <-> name is a bijection per step, so
        # the per-bin sums match lag_update_reference's name-space sums)
        avail = lag + produced
        live_p = readable & (slot_t >= 0)
        iota_m = jnp.arange(m, dtype=jnp.int32)
        onehot = ((slot_t[:, None, :] == iota_m[None, :, None])
                  & live_p[:, None, :])
        per_bin = jnp.sum(jnp.where(onehot, avail[:, None, :], 0.0), axis=-1)
        ratio = jnp.minimum(1.0, cap_step / jnp.maximum(per_bin, _TINY))
        frac = jnp.where(
            live_p,
            jnp.take_along_axis(ratio, jnp.maximum(slot_t, 0), axis=-1), 0.0)
        new_lag = jnp.maximum(avail * (1.0 - frac), 0.0)
        if act_r is not None:
            new_lag = jnp.where(act_r, new_lag, 0.0)
        new_carry = (new_lag, new_assign, down)
        return new_carry, new_carry

    lag0 = (jnp.zeros((r, n), jnp.float32) if initial_lag is None
            else jnp.broadcast_to(
                initial_lag.astype(jnp.float32), (r, n)))
    carry0 = (lag0, jnp.full((r, n), NEG, jnp.int32),
              jnp.zeros((r, n), jnp.int32))
    xs = (rates_tn, slot_of, creator, kk)
    if act_tn is not None:
        xs = xs + (act_tn,)
    _, (lag_t, asg_t, down_t) = lax.scan(one_step, carry0, xs)
    return lag_t, asg_t, down_t, kk, carry0[1]


def _shape_pb(x, p, b):
    """[T, R] -> [P, B, T] (row r = p * B + stream)."""
    t = x.shape[0]
    return x.reshape(t, p, b).transpose(1, 2, 0)


def _obs_states(tele, cfg, names, vec_w, lag_tot, kk, unread_ct, valid_tr, r):
    """Post-hoc sketch/alert aggregation: replay the per-step channel
    vectors (already bit-equal to the unfused recorder's) through the
    same ``sketch_update``/``alert_step`` sequence, vmapped over rows.
    Same values, same order, same float ops => bit-identical states."""
    from repro.telemetry.alerts import alert_init, alert_step
    from repro.telemetry.sketch import sketch_init, sketch_update

    sketch_on = tele.sketch is not None
    alerts_on = tele.alerts is not None
    has_valid = valid_tr is not None
    rows = jnp.arange(r)
    sk0 = (jax.vmap(lambda _: sketch_init(tele.sketch, names))(rows)
           if sketch_on else None)
    al0 = (jax.vmap(lambda _: alert_init(tele.alerts))(rows)
           if alerts_on else None)

    def step(carry, xs_t):
        sk, al = carry
        if has_valid:
            vec_t, lt, co, un, va = xs_t
        else:
            vec_t, lt, co, un = xs_t
        if sketch_on:
            if has_valid:
                sk = jax.vmap(
                    lambda s, v, g: sketch_update(tele.sketch, s, v, valid=g)
                )(sk, vec_t, va)
            else:
                sk = jax.vmap(
                    lambda s, v: sketch_update(tele.sketch, s, v))(sk, vec_t)
        if alerts_on:
            def one(a, lt1, co1, un1, va1=None):
                return alert_step(
                    tele.alerts, a, lag_total=lt1, consumers=co1,
                    unreadable=un1, storm_parts=jnp.float32(0.0),
                    slo_lag=cfg.slo_lag, valid=va1)
            if has_valid:
                al = jax.vmap(one)(al, lt, co, un, va)
            else:
                al = jax.vmap(one)(al, lt, co, un)
        return (sk, al), None

    xs = (vec_w, lag_tot, kk, unread_ct)
    if has_valid:
        xs = xs + (valid_tr,)
    (sk, al), _ = lax.scan(step, (sk0, al0), xs)
    return sk, al


def sweep_fused(policies: Tuple[str, ...], traces: jax.Array, cfg,
                active: Optional[jax.Array] = None,
                valid: Optional[jax.Array] = None,
                initial_lag: Optional[jax.Array] = None,
                record_assign: bool = False) -> Dict[str, dict]:
    """Family-batched fused sweep of heuristic ``policies`` over
    ``traces f32[B, T, N]``.

    Returns ``{policy: field dict}`` with the exact ``LagTrace`` fields
    the unfused ``_simulate`` vmap would produce (``[B, T]`` arrays;
    sketch/alert states with leading ``[B]``), so the engine can splice
    fused rows into a mixed sweep transparently.  With
    ``record_assign=True`` each dict also carries ``assigns i32[B, T, N]``.
    """
    traces = traces.astype(jnp.float32)
    if active is not None:
        active = active.astype(bool)
    b, t, n = traces.shape
    p = len(policies)
    r = p * b
    cfg = cfg.resolve(n)

    tele_cfg = cfg.telemetry if cfg.telemetry_on else None
    obs_on = tele_cfg is not None and (tele_cfg.sketch is not None
                                       or tele_cfg.alerts is not None)
    if cfg.fused_kernel and not obs_on:
        # recorder-free run: the Pallas megakernel advances fused_steps
        # steps per launch with the carry resident in VMEM.  With sketch
        # or alerts on, the XLA fused path below emits the aggregates.
        return _sweep_kernel(policies, traces, cfg, active, initial_lag,
                             record_assign)

    lag_t, asg_t, down_t, kk, prev0 = _fused_wide(
        policies, traces, cfg, active, initial_lag)
    prev_t = jnp.concatenate([prev0[None], asg_t[:-1]], axis=0)
    moved_t = (prev_t >= 0) & (asg_t >= 0) & (asg_t != prev_t)
    blocked_t = down_t > 0
    if active is None:
        act_w = None
        unread_t = blocked_t
    else:
        act_w = jnp.broadcast_to(
            jnp.moveaxis(active, 0, 1)[:, None], (t, p, b, n)).reshape(
                t, r, n)
        unread_t = blocked_t & act_w

    lag_tot = jnp.sum(lag_t, axis=-1)                       # [T, R]
    lag_max = jnp.max(lag_t, axis=-1)
    migs = jnp.sum(moved_t.astype(jnp.int32), axis=-1)
    unread = jnp.sum(unread_t.astype(jnp.int32), axis=-1)

    tele = cfg.telemetry if cfg.telemetry_on else None
    sk = al = None
    if tele is not None and (tele.sketch is not None
                             or tele.alerts is not None):
        names_box = [None]
        if tele.sketch is not None:
            from repro.telemetry.record import record_step

            rate_w = jnp.broadcast_to(
                jnp.moveaxis(traces, 0, 1)[:, None], (t, p, b, n)).reshape(
                    t, r, n)

            def one_vec(rate, new_lag, moved, blocked, k_t, act):
                vec, names_box[0] = record_step(
                    tele, speeds=rate, new_lag=new_lag, moved=moved,
                    blocked=blocked, storm=None, n_consumers=k_t, act_t=act,
                    capacity=cfg.capacity, pstate=jnp.int32(0))
                return vec

            if act_w is None:
                vec_w = jax.vmap(jax.vmap(
                    lambda rt, nl, mv, bl, k_t: one_vec(rt, nl, mv, bl, k_t,
                                                        None)))(
                    rate_w, lag_t, moved_t, unread_t, kk)
            else:
                vec_w = jax.vmap(jax.vmap(one_vec))(
                    rate_w, lag_t, moved_t, unread_t, kk, act_w)
        else:
            vec_w = jnp.zeros((t, 1), jnp.float32)   # alerts-only: unused
        valid_tr = None
        if valid is not None:
            valid_tr = jnp.broadcast_to(
                valid.astype(bool).T[:, None], (t, p, b)).reshape(t, r)
        sk, al = _obs_states(tele, cfg, names_box[0], vec_w, lag_tot,
                             kk, unread, valid_tr, r)

    out: Dict[str, dict] = {}
    for pi, name in enumerate(policies):
        fields = dict(
            lag_total=_shape_pb(lag_tot, p, b)[pi],
            lag_max=_shape_pb(lag_max, p, b)[pi],
            consumers=_shape_pb(kk, p, b)[pi],
            migrations=_shape_pb(migs, p, b)[pi],
            unreadable=_shape_pb(unread, p, b)[pi],
            telemetry=None,
            sketch=None if sk is None else jax.tree_util.tree_map(
                lambda a: a.reshape((p, b) + a.shape[1:])[pi], sk),
            incidents=None if al is None else jax.tree_util.tree_map(
                lambda a: a.reshape((p, b) + a.shape[1:])[pi], al),
        )
        if record_assign:
            fields["assigns"] = asg_t.reshape(
                t, p, b, n)[:, pi].transpose(1, 0, 2)       # [B, T, N]
        out[name] = fields
    return out


def _sweep_kernel(policies, traces, cfg, active, initial_lag, record_assign):
    """Fused path via the Pallas megakernel (``cfg.fused_kernel``): one
    launch per policy advances ``fused_steps`` steps per grid block with
    the carry resident in VMEM (interpret mode on CPU).  Recorder-free:
    the engine routes telemetry-on runs through the XLA fused path."""
    from repro.kernels.loop_fused import loop_fused_batch

    out: Dict[str, dict] = {}
    for name in policies:
        hyper = get_spec(name, backend="jax").hyperparams
        tot, mx, cons, migs, unread, asg = loop_fused_batch(
            traces, strategy=hyper["strategy"],
            decreasing=bool(hyper["decreasing"]), capacity=cfg.capacity,
            dt=cfg.dt, migration_steps=cfg.migration_steps,
            fused_steps=cfg.fused_steps, active=active,
            initial_lag=initial_lag)
        fields = dict(lag_total=tot, lag_max=mx, consumers=cons,
                      migrations=migs, unreadable=unread,
                      telemetry=None, sketch=None, incidents=None)
        if record_assign:
            fields["assigns"] = asg
        out[name] = fields
    return out


def simulate_fused(trace: jax.Array, initial_lag: jax.Array, policy: str,
                   cfg, active: Optional[jax.Array] = None,
                   record_assign: bool = False,
                   valid: Optional[jax.Array] = None):
    """Single-stream fused run, mirroring ``engine._simulate``'s contract
    (returns a ``LagTrace`` of ``[T]`` arrays, or ``(trace, assigns)``).
    """
    from repro.lagsim.engine import LagTrace

    fields = sweep_fused(
        (policy,), trace[None], cfg,
        active=None if active is None else active[None],
        valid=None if valid is None else valid[None],
        initial_lag=initial_lag, record_assign=record_assign)[policy]
    assigns = fields.pop("assigns", None)
    out = LagTrace(**jax.tree_util.tree_map(lambda a: a[0], fields))
    return (out, assigns[0]) if record_assign else out


def _smoke() -> None:      # pragma: no cover - exercised by CI, not pytest
    """CI fused smoke: the jnp fused engine and the interpret-mode
    megakernel agree with the unfused scan on a masked lifecycle
    workload."""
    from repro.core.scenarios import generate_masked_scenario
    from repro.lagsim.engine import LagSimConfig, sweep_lag
    from repro.lagsim.metrics import agrees

    pols = ("NF", "FFD", "BFD", "WF")
    speeds, act = generate_masked_scenario(
        "topic_lifecycle", jax.random.key(0), 2, 33, 6)
    base = LagSimConfig(capacity=1.0, dt=1.0, migration_steps=2)
    ref = sweep_lag(pols, speeds, base, active=act)
    for cfg, label in (
            (LagSimConfig(capacity=1.0, dt=1.0, migration_steps=2,
                          fused_steps=8), "fused engine"),
            (LagSimConfig(capacity=1.0, dt=1.0, migration_steps=2,
                          fused_steps=8, fused_kernel=True),
             "fused megakernel")):
        got = sweep_lag(pols, speeds, cfg, active=act)
        for f in ("lag_total", "lag_max", "consumers", "migrations",
                  "unreadable"):
            assert agrees(getattr(got, f), getattr(ref, f)), (
                f"{label}: field {f} diverged from the unfused oracle")
        print(f"fused smoke OK: {label} agrees with unfused "
              f"({len(pols)} policies, masked lifecycle, T % K != 0)")


if __name__ == "__main__":      # pragma: no cover
    _smoke()
