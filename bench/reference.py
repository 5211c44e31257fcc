"""Plain reference of what the benchmark's cells compute.

Straightforward Python and NumPy, written from the published semantics
and sharing no code with the program under test:

* the two packers of the cells, best fit decreasing with the sticky
  naming rule of Sec. IV-C (``bfd``) and Modified Any Fit with best fit
  and consumers sorted by their largest partition, Algorithm 1
  (``mbfp``);
* the R-score of Eq. 10 (``rscore``);
* KEDA's lag trigger behind a ScaledObject control plane: polling,
  observation and actuation delay, cooldown, replica clamp and the
  rebalance storm (``KedaLag``);
* the closed-loop lag twin (``twin``): per step, production, the
  policy's decision, migration downtime for moved partitions, and a
  drain in which every consumer sheds up to ``C * dt`` of its readable
  partitions' backlog, each in proportion to its backlog.

Every float is computed in the precision given by ``Arith``: float64 for
the reference, bfloat16 for the control that a sound comparison must
reject.  Rates are in units of the consumer capacity C.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import ml_dtypes
import numpy as np

NEG = -1


@dataclass(frozen=True)
class Arith:
    """One precision: ``dtype`` for arrays, ``r`` rounds a Python float."""

    name: str
    dtype: type
    r: Callable[[float], float]


def _bf16(x: float) -> float:
    return float(ml_dtypes.bfloat16(x))


PRECISIONS = {
    "float64": Arith("float64", np.float64, float),
    "bfloat16": Arith("bfloat16", ml_dtypes.bfloat16, _bf16),
}


# ---------------------------------------------------------------------------
# packers: speeds and prev are per-partition lists; inactive partitions
# are absent (assigned NEG, no load, no name)
# ---------------------------------------------------------------------------

def _best_slot(loads: List[float], w: float, cap: float, r) -> int:
    """The fullest open bin that still holds ``w``; the first on a tie."""
    best = -1
    for s, load in enumerate(loads):
        if r(load + w) <= cap and (best < 0 or load > loads[best]):
            best = s
    return best


def _fresh_name(used: set, prev_name: int) -> int:
    """The partition's previous consumer if not yet taken, else the
    lowest free consumer id (Sec. IV-C)."""
    if prev_name >= 0 and prev_name not in used:
        return prev_name
    i = 0
    while i in used:
        i += 1
    return i


class _Bins:
    def __init__(self, cap: float, r):
        self.cap, self.r = cap, r
        self.loads: List[float] = []
        self.names: List[int] = []
        self.used: set = set()

    def open(self, name: int) -> int:
        self.loads.append(0.0)
        self.names.append(name)
        self.used.add(name)
        return len(self.loads) - 1

    def add(self, slot: int, w: float) -> int:
        self.loads[slot] = self.r(self.loads[slot] + w)
        return self.names[slot]

    def any_fit(self, w: float, prev_name: int) -> int:
        slot = _best_slot(self.loads, w, self.cap, self.r)
        if slot < 0:
            slot = self.open(_fresh_name(self.used, prev_name))
        return self.add(slot, w)


def bfd(speeds: Sequence[float], active: Sequence[bool],
        prev: Sequence[int], cap: float, r=float) -> Tuple[List[int], int]:
    """Best fit decreasing, sticky naming: ``(assignment, bins)``."""
    n = len(speeds)
    bins = _Bins(cap, r)
    assign = [NEG] * n
    for j in sorted((j for j in range(n) if active[j]),
                    key=lambda j: (-speeds[j], j)):
        assign[j] = bins.any_fit(speeds[j], prev[j])
    return assign, len(bins.loads)


def mbfp(speeds: Sequence[float], active: Sequence[bool],
         prev: Sequence[int], cap: float, r=float) -> Tuple[List[int], int]:
    """Modified Any Fit, best fit, consumers by largest partition
    (Algorithm 1): ``(assignment, bins)``."""
    n = len(speeds)
    live = [j for j in range(n) if active[j]]
    group: Dict[int, List[int]] = {}
    for j in live:
        if prev[j] >= 0:
            group.setdefault(prev[j], []).append(j)
    pending = [j for j in live if prev[j] < 0]
    bins = _Bins(cap, r)
    assign = [NEG] * n
    key = {c: max(speeds[p] for p in ps) for c, ps in group.items()}
    for c in sorted(group, key=lambda c: (-key[c], c)):
        pset = sorted(group[c], key=lambda p: (-speeds[p], p))
        # smallest first into bins already open; the first miss stops it
        while pset:
            slot = _best_slot(bins.loads, speeds[pset[-1]], cap, r)
            if slot < 0:
                break
            p = pset.pop()
            assign[p] = bins.add(slot, speeds[p])
        if not pset:
            continue
        # the consumer's own bin, biggest first; the first miss stops it
        own = bins.open(c)
        k = 0
        while k < len(pset):
            w = speeds[pset[k]]
            if not (r(bins.loads[own] + w) <= cap
                    or (bins.loads[own] == 0.0 and w > cap)):
                break
            assign[pset[k]] = bins.add(own, w)
            k += 1
        pending.extend(pset[k:])
    pending.sort(key=lambda p: -speeds[p])
    for p in pending:
        assign[p] = bins.any_fit(speeds[p], prev[p])
    return assign, len(bins.loads)


PACKERS = {"BFD": bfd, "MBFP": mbfp}


def rscore(prev: Sequence[int], new: Sequence[int], speeds: Sequence[float],
           cap: float, r=float) -> Optional[float]:
    """Eq. 10: the write speed of the partitions whose consumer changed,
    over C.  ``None`` when no partition had a consumer before."""
    if all(p < 0 for p in prev):
        return None
    total = 0.0
    for p, (a, b) in enumerate(zip(prev, new)):
        if a >= 0 and a != b:
            total = r(total + speeds[p])
    return r(total / cap)


# ---------------------------------------------------------------------------
# KEDA lag trigger behind a ScaledObject control plane, over a batch of
# groups (arrays [B, N])
# ---------------------------------------------------------------------------

@dataclass
class KedaLag:
    """KEDA's Kafka lag trigger: ``ceil(total lag / lagThreshold)``
    replicas within ``[1, n]``, up at once, down after ``patience``
    polls that want fewer; partitions dealt round robin over the live
    ones.  The control plane around it polls every ``poll`` steps, sees
    metrics ``obs_delay`` steps old, applies a decision ``act_delay``
    steps later, accepts none for ``cooldown`` steps after one applies,
    folds consumers beyond ``max_replicas`` onto the first ones, and
    makes every partition of a consumer that a decision touched
    unreadable for ``warmup`` steps."""

    n: int
    lag_threshold: float
    patience: int
    poll: int
    obs_delay: int
    act_delay: int
    cooldown: int
    min_replicas: int
    max_replicas: int
    warmup: int
    ar: Arith = field(default_factory=lambda: PRECISIONS["float64"])

    def init(self, b: int):
        d1, n = self.obs_delay + 1, self.n
        self.obs_lag = np.zeros((b, d1, n), self.ar.dtype)
        self.obs_active = np.ones((b, d1, n), bool)
        self.held_n = np.zeros(b, np.int64)
        self.pend_assign = np.full((b, n), NEG, np.int64)
        self.pend_n = np.zeros(b, np.int64)
        self.pend_at = np.zeros(b, np.int64)
        self.pend_valid = np.zeros(b, bool)
        self.cool_until = np.zeros(b, np.int64)
        self.warming = np.zeros((b, n), np.int64)
        self.n_cur = np.ones(b, np.int64)
        self.under = np.zeros(b, np.int64)

    def _trigger(self, lag, act):
        """The bare lag rule on one observation: ``(assign, replicas)``."""
        total = rowsum(np.where(act, lag, np.asarray(0.0, self.ar.dtype)),
                       self.ar.dtype)
        thr = np.asarray(self.lag_threshold, self.ar.dtype)
        want = np.ceil((total / thr).astype(np.float64)).astype(np.int64)
        want = np.clip(want, 1, self.n)
        self.under = np.where(want < self.n_cur, self.under + 1, 0)
        down = self.under >= self.patience
        n_new = np.where(want > self.n_cur, want,
                         np.where(down, want, self.n_cur))
        self.under = np.where(down, 0, self.under)
        self.n_cur = n_new
        rank = np.cumsum(act, axis=1) - 1
        assign = np.where(act, rank % n_new[:, None], NEG)
        return assign, n_new

    def _fold(self, assign, n_bins):
        """Consumers ranked by id; one of rank r >= max_replicas hands its
        partitions to the consumer of rank r mod max_replicas."""
        b, n = assign.shape
        m = 2 * n + 2
        used = np.zeros((b, m + 1), bool)
        used[np.arange(b)[:, None], np.where(assign >= 0, assign, m)] = True
        used = used[:, :m]
        rank = np.take_along_axis(np.cumsum(used, axis=1) - 1,
                                  np.clip(assign, 0, m - 1), axis=1)
        by_rank = np.argsort(~used, axis=1, kind="stable")   # used ids first
        folded = np.take_along_axis(by_rank, rank % self.max_replicas, axis=1)
        out = np.where((assign >= 0) & (rank >= self.max_replicas), folded,
                       assign)
        return out, np.minimum(n_bins, self.max_replicas)

    def step(self, t, speeds, observed, prev, act):
        d1 = self.obs_delay + 1
        idx, rd = t % d1, (t + 1) % d1
        self.obs_lag[:, idx] = observed
        self.obs_active[:, idx] = act
        cand, cand_n = self._trigger(self.obs_lag[:, rd],
                                     self.obs_active[:, rd])
        cand, cand_n = self._fold(cand, cand_n)
        cand_n = np.maximum(cand_n, self.min_replicas) \
            if self.min_replicas > 1 else cand_n
        cand = np.where(act, cand, NEG)
        held = np.where(act, prev, NEG)
        change = (cand_n != self.held_n) | np.any(cand != held, axis=1)
        accept = (t % self.poll == 0) & change & (t >= self.cool_until)
        self.pend_assign = np.where(accept[:, None], cand, self.pend_assign)
        self.pend_n = np.where(accept, cand_n, self.pend_n)
        self.pend_at = np.where(accept, t + self.act_delay, self.pend_at)
        self.pend_valid = self.pend_valid | accept
        apply = self.pend_valid & (self.pend_at <= t)
        out = np.where(apply[:, None], self.pend_assign, held)
        out = np.where(act, out, NEG)
        out_n = np.where(apply, self.pend_n, self.held_n)
        if self.min_replicas > 1:
            out_n = np.maximum(out_n, self.min_replicas)
        warming = np.maximum(self.warming - 1, 0)
        if self.warmup > 0:
            for b in np.flatnonzero(apply):
                moved = held[b] != out[b]
                touched = set(held[b][moved]) | set(out[b][moved])
                hit = np.array([c >= 0 and c in touched for c in out[b]])
                warming[b] = np.where(hit, self.warmup, warming[b])
        self.warming = warming
        self.held_n = out_n
        self.pend_valid = self.pend_valid & ~apply
        self.cool_until = np.where(apply, t + self.cooldown, self.cool_until)
        return out, out_n, self.warming > 0


class PackerPolicy:
    """A packer repacking every step with the last assignment as ``prev``:
    ``PACKERS[name]``, or ``fn`` of the same signature where given."""

    def __init__(self, name: str, cap: float, ar: Arith,
                 fn: Optional[Callable] = None):
        self.fn, self.cap, self.ar = fn or PACKERS[name], cap, ar

    def init(self, b: int):
        pass

    def step(self, t, speeds, observed, prev, act):
        r = self.ar.r
        out = np.full(prev.shape, NEG, np.int64)
        n_bins = np.zeros(prev.shape[0], np.int64)
        for b in range(prev.shape[0]):
            sp = [r(float(w)) for w in speeds[b]]
            a, k = self.fn(sp, act[b].tolist(), prev[b].tolist(), self.cap, r)
            out[b], n_bins[b] = a, k
        return out, n_bins, None


# ---------------------------------------------------------------------------
# the closed-loop lag twin
# ---------------------------------------------------------------------------

def rowsum(x: np.ndarray, dtype) -> np.ndarray:
    """Sum along the last axis, left to right, rounding each partial sum
    to ``dtype``."""
    total = np.zeros(x.shape[:-1], dtype)
    for j in range(x.shape[-1]):
        total = (total + x[..., j]).astype(dtype)
    return total


def drain(lag, produced, assign, readable, act, cap_step, dtype):
    """One step's drain over ``[B, N]``: each consumer sheds
    ``min(1, C dt / its readable backlog)`` of every readable partition's
    backlog; absent partitions end the step empty."""
    avail = (lag + produced).astype(dtype)
    live = readable & act & (assign >= 0)
    b, n = assign.shape
    m = 2 * n + 2
    per_bin = np.zeros((b, m), dtype)
    for j in range(n):
        rows = np.flatnonzero(live[:, j])
        per_bin[rows, assign[rows, j]] += avail[rows, j]
    ratio = np.minimum(np.asarray(1.0, dtype),
                       np.asarray(cap_step, dtype)
                       / np.maximum(per_bin, np.asarray(1e-30, dtype)))
    frac = np.where(live, np.take_along_axis(
        ratio, np.clip(assign, 0, m - 1), axis=1), np.asarray(0.0, dtype))
    out = np.maximum(avail * (np.asarray(1.0, dtype) - frac),
                     np.asarray(0.0, dtype))
    return np.where(act, out, np.asarray(0.0, dtype))


def twin(rates: np.ndarray, active: np.ndarray, policy, *, dt: float,
         capacity: float, migration_steps: int, ar: Arith
         ) -> Dict[str, np.ndarray]:
    """Run one policy over groups ``rates f32[B, T, N]`` from empty
    backlogs.  Returns per-step ``[B, T]`` trajectories: ``lag_total``,
    ``lag_max``, ``consumers``, ``migrations`` and ``unreadable``."""
    b, steps, n = rates.shape
    dtype = ar.dtype
    lag = np.zeros((b, n), dtype)
    assign = np.full((b, n), NEG, np.int64)
    down = np.zeros((b, n), np.int64)
    policy.init(b)
    out = {k: [] for k in ("lag_total", "lag_max", "consumers", "migrations",
                           "unreadable")}
    for t in range(steps):
        act = active[:, t]
        rate = rates[:, t].astype(np.float64).astype(dtype)
        produced = np.where(act, rate * np.asarray(dt, dtype),
                            np.asarray(0.0, dtype)).astype(dtype)
        observed = (lag + produced).astype(dtype)
        new, n_cons, storm = policy.step(t, rate, observed, assign, act)
        moved = (assign >= 0) & (new >= 0) & (new != assign)
        down = np.where(moved, migration_steps, np.maximum(down - 1, 0))
        readable = (down == 0) & (new >= 0)
        blocked = down > 0
        if storm is not None:
            readable &= ~storm
            blocked |= storm & (new >= 0)
        lag = drain(lag, produced, new, readable, act, capacity * dt, dtype)
        assign = new
        out["lag_total"].append(rowsum(lag, dtype))
        out["lag_max"].append(np.max(lag, axis=1))
        out["consumers"].append(n_cons)
        out["migrations"].append(moved.sum(axis=1))
        out["unreadable"].append((blocked & act).sum(axis=1))
    return {k: np.stack(v, axis=1).astype(
        np.float64 if k.startswith("lag") else np.int64)
        for k, v in out.items()}
