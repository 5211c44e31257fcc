"""JAX implementations of the paper's packing algorithms.

Pure ``jax.lax`` control flow (scan over items, masked argmin/argmax over
bins), so a whole 500-iteration stream evaluation jit-compiles into a single
XLA program and the packer can run *inside* the controller's jitted decision
step on device.  Inside a scan body the carried state is read and written
only by one-hot selects against an iota (``jnp.where(iota == slot, ...)``
and masked reductions), never at a traced index: under the fleet's
``jax.vmap`` a traced-index access is a batched gather or scatter, a slow
serialized op on TPU, where a select is elementwise work that fuses.  The
per-item inputs are permuted into scan order once, before the loop, and
fed as the scan's ``xs``.  Semantics (including tie-breaking and the
Sec. IV-C sticky naming rule) match ``binpack.py`` / ``modified.py``
bit-for-bit; the property tests in ``tests/test_jaxpack.py`` enforce exact
agreement.

Conventions
-----------
* ``speeds``: f32[n] item sizes.
* ``prev``:   i32[n] previous bin name per item, ``-1`` = unassigned.
* ``active``: optional bool[n] partition mask.  An inactive item -- a
  partition that does not currently exist (topic deleted, not yet
  created, or fleet padding) -- packs to ``NEG``, contributes no load,
  claims no bin name and never creates a bin.  ``active=None`` keeps
  the exact unmasked program, and an all-``True`` mask reproduces the
  unmasked pack bit-for-bit (tests/test_masking.py).
* bin *names* are ints in ``[0, 2n+1)``; ``-1`` never names a bin.
* returns ``PackedJax(bin_of: i32[n], loads: f32[M], names: i32[M], n_bins)``
  where slot ``s < n_bins`` holds ``loads[s]`` and is named ``names[s]``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

NEG = -1


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PackedJax:
    bin_of: jax.Array   # i32[n]  bin name per item
    loads: jax.Array    # f32[M]  load per creation slot
    names: jax.Array    # i32[M]  name per creation slot
    n_bins: jax.Array   # i32[]   number of created bins


def _pick(x, hot):
    """``x[i]`` where ``hot = iota == i``: a one-hot masked reduction.

    A traced-index read ``x[i]`` is a gather under ``vmap`` (a batched one on
    the fleet path); the reduction is elementwise work on every backend.
    """
    if x.dtype == jnp.bool_:
        return jnp.any(hot & x, axis=-1)
    if jnp.issubdtype(x.dtype, jnp.floating):
        return jnp.max(jnp.where(hot, x, -jnp.inf), axis=-1)
    return jnp.sum(jnp.where(hot, x, 0), axis=-1)


def _select_slot(loads, k, w, capacity, strategy: str):
    """Masked fit-strategy selection over created slots [0, k). Returns
    (slot, found)."""
    iota = jnp.arange(loads.shape[0])
    created = iota < k
    fits = created & (loads + w <= capacity)
    if strategy == "next":
        last = jnp.maximum(k - 1, 0)
        ok = (k > 0) & _pick(fits, iota == last)
        return last, ok
    if strategy == "first":
        return jnp.argmax(fits), fits.any()
    if strategy == "best":    # tightest fit = max load among fitting, first on tie
        score = jnp.where(fits, loads, -jnp.inf)
        return jnp.argmax(score), fits.any()
    if strategy == "worst":   # most slack = min load among fitting, first on tie
        score = jnp.where(fits, loads, jnp.inf)
        return jnp.argmin(score), fits.any()
    raise ValueError(f"unknown strategy {strategy!r}")


def _fresh_name(used, prev_name):
    """Sec. IV-C naming: the item's previous bin if still unused, else the
    lowest unused name."""
    lowest = jnp.argmin(used)                     # first False
    held = _pick(used, jnp.arange(used.shape[0]) == prev_name)
    sticky_ok = (prev_name >= 0) & ~held
    return jnp.where(sticky_ok, prev_name, lowest)


def _place_or_create(state, j, w, prev_name, live, capacity, strategy: str,
                     sticky: bool):
    """Any-fit insert of item ``j``: selected open bin, else a new bin.

    ``live`` (bool, or None for always) gates every write, so a dead item
    leaves the state as it was.  Writes are one-hot selects against an
    iota: under ``vmap`` an ``x.at[i].set`` with a traced ``i`` is a batched
    scatter, a slow serialized op on TPU, where a select stays elementwise.
    """
    loads, names, used, k, bin_of = state
    slot, found = _select_slot(loads, k, w, capacity, strategy)
    name_new = _fresh_name(used, prev_name if sticky else jnp.int32(NEG))
    slot = jnp.where(found, slot, k)
    at_slot = jnp.arange(loads.shape[0]) == slot
    name = jnp.where(found, _pick(names, at_slot), name_new)
    at_name = jnp.arange(used.shape[0]) == name
    at_j = jnp.arange(bin_of.shape[0]) == j
    grow = ~found
    if live is not None:
        at_slot, at_name, at_j = at_slot & live, at_name & live, at_j & live
        grow = grow & live
    loads = jnp.where(at_slot, loads + w, loads)
    names = jnp.where(at_slot, name, names)
    used = used | at_name
    k = jnp.where(grow, k + 1, k)
    bin_of = jnp.where(at_j, name, bin_of)
    return loads, names, used, k, bin_of


# ---------------------------------------------------------------------------
# classical algorithms (NF/FF/BF/WF and their Decreasing variants)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("strategy", "decreasing", "sticky"))
def pack_jax(
    speeds: jax.Array,
    prev: jax.Array,
    capacity,
    *,
    strategy: str = "first",
    decreasing: bool = False,
    sticky: bool = True,
    active: jax.Array | None = None,
) -> PackedJax:
    n = speeds.shape[0]
    m = n + 1
    u = 2 * n + 2                  # name universe
    speeds = speeds.astype(jnp.float32)
    prev = prev.astype(jnp.int32)
    capacity = jnp.float32(capacity)
    if active is not None:
        active = active.astype(bool)

    # the item order is fixed before the loop, so every per-item input is
    # permuted into it once and fed as the scan's xs
    order = jnp.arange(n, dtype=jnp.int32)
    xs = (speeds, prev, active)
    if decreasing:
        # stable non-increasing sort: (-speed, original index)
        order = jnp.lexsort((order, -speeds)).astype(jnp.int32)
        xs = jax.tree_util.tree_map(lambda a: a[order], xs)

    def body(state, x):
        j, (w, prev_name, live) = x
        return _place_or_create(state, j, w, prev_name, live, capacity,
                                strategy, sticky), None

    init = (
        jnp.zeros(m, jnp.float32),
        jnp.full(m, NEG, jnp.int32),
        jnp.zeros(u, bool),
        jnp.int32(0),
        jnp.full(n, NEG, jnp.int32),
    )
    (loads, names, used, k, bin_of), _ = lax.scan(body, init, (order, xs))
    return PackedJax(bin_of=bin_of, loads=loads, names=names, n_bins=k)


# ---------------------------------------------------------------------------
# Modified Any Fit (Algorithm 1) -- MWF / MBF / MWFP / MBFP
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("fit", "sort_key"))
def modified_any_fit_jax(
    speeds: jax.Array,
    prev: jax.Array,
    capacity,
    *,
    fit: str = "best",
    sort_key: str = "cumulative",
    active: jax.Array | None = None,
) -> PackedJax:
    """Algorithm 1 as a single lax.scan over a 2n-entry flattened schedule.

    Each item appears twice: once in its consumer's phase-1 slot (smallest ->
    biggest, try open bins only) and once in phase-2 (biggest -> smallest,
    own-bin insert).  Consumers are visited in non-increasing key order and
    their two phases are contiguous, reproducing the per-consumer interleave
    of the pseudocode.  Leftovers are packed by a final decreasing any-fit
    scan with sticky bin naming.

    With an ``active`` mask, an inactive item counts as *absent*: it is
    treated as neither assigned nor pending (so it enters no phase and
    never reaches the final any-fit stage), matching the reference
    semantics of simply dropping the partition from the ``speeds`` map.
    """
    if fit not in ("best", "worst"):
        raise ValueError(fit)
    n = speeds.shape[0]
    m = 2 * n + 1                   # phase-2 creates <= n bins, final <= n
    u = 2 * n + 2                   # name universe (names provably <= 2n)
    s = u                           # consumer-segment universe: prev names <= 2n
    speeds = speeds.astype(jnp.float32)
    prev = prev.astype(jnp.int32)
    capacity = jnp.float32(capacity)
    pid = jnp.arange(n)
    assigned = prev >= 0
    pending0 = ~assigned
    if active is not None:
        active = active.astype(bool)
        assigned = assigned & active
        pending0 = ~assigned & active
    cseg = jnp.where(assigned, prev, s - 1)   # s-1 = dummy for unassigned

    # consumer sort keys (non-increasing; tie -> lower consumer id first)
    zero = jnp.zeros(s, jnp.float32)
    cum = zero.at[cseg].add(speeds)
    mx = zero.at[cseg].max(speeds)
    key = cum if sort_key == "cumulative" else (
        mx if sort_key == "max_partition" else None)
    if key is None:
        raise ValueError(sort_key)
    has = jnp.zeros(s, bool).at[cseg].set(True)
    key = jnp.where(has, key, -jnp.inf)
    crank_order = jnp.lexsort((jnp.arange(s), -key))          # rank -> consumer
    crank = jnp.zeros(s, jnp.int32).at[crank_order].set(jnp.arange(s, dtype=jnp.int32))
    item_rank = crank[cseg]                                    # i32[n]

    # phase-1 within-consumer order: speed asc, pid desc  (reverse of the
    # decreasing list, traversed back-to-front as in lines 6-13)
    p1 = jnp.lexsort((-pid, speeds, item_rank))
    # phase-2 within-consumer order: speed desc, pid asc (lines 18-24)
    p2 = jnp.lexsort((pid, -speeds, item_rank))
    # interleave: for each consumer, all its phase-1 entries then phase-2.
    seq_items = jnp.concatenate([p1, p2])
    seq_phase = jnp.concatenate([jnp.zeros(n, jnp.int32), jnp.ones(n, jnp.int32)])
    seq_pos = jnp.concatenate([jnp.arange(n), jnp.arange(n)])
    seq_rank = item_rank[seq_items]
    entry_order = jnp.lexsort((seq_pos, seq_phase, seq_rank))
    seq_items = seq_items[entry_order]
    seq_phase = seq_phase[entry_order]

    iota_m = jnp.arange(m)
    iota_u = jnp.arange(u)
    iota_n = jnp.arange(n)

    def body(state, ent):
        j, phase, entry_idx, w, c, asg = ent
        at_j = iota_n == j
        at_c = iota_u == c                    # s == u: one iota serves both

        def phase1(state):
            (loads, names, used, k, bin_of, placed, to_u, u_order,
             fail1, own_slot, own_fail) = state
            slot, found = _select_slot(loads, k, w, capacity, fit)
            found = found & ~_pick(fail1, at_c)
            loads = jnp.where(found & (iota_m == slot), loads + w, loads)
            bin_of = jnp.where(found & at_j, _pick(names, iota_m == slot), bin_of)
            placed = placed | (found & at_j)
            fail1 = fail1 | (~found & at_c)
            return (loads, names, used, k, bin_of, placed, to_u, u_order,
                    fail1, own_slot, own_fail)

        def phase2(state):
            (loads, names, used, k, bin_of, placed, to_u, u_order,
             fail1, own_slot, own_fail) = state
            # create the consumer's own bin (named c) on its first
            # still-unplaced item (pset nonempty <=> some phase-1 failure)
            own = _pick(own_slot, at_c)
            need_create = own < 0
            own = jnp.where(need_create, k, own)
            at_own = iota_m == own
            names = jnp.where(need_create & at_own, c, names)
            used = used | (need_create & at_c)
            own_slot = jnp.where(need_create & at_c, own, own_slot)
            k = jnp.where(need_create, k + 1, k)
            # oversized exception: an item with w > C may hold its own
            # empty bin (matches modified.py; see comment there)
            load_own = _pick(loads, at_own)
            fits = ((load_own + w <= capacity) |
                    ((load_own == 0.0) & (w > capacity))) & ~_pick(own_fail, at_c)
            loads = jnp.where(fits & at_own, loads + w, loads)
            bin_of = jnp.where(fits & at_j, c, bin_of)
            placed = placed | (fits & at_j)
            own_fail = own_fail | (~fits & at_c)
            to_u = to_u | (~fits & at_j)
            u_order = jnp.where(~fits & at_j, n + entry_idx, u_order)
            return (loads, names, used, k, bin_of, placed, to_u, u_order,
                    fail1, own_slot, own_fail)

        # Under the fleet's vmap both conds run as selects of their
        # branches; unbatched (api.pack) they skip the entries with no work:
        # an item's phase-2 entry once phase 1 placed it, and both entries
        # of an unassigned item.
        placed = state[5]
        skip = _pick(placed, at_j) | ~asg
        return lax.cond(skip, lambda st: st,
                        lambda st: lax.cond(phase == 0, phase1, phase2, st),
                        state), None

    init = (
        jnp.zeros(m, jnp.float32),            # loads
        jnp.full(m, NEG, jnp.int32),          # names
        jnp.zeros(u, bool),                   # used names
        jnp.int32(0),                         # k
        jnp.full(n, NEG, jnp.int32),          # bin_of
        jnp.zeros(n, bool),                   # placed
        pending0,                             # to_u (initially: unassigned items)
        jnp.where(assigned, 3 * n, pid).astype(jnp.int32),  # u_order (pid for initial U)
        jnp.zeros(s, bool),                   # fail1 per consumer
        jnp.full(s, NEG, jnp.int32),          # own_slot per consumer
        jnp.zeros(s, bool),                   # own_fail per consumer
    )
    ents = (seq_items, seq_phase, jnp.arange(2 * n, dtype=jnp.int32),
            speeds[seq_items], cseg[seq_items], assigned[seq_items])
    state, _ = lax.scan(body, init, ents)
    (loads, names, used, k, bin_of, placed, to_u, u_order, *_rest) = state

    # final stage (lines 27-29): decreasing any-fit over U with sticky naming
    final_order = jnp.lexsort((u_order, -speeds))

    def fbody(state, x):
        j, w, prev_name, pending = x
        return lax.cond(
            pending,
            lambda st: _place_or_create(st, j, w, prev_name, None, capacity,
                                        fit, True),
            lambda st: st, state), None

    (loads, names, used, k, bin_of), _ = lax.scan(
        fbody, (loads, names, used, k, bin_of),
        (final_order, speeds[final_order], prev[final_order], to_u[final_order]))
    return PackedJax(bin_of=bin_of, loads=loads, names=names, n_bins=k)


# ---------------------------------------------------------------------------
# whole-stream evaluation (bins + Rscore per iteration) in one jitted scan
# ---------------------------------------------------------------------------

def packer_for(name: str):
    """Public dispatch: ``name`` -> ``fn(speeds, prev, capacity) -> PackedJax``.

    The callable is scan-safe (pure jax.lax control flow), so downstream
    closed loops -- the controller decision step, ``repro.lagsim`` -- can run
    a repack every simulated step inside one jitted program.  Names resolve
    through ``repro.registry`` (the single policy catalogue); the identity
    of each algorithm -- fit strategy, decreasing pre-sort, consumer sort
    key -- lives in its registered ``PolicySpec``.
    """
    from repro.registry import packer_for as _registry_packer_for

    return _registry_packer_for(name, backend="jax")


def _stream_scan(stream: jax.Array, capacity, algorithm: str,
                 active: jax.Array | None = None
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Shared scan over an (N, P) stream: the previous iteration's assignment
    feeds the next, as in the controller loop.  ``active`` (bool[N, P],
    optional) masks partitions per iteration: a dead partition packs to
    ``NEG``, so a *death* costs no migration and a *rebirth* restarts with
    no sticky memory.  Returns per-iteration (bins i32[N], rscore f32[N],
    migrations i32[N])."""
    packer = packer_for(algorithm)
    n = stream.shape[1]
    capacity = jnp.float32(capacity)

    def step(prev, xs):
        if active is None:
            speeds = xs
            res = packer(speeds, prev, capacity)
        else:
            speeds, act = xs
            res = packer(speeds, prev, capacity, active=act)
        # NEG never counts as a move: a newly-dead partition hands off
        # nothing (its consumer just stops reading), and res.bin_of >= 0
        # always holds in the unmasked path
        moved = (prev >= 0) & (res.bin_of >= 0) & (res.bin_of != prev)
        r = jnp.sum(jnp.where(moved, speeds, 0.0)) / capacity
        migs = jnp.sum(moved.astype(jnp.int32))
        return res.bin_of, (res.n_bins, r, migs)

    xs = (stream.astype(jnp.float32) if active is None
          else (stream.astype(jnp.float32), active.astype(bool)))
    _, (bins, rs, migs) = lax.scan(step, jnp.full(n, NEG, jnp.int32), xs)
    return bins, rs, migs


@functools.partial(jax.jit, static_argnames=("algorithm",))
def evaluate_stream_jax(stream: jax.Array, capacity, *, algorithm: str,
                        active: jax.Array | None = None
                        ) -> Tuple[jax.Array, jax.Array]:
    """Run one algorithm over an (N, P) stream.

    Returns (bins_per_iter i32[N], rscore_per_iter f32[N]).  The previous
    iteration's assignment feeds the next, as in the controller loop.
    ``active`` (bool[N, P]) masks partitions per iteration.
    """
    bins, rs, _ = _stream_scan(stream, capacity, algorithm, active)
    return bins, rs


# ---------------------------------------------------------------------------
# batched scenario sweep: all algorithms x a whole batch of streams
# ---------------------------------------------------------------------------

def __getattr__(name: str):
    # deprecation shim: the hand-enumerated name table is now derived from
    # the registry (tests/test_registry.py pins the warning)
    if name == "ALL_ALGORITHM_NAMES":
        from repro.registry import PACKER_FAMILIES, list_policies
        from repro.registry.compat import warn_deprecated

        warn_deprecated(__name__, "ALL_ALGORITHM_NAMES",
                        "repro.registry.list_policies(family=('heuristic', "
                        "'sticky'), backend='jax')")
        return list_policies(family=PACKER_FAMILIES, backend="jax")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SweepResult:
    """Per-step traces of a batched sweep, indexed [algorithm, stream, iter].

    ``algorithms`` records the row order of axis 0 (static metadata).
    """
    bins: jax.Array        # i32[A, B, T]  consumers used per iteration
    rscores: jax.Array     # f32[A, B, T]  Eq. 10 rebalance cost per iteration
    migrations: jax.Array  # i32[A, B, T]  partitions moved per iteration
    algorithms: Tuple[str, ...] = dataclasses.field(
        metadata=dict(static=True))

    def for_algorithm(self, name: str
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        a = self.algorithms.index(name.upper())
        return self.bins[a], self.rscores[a], self.migrations[a]


def _sweep_streams_impl(algorithms: Tuple[str, ...], speeds_batch: jax.Array,
                        capacity, active: jax.Array | None = None
                        ) -> SweepResult:
    """Unjitted sweep core, shared by the module-level jit below and the
    fleet execution layer (``repro.fleet``), which jits it under its own
    bounded per-bucket cache."""
    if active is None:
        per_algo = [
            jax.vmap(lambda s, a=a: _stream_scan(s, capacity, a))(speeds_batch)
            for a in algorithms
        ]
    else:
        per_algo = [
            jax.vmap(lambda s, m, a=a: _stream_scan(s, capacity, a, m))(
                speeds_batch, active)
            for a in algorithms
        ]
    bins = jnp.stack([p[0] for p in per_algo])
    rs = jnp.stack([p[1] for p in per_algo])
    migs = jnp.stack([p[2] for p in per_algo])
    return SweepResult(bins=bins, rscores=rs, migrations=migs,
                       algorithms=algorithms)


@functools.partial(jax.jit, static_argnames=("algorithms",))
def _sweep_streams_jit(algorithms: Tuple[str, ...], speeds_batch: jax.Array,
                       capacity, active: jax.Array | None = None
                       ) -> SweepResult:
    return _sweep_streams_impl(algorithms, speeds_batch, capacity, active)


def sweep_streams(algorithms: Tuple[str, ...], speeds_batch: jax.Array,
                  capacity, active: jax.Array | None = None) -> SweepResult:
    """Evaluate ``algorithms`` over a whole batch of streams in one program.

    ``speeds_batch``: f32[B, T, N] -- B streams of T measurements over N
    partitions (e.g. from ``scenarios.scenario_suite`` / ``stack_suite``).
    ``active``: optional bool[B, T, N] partition mask (see
    ``scenarios.masked_scenario_suite``); inactive partitions pack to
    ``NEG`` and contribute no bins, load, or R-score.
    Each algorithm's scan is vmapped over the batch axis; with batch size 1
    the result is bit-identical to ``evaluate_stream_jax`` on the single
    stream (enforced by tests/test_scenarios.py).

    Names are case-normalized *before* the jit boundary so equivalent
    spellings share one compile-cache entry.
    """
    return _sweep_streams_jit(tuple(a.upper() for a in algorithms),
                              speeds_batch, capacity, active)
