"""The variable-N mask contract, pinned end to end.

Load-bearing properties (ISSUE acceptance criteria):

* **All-active is the identity** -- packing with an all-``True`` mask is
  bit-identical to the unmasked packer, on every registered algorithm
  (hypothesis property, both backends).
* **A masked-out item does not exist** -- it never names a bin (its
  ``bin_of`` is ``NEG``), contributes no load, and the masked jax pack
  equals the reference pack of the speed map with the item removed --
  the py backend's native notion of absence (hypothesis property).
* The same holds one level up (sweep driver, run_stream, policies,
  annealer) and one level down (the Pallas kernels' masked variants
  against their oracles).
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # pragma: no cover - CI installs it
    HAVE_HYPOTHESIS = False

import jax
import jax.numpy as jnp

from repro.core.jaxpack import evaluate_stream_jax, sweep_streams
from repro.core.metrics import run_stream
from repro.registry import (PACKER_FAMILIES, list_policies, make_policy,
                            packer_for)

C = 1.0
NEG = -1

ALGORITHMS = list_policies(family=PACKER_FAMILIES, backend="jax")

if HAVE_HYPOTHESIS:
    speeds_st = st.lists(
        st.integers(min_value=0, max_value=2048).map(lambda k: k / 1024.0),
        min_size=1,
        max_size=20,
    )


def _instance(speeds, seed):
    """Quantized instance + random prev + random mask from one seed."""
    n = len(speeds)
    rng = np.random.default_rng(seed)
    prev = rng.integers(-1, max(1, n // 2), size=n).astype(np.int32)
    active = rng.integers(0, 2, size=n).astype(bool)
    return (jnp.asarray(speeds, jnp.float32), jnp.asarray(prev),
            jnp.asarray(active), prev, active)


# ---------------------------------------------------------------------------
# one-shot packers (the satellite property, both backends)
# ---------------------------------------------------------------------------
def _check_all_active_identity(speeds, seed, name):
    sj, pj, _, _, _ = _instance(speeds, seed)
    n = len(speeds)
    fn = packer_for(name, backend="jax")
    plain = fn(sj, pj, C)
    masked = fn(sj, pj, C, active=jnp.ones(n, bool))
    assert np.asarray(plain.bin_of).tobytes() == \
        np.asarray(masked.bin_of).tobytes(), name
    assert np.asarray(plain.loads).tobytes() == \
        np.asarray(masked.loads).tobytes(), name
    assert np.asarray(plain.names).tobytes() == \
        np.asarray(masked.names).tobytes(), name
    assert int(plain.n_bins) == int(masked.n_bins), name


def _check_masked_absent(speeds, seed, name):
    """A masked-out item packs to NEG, adds no load, opens no bin; the
    surviving pack is exactly the py reference pack of the speed map with
    the masked items *removed* (both backends see one semantics)."""
    sj, pj, aj, prev, active = _instance(speeds, seed)
    res = packer_for(name, backend="jax")(sj, pj, C, active=aj)
    bin_of = np.asarray(res.bin_of)
    k = int(res.n_bins)
    # absent: no bin name, no load
    assert (bin_of[~active] == NEG).all(), name
    live_load = sum(w for j, w in enumerate(speeds) if active[j])
    assert float(np.asarray(res.loads)[:k].sum()) == \
        pytest.approx(live_load, abs=1e-5), name
    # cross-backend: reference pack of the filtered dict
    sp = {j: w for j, w in enumerate(speeds) if active[j]}
    prev_map = {j: int(c) for j, c in enumerate(prev)
                if active[j] and c >= 0}
    ref = packer_for(name, backend="py")(sp, C, prev=prev_map)
    assert k == ref.n_bins, name
    for j, cid in ref.pid_to_bin.items():
        assert int(bin_of[j]) == cid, (name, j)
    jl = {int(nm): float(ld)
          for nm, ld in zip(np.asarray(res.names)[:k],
                            np.asarray(res.loads)[:k])}
    for cid, load in ref.loads.items():
        assert jl[cid] == pytest.approx(load, abs=1e-6), (name, cid)


if HAVE_HYPOTHESIS:
    @settings(max_examples=150, deadline=None)
    @given(speeds=speeds_st, seed=st.integers(0, 2**31 - 1),
           name=st.sampled_from(sorted(ALGORITHMS)))
    def test_all_active_mask_is_bit_identical(speeds, seed, name):
        _check_all_active_identity(speeds, seed, name)

    @settings(max_examples=150, deadline=None)
    @given(speeds=speeds_st, seed=st.integers(0, 2**31 - 1),
           name=st.sampled_from(sorted(ALGORITHMS)))
    def test_masked_item_absent_and_backends_agree(speeds, seed, name):
        _check_masked_absent(speeds, seed, name)


@pytest.mark.parametrize("name", sorted(ALGORITHMS))
@pytest.mark.parametrize("seed", (0, 7))
def test_mask_contract_fixed_instances(name, seed):
    """Deterministic fallback of the hypothesis properties above (always
    runs, with or without hypothesis installed)."""
    rng = np.random.default_rng(100 + seed)
    speeds = list(np.round(rng.uniform(0, 2, 14) * 1024) / 1024.0)
    _check_all_active_identity(speeds, seed, name)
    _check_masked_absent(speeds, seed, name)


def _masked_fleet(b=8, n=16, seed=3):
    """A batch of masked instances: an all-inactive row, speed ties,
    oversized items (w > C), and previous names at slots >= n."""
    rng = np.random.default_rng(seed)
    speeds = rng.integers(0, 1100, (b, n)) / 1024.0
    speeds[1] = rng.choice([0.25, 0.5], n)              # ties in speed
    speeds[2, ::3] = rng.integers(1025, 3072, len(speeds[2, ::3])) / 1024.0
    prev = rng.integers(-1, max(1, n // 2), (b, n))
    prev[3] = rng.integers(n, 2 * n + 1, n)             # names >= n
    prev[4, ::2] = NEG
    active = rng.random((b, n)) < 0.75
    active[0] = False                                   # all inactive
    active[5] = True
    return (jnp.asarray(speeds, jnp.float32), jnp.asarray(prev, jnp.int32),
            jnp.asarray(active))


@pytest.mark.parametrize("name", sorted(ALGORITHMS))
def test_vmapped_pack_equals_row_by_row(name):
    """The fleet path (``vmap`` over masked rows) packs each row exactly
    as the packer run on that row alone, bit for bit."""
    fn = packer_for(name, backend="jax")
    speeds, prev, active = _masked_fleet()
    batched = jax.jit(jax.vmap(lambda s, p, a: fn(s, p, C, active=a)))(
        speeds, prev, active)
    for i in range(speeds.shape[0]):
        row = fn(speeds[i], prev[i], C, active=active[i])
        for field in ("bin_of", "loads", "names", "n_bins"):
            got = np.asarray(getattr(batched, field))[i]
            want = np.asarray(getattr(row, field))
            assert got.tobytes() == want.tobytes(), (name, i, field)


# ---------------------------------------------------------------------------
# sweep driver + reference stream runner
# ---------------------------------------------------------------------------
def test_sweep_all_active_bit_identical():
    traces = jax.random.uniform(jax.random.key(0), (2, 14, 6), maxval=0.9)
    ones = jnp.ones(traces.shape, bool)
    plain = sweep_streams(("BFD", "MBFP", "WF"), traces, C)
    masked = sweep_streams(("BFD", "MBFP", "WF"), traces, C, ones)
    for a, b in ((plain.bins, masked.bins), (plain.rscores, masked.rscores),
                 (plain.migrations, masked.migrations)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_masked_sweep_matches_reference_run_stream():
    """Whole-stream masked scan == the py controller loop that drops dead
    partitions from each iteration's speed map."""
    rng = np.random.default_rng(5)
    t, n = 20, 7
    stream = np.round(rng.uniform(0, 1, (t, n)) * 1024) / 1024.0
    active = rng.integers(0, 2, (t, n)).astype(bool)
    for name in ("BFD", "MWFP"):
        runs = run_stream({name: packer_for(name, backend="py")},
                          stream, C, active=active)
        bins_jax, rs_jax = evaluate_stream_jax(
            jnp.asarray(stream, jnp.float32), C, algorithm=name,
            active=jnp.asarray(active))
        np.testing.assert_array_equal(np.asarray(bins_jax),
                                      np.array(runs[name].bins))
        np.testing.assert_allclose(np.asarray(rs_jax),
                                   np.array(runs[name].rscores), atol=1e-6)


def test_dead_partition_costs_no_migration():
    """A partition dying mid-stream (active -> inactive) must not itself
    count as a migration or price an R-score move.  Speeds are 0.8 per
    partition (capacity 1.0), so every partition sits alone in its own
    sticky-named bin and a death cannot make the *others* repack."""
    stream = jnp.full((4, 3), 0.8, jnp.float32)
    active = jnp.asarray([[True, True, True],
                          [True, True, True],
                          [True, False, True],   # partition 1 dies
                          [True, False, True]])
    res = sweep_streams(("BFD",), stream[None], C, active[None])
    bins = np.asarray(res.bins[0, 0])
    migs = np.asarray(res.migrations[0, 0])
    rs = np.asarray(res.rscores[0, 0])
    np.testing.assert_array_equal(bins, [3, 3, 2, 2])  # the bin disappears
    assert (migs[1:] == 0).all() and (rs[1:] == 0.0).all()


# ---------------------------------------------------------------------------
# Policy protocol (registry builders honor the mask)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ("BFD", "MBFP", "KEDA_LAG",
                                  "RATE_THRESHOLD", "ANNEAL_STICKY"))
def test_policy_step_masks_partitions(name):
    n = 6
    pol = make_policy(name, n, C, backend="jax", strict=False)
    speeds = jnp.asarray([0.4, 0.5, 0.3, 0.6, 0.2, 0.4], jnp.float32)
    lag = 2.0 * speeds
    prev = jnp.full(n, NEG, jnp.int32)
    active = jnp.asarray([True, False, True, True, False, True])
    assign, k, _ = pol.step(speeds, lag, prev, pol.init(n), active)
    assign = np.asarray(assign)
    assert (assign[~np.asarray(active)] == NEG).all(), name
    assert (assign[np.asarray(active)] >= 0).all(), name
    assert int(k) >= 1


@pytest.mark.parametrize("name", ("BFD", "KEDA_LAG", "RATE_THRESHOLD"))
def test_policy_step_all_active_equals_unmasked(name):
    n = 5
    pol = make_policy(name, n, C, backend="jax", strict=False)
    speeds = jnp.asarray([0.7, 0.2, 0.9, 0.4, 0.5], jnp.float32)
    lag = 3.0 * speeds
    prev = jnp.asarray([1, 0, NEG, 2, 1], jnp.int32)
    a0, k0, _ = pol.step(speeds, lag, prev, pol.init(n))
    a1, k1, _ = pol.step(speeds, lag, prev, pol.init(n), jnp.ones(n, bool))
    np.testing.assert_array_equal(np.asarray(a0), np.asarray(a1))
    assert int(k0) == int(k1)


# ---------------------------------------------------------------------------
# annealer
# ---------------------------------------------------------------------------
def test_anneal_mask_semantics():
    from repro.opt.anneal import anneal_assign, assignment_cost, name_universe

    rng = np.random.default_rng(2)
    n = 10
    speeds = jnp.asarray(rng.uniform(0.05, 0.6, n), jnp.float32)
    prev = jnp.asarray(rng.integers(-1, 5, n), jnp.int32)
    active = jnp.asarray(rng.integers(0, 2, n).astype(bool))
    key = jax.random.key(7)
    # all-active == unmasked bit-for-bit (same PRNG shapes, same logits)
    a0 = anneal_assign(speeds, prev, C, key, lam=2.0, chains=4, steps=40)
    a1 = anneal_assign(speeds, prev, C, key, lam=2.0, chains=4, steps=40,
                       active=jnp.ones(n, bool))
    np.testing.assert_array_equal(np.asarray(a0[0]), np.asarray(a1[0]))
    assert int(a0[1]) == int(a1[1])
    # masked: inactive items come back NEG; bins count only live items
    assign, bins = anneal_assign(speeds, prev, C, key, lam=2.0, chains=4,
                                 steps=40, active=active)
    assign = np.asarray(assign)
    act = np.asarray(active)
    assert (assign[~act] == NEG).all()
    assert (assign[act] >= 0).all()
    _, bins2, _ = assignment_cost(jnp.asarray(assign), speeds, prev, C,
                                  jnp.float32(2.0), m=name_universe(n),
                                  active=active)
    assert int(bins) == int(bins2) == len(set(assign[act]))


def test_assignment_cost_ignores_masked_items():
    from repro.opt.anneal import assignment_cost

    speeds = jnp.asarray([0.5, 0.5, 0.5], jnp.float32)
    prev = jnp.asarray([0, 1, 2], jnp.int32)
    assign = jnp.asarray([0, 5, 2], jnp.int32)    # item 1 moved
    active = jnp.asarray([True, False, True])
    cost, bins, r = assignment_cost(assign, speeds, prev, C,
                                    jnp.float32(1.0), m=8, active=active)
    assert int(bins) == 2          # item 1's bin does not exist
    assert float(r) == 0.0         # its move is not priced


# ---------------------------------------------------------------------------
# kernels: masked variants vs oracles
# ---------------------------------------------------------------------------
def test_select_slot_masked_rows_return_neg():
    from repro.kernels.binpack_select import select_slot_grid

    rng = np.random.default_rng(0)
    b, n, m = 2, 40, 16
    loads = jnp.asarray(rng.uniform(0, 1, (b, n, m)), jnp.float32)
    w = jnp.asarray(rng.uniform(0, 0.6, (b, n)), jnp.float32)
    k = jnp.asarray(rng.integers(0, m + 1, (b, n)), jnp.int32)
    cap = jnp.ones((b, n), jnp.float32)
    active = jnp.asarray(rng.integers(0, 2, (b, n)), jnp.int32)
    got = np.asarray(select_slot_grid(loads, w, k, cap, active=active))
    plain = np.asarray(select_slot_grid(loads, w, k, cap))
    act = np.asarray(active).astype(bool)
    assert (got[~act] == NEG).all()
    np.testing.assert_array_equal(got[act], plain[act])


def test_lag_update_masked_matches_reference_and_zeroes_dead():
    from repro.kernels.lag_update import lag_update_batch, lag_update_reference

    rng = np.random.default_rng(1)
    b, n, m = 3, 12, 26
    lag = jnp.asarray(rng.uniform(0, 5, (b, n)), jnp.float32)
    prod = jnp.asarray(rng.uniform(0, 1, (b, n)), jnp.float32)
    assign = jnp.asarray(rng.integers(-1, m, (b, n)), jnp.int32)
    readable = jnp.asarray(rng.integers(0, 2, (b, n)), jnp.int32)
    cap = jnp.full((b, m), 1.1, jnp.float32)
    active = jnp.asarray(rng.integers(0, 2, (b, n)), jnp.int32)
    out_k = lag_update_batch(lag, prod, assign, readable, cap, active=active)
    out_r = lag_update_reference(lag, prod, assign, readable, cap, m=m,
                                 active=active)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-6, atol=1e-6)
    assert (np.asarray(out_k)[~np.asarray(active).astype(bool)] == 0.0).all()


def test_move_delta_masked_blocks_inactive_rows():
    from repro.kernels.move_eval import (MOVE_BLOCKED, move_delta_batch,
                                         move_delta_reference)

    rng = np.random.default_rng(3)
    k, n, m = 3, 8, 18
    assign = jnp.asarray(rng.integers(0, m, (k, n)), jnp.int32)
    counts = jnp.zeros((k, m), jnp.int32)
    counts = counts.at[jnp.arange(k)[:, None], assign].add(1)
    speeds = jnp.asarray(rng.uniform(0.05, 0.5, (k, n)), jnp.float32)
    loads = jnp.zeros((k, m), jnp.float32)
    loads = loads.at[jnp.arange(k)[:, None], assign].add(speeds)
    prev = jnp.asarray(rng.integers(-1, m, (k, n)), jnp.int32)
    lam = jnp.asarray(rng.uniform(0, 4, k), jnp.float32)
    cap = jnp.ones(k, jnp.float32)
    active = jnp.asarray(rng.integers(0, 2, (k, n)), jnp.int32)
    ref = move_delta_reference(loads, counts, assign, speeds, prev, lam, cap,
                               active=active)
    got = move_delta_batch(loads, counts, assign, speeds, prev, lam, cap,
                           active=active)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    dead = ~np.asarray(active).astype(bool)
    assert (np.asarray(got)[dead, :] == MOVE_BLOCKED).all()


# ---------------------------------------------------------------------------
# lag twin: masked partitions are unreadable and empty
# ---------------------------------------------------------------------------
def test_lagsim_dead_columns_equal_removed_columns():
    """Simulating [T, N + D] with D always-dead partitions equals
    simulating the live [T, N] columns alone -- the padding-exactness
    property the fleet layer is built on (deterministic policies)."""
    import dataclasses

    from repro.lagsim import LagSimConfig, simulate_lag

    rng = np.random.default_rng(4)
    live = jnp.asarray(rng.uniform(0, 0.8, (18, 5)), jnp.float32)
    dead = jnp.asarray(rng.uniform(0, 0.9, (18, 3)), jnp.float32)
    padded = jnp.concatenate([live, dead], axis=1)
    mask = jnp.concatenate([jnp.ones((18, 5), bool),
                            jnp.zeros((18, 3), bool)], axis=1)
    cfg = LagSimConfig(capacity=1.0, dt=1.0, migration_steps=2).resolve(5)
    for pol in ("BFD", "MBFP", "KEDA_LAG"):
        a = simulate_lag(live, policy=pol, cfg=cfg)
        b = simulate_lag(padded, policy=pol, cfg=cfg, active=mask)
        np.testing.assert_allclose(np.asarray(a.lag_total),
                                   np.asarray(b.lag_total), atol=1e-6,
                                   err_msg=pol)
        np.testing.assert_array_equal(np.asarray(a.consumers),
                                      np.asarray(b.consumers), err_msg=pol)
        np.testing.assert_array_equal(np.asarray(a.migrations),
                                      np.asarray(b.migrations), err_msg=pol)
