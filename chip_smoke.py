"""Smoke run of the autoscaler's main path on one TPU chip.

Drives ``repro.api`` -> ``FleetRunner`` -> the lag engine, Pallas kernels
included, at a deployment's size, and checks every result against a plain
reference.  The fleet is one OpenMessaging-Benchmark-sized topic (N = 100
partitions) over T = 1200 steps (one hour at KEDA's ``pollingInterval:
3``) for B = 64 consumer groups drawn from every scenario family of
``repro.core.scenarios``, masked ``topic_lifecycle`` included, all made
from ``--seed``.  Rates are rounded to 1/1024 of a consumer's capacity so
every load sum is exact in float32 and the float64 reference packers must
agree decision for decision.

Phases (one chip, no arguments):

  a. ``api.sweep`` of all 12 packers; bins and migrations equal
     ``repro.core.metrics.run_stream`` over the ``py`` packers on two
     groups' first 200 steps, R-score to 1e-5 relative.
  b. ``api.simulate`` of BFD, MBFP, KEDA_LAG_REAL and ANNEAL_STICKY with
     sketches and the default alert rules on.  Heuristic and sticky
     decisions (consumers, migrations, one group's assignments) equal
     the same run on the host CPU; lag agrees to the contract of
     ``repro.lagsim.metrics.agrees`` (floats within ``FLOAT_RTOL`` of
     their scale); KEDA_LAG_REAL stays in its replica clamp;
     ANNEAL_STICKY is held to packing invariants.
  c. The Pallas kernels compiled for the chip (``tpu_custom_call`` in the
     executable) against their jnp oracles at N = 100, and the engine's
     kernel and fused paths at N = 14 (``FUSED_MAX_PARTITIONS``) against
     the default scan.

``--chips 4`` runs only the sharded fleet: ``FleetRunner`` over four
devices against one device on the same fleet.

Every check raises on failure.  The last line of standard output is a
JSON object naming the device.  Timings printed before it are wall
seconds of single calls, the first (compiling) call of each verb and
the host-side references.

Run:  python chip_smoke.py [--seed 0] [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

B, T, N = 64, 1200, 100        # consumer groups, steps, partitions
N_FUSED = 14                   # FUSED_MAX_PARTITIONS
DT = 3.0                       # seconds per step: KEDA pollingInterval
REF_GROUPS, REF_STEPS = 2, 200  # phase (a)'s python reference slice
SIM_POLICIES = ("BFD", "MBFP", "KEDA_LAG_REAL", "ANNEAL_STICKY")
CPU_CHECKED = ("BFD", "MBFP")   # decisions independent of float lag
FUSED_POLICIES = ("NF", "FFD", "BFD", "WF")
FIELDS = ("lag_total", "consumers", "migrations")


def check(ok, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {what}")


def log(msg: str) -> None:
    print(msg, flush=True)


def timed(fn, *args, **kwargs):
    """``(result, seconds)`` of one call, its outputs ready."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kwargs))
    return out, time.perf_counter() - t0


def peak_bytes(device) -> int:
    return (device.memory_stats() or {}).get("peak_bytes_in_use", -1)


def make_fleet(seed: int, b: int, t: int, n: int):
    """``(labels, speeds f32[b, t, n], active bool[b, t, n])``: an equal
    share of groups from every registered scenario family."""
    import jax.numpy as jnp
    import jax.random as jr

    from repro.core.scenarios import (MASKED_SCENARIO_FAMILIES,
                                      masked_scenario_suite,
                                      stack_masked_suite)

    fams = tuple(MASKED_SCENARIO_FAMILIES)
    check(b % len(fams) == 0, f"B={b} splits evenly over {len(fams)} families")
    suite = masked_scenario_suite(jr.key(seed), b // len(fams), t, n,
                                  families=fams)
    labels, speeds, active = stack_masked_suite(suite)
    return labels, jnp.round(speeds * 1024.0) / 1024.0, active


def sim_config(**over):
    """The fleet's lag-twin config: one step per KEDA poll, sketches and
    the default alert rules on (``over`` replaces any field)."""
    from repro import api
    from repro.lagsim import LagSimConfig
    from repro.telemetry import default_rules

    tele = api.TelemetryConfig(record_frames=False,
                               sketch=api.SketchConfig(),
                               alerts=api.AlertConfig(rules=default_rules()))
    return LagSimConfig(**{"dt": DT, "telemetry": tele, **over})


def compiled_run(fn, *args):
    """Compile ``fn`` for the default device, check that a Pallas kernel
    made it into the executable, and run it."""
    import jax

    exe = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in exe.as_text(),
          f"{getattr(fn, '__name__', fn)}: no tpu_custom_call in executable")
    return jax.block_until_ready(exe(*args))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_sweep(labels, speeds, active) -> None:
    import numpy as np

    from repro import api
    from repro.core.metrics import run_stream

    algos = api.list_policies(family=api.PACKER_FAMILIES, backend="jax")
    check(len(algos) == 12, f"12 packers registered, got {algos}")
    out, first = timed(api.sweep, speeds, 1.0, algorithms=algos,
                       active=active)
    t0 = time.perf_counter()
    groups = [labels.index("topic_lifecycle"), labels.index("bursty")]
    sp, ac = np.asarray(speeds), np.asarray(active)
    for g in groups[:REF_GROUPS]:
        ref = run_stream({a: api.packer_for(a, backend="py") for a in algos},
                         sp[g, :REF_STEPS], 1.0, active=ac[g, :REF_STEPS])
        for i, a in enumerate(algos):
            where = f"sweep {a} group {g} ({labels[g]})"
            check(np.array_equal(out.bins[i, g, :REF_STEPS], ref[a].bins),
                  f"{where}: bins != python reference")
            check(np.array_equal(out.migrations[i, g, :REF_STEPS],
                                 ref[a].migrations),
                  f"{where}: migrations != python reference")
            check(np.allclose(out.rscores[i, g, :REF_STEPS], ref[a].rscores,
                              rtol=1e-5, atol=0.0),
                  f"{where}: R-score beyond 1e-5 relative")
    log(f"phase a sweep: 12 packers x {speeds.shape} ok; first call "
        f"{first:.2f} s wall, python reference "
        f"{time.perf_counter() - t0:.2f} s wall")


def phase_simulate(speeds, active) -> None:
    import jax
    import numpy as np

    from repro import api
    from repro.lagsim import simulate_lag
    from repro.lagsim.metrics import FLOAT_RTOL, agrees

    n = speeds.shape[2]
    cfg = sim_config()
    out, first = timed(api.simulate, speeds, policies=SIM_POLICIES,
                       active=active, config=cfg)
    lag = out.lag_total
    check(np.all(np.isfinite(lag)) and np.all(lag >= 0),
          "lag_total finite and >= 0 for every policy")

    # decisions vs the same program on the host CPU
    cpu = jax.devices("cpu")[0]
    t0 = time.perf_counter()
    with jax.default_device(cpu):
        ref = api.simulate(jax.device_put(speeds, cpu),
                           policies=CPU_CHECKED,
                           active=jax.device_put(active, cpu), config=cfg,
                           fleet=api.FleetRunner(
                               api.FleetConfig(devices=(cpu,))))
    on_cpu = time.perf_counter() - t0
    for i, pol in enumerate(CPU_CHECKED):
        j = SIM_POLICIES.index(pol)
        for f in FIELDS:
            check(agrees(getattr(out, f)[j], getattr(ref, f)[i]),
                  f"simulate {pol}: {f} disagrees with the CPU run")

    sp, ac = np.asarray(speeds), np.asarray(active)
    # the first decision actuates one step late (actuation_delay = 1)
    keda = out.consumers[SIM_POLICIES.index("KEDA_LAG_REAL")][:, 1:]
    check(keda.min() >= 1 and keda.max() <= n,
          f"KEDA_LAG_REAL consumers within [1, {n}]: "
          f"[{keda.min()}, {keda.max()}]")
    ann = out.consumers[SIM_POLICIES.index("ANNEAL_STICKY")]
    check(np.all(ann >= bin_bound(np.where(ac, sp, 0.0))),
          "ANNEAL_STICKY bins below the packing lower bound")

    # one group's per-step assignments: CPU equality and packing validity
    g = 0
    plain = sim_config(telemetry=None)
    for pol in CPU_CHECKED:
        _, asg = simulate_lag(speeds[g], policy=pol, cfg=plain,
                              active=active[g], record_assign=True)
        with jax.default_device(cpu):
            _, asg_cpu = simulate_lag(
                jax.device_put(speeds[g], cpu), policy=pol, cfg=plain,
                active=jax.device_put(active[g], cpu), record_assign=True)
        check(np.array_equal(asg, asg_cpu),
              f"{pol}: assignments of group {g} differ from the CPU run")
    tr, asg = simulate_lag(speeds[g], policy="ANNEAL_STICKY", cfg=plain,
                           active=active[g], record_assign=True)
    check_packing(sp[g], ac[g], np.asarray(asg), np.asarray(tr.consumers))

    incidents = sum(len(x) for x in out.incidents)
    log(f"phase b simulate: {SIM_POLICIES} x {speeds.shape} ok (float "
        f"fields within {FLOAT_RTOL} of scale, {incidents} incidents); "
        f"first call {first:.2f} s wall, CPU reference {on_cpu:.2f} s wall")


def bin_bound(speeds):
    """Least bins any packing of ``speeds [..., N]`` into capacity 1.0
    uses: an oversized partition sits alone, the rest need at least
    ceil(sum w / C) bins."""
    import numpy as np

    big = speeds > 1.0
    return big.sum(-1) + np.ceil(np.where(big, 0.0, speeds).sum(-1))


def check_packing(speeds, active, assign, consumers) -> None:
    """Every step's assignment is a packing: no bin over capacity 1.0
    except an oversized partition alone, the bin count is what the
    policy billed, and it is at least ``bin_bound``."""
    import numpy as np

    for t in range(speeds.shape[0]):
        live = active[t]
        names, inv = np.unique(assign[t][live], return_inverse=True)
        check(np.all(assign[t][~live] == -1),
              f"ANNEAL_STICKY step {t}: inactive partition assigned")
        loads = np.bincount(inv, weights=speeds[t][live],
                            minlength=len(names))
        sizes = np.bincount(inv, minlength=len(names))
        check(np.all((loads <= 1.0) | (sizes == 1)),
              f"ANNEAL_STICKY step {t}: a shared bin is over capacity")
        check(len(names) == consumers[t],
              f"ANNEAL_STICKY step {t}: {len(names)} bins, billed "
              f"{consumers[t]}")
        check(len(names) >= bin_bound(speeds[t][live]),
              f"ANNEAL_STICKY step {t}: fewer bins than the lower bound")


def phase_kernels(speeds, active, seed: int) -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import jax.random as jr
    import numpy as np

    from repro.kernels.binpack_select import NEG, select_slot_grid
    from repro.kernels.lag_update import (lag_update_batch,
                                          lag_update_reference,
                                          lag_update_single)
    from repro.kernels.move_eval import (MOVE_BLOCKED, move_delta_batch,
                                         move_delta_reference)
    from repro.kernels.ref import select_slot_ref
    from repro.lagsim import sweep_lag
    from repro.lagsim.metrics import agrees

    b, _, n = speeds.shape
    keys = jr.split(jr.key(seed + 1), 8)
    act = active[:, -1].astype(jnp.int32)                # (B, N)

    # fit selection, three strategies, with the partition mask
    m = n + 1
    loads = jr.uniform(keys[0], (b, n, m))
    w = speeds[:, -1]
    k = jr.randint(keys[1], (b, n), 0, m + 1)
    cap = jnp.ones((b, n), jnp.float32)
    for strategy in ("first", "best", "worst"):
        got = compiled_run(
            lambda l, w_, k_, c, a, s=strategy: select_slot_grid(
                l, w_, k_, c, active=a, strategy=s, interpret=False),
            loads, w, k, cap, act)
        want = jax.vmap(lambda l, w_, k_, c, s=strategy: select_slot_ref(
            l, w_, k_, c, strategy=s))(loads, w, k, cap)
        want = jnp.where(act > 0, want, NEG)
        check(np.array_equal(got, want),
              f"select_slot_grid[{strategy}] != select_slot_ref")

    # lag update, batch and single entries, name universe 2N + 2
    m = 2 * n + 2
    lag = jr.uniform(keys[2], (b, n), maxval=5.0)
    assign = jr.randint(keys[3], (b, n), -1, m)
    readable = jr.bernoulli(keys[4], 0.8, (b, n)).astype(jnp.int32)
    budget = jr.uniform(keys[5], (b, m), minval=0.5, maxval=1.5)
    got = compiled_run(
        lambda *a: lag_update_batch(*a[:5], active=a[5], interpret=False),
        lag, w, assign, readable, budget, act)
    want = lag_update_reference(lag, w, assign, readable, budget, m=m,
                                active=act)
    check(agrees(got, want), "lag_update_batch vs lag_update_reference")
    got = compiled_run(
        lambda *a: lag_update_single(*a, interpret=False),
        lag[0], w[0], assign[0], readable[0], budget[0])
    want = lag_update_reference(lag[0], w[0], assign[0], readable[0],
                                budget[0], m=m)
    check(agrees(got, want), "lag_update_single vs lag_update_reference")

    # annealer move deltas over 64 chains
    onehot = jax.nn.one_hot(jnp.clip(assign, 0), m) * act[..., None]
    counts = onehot.sum(1).astype(jnp.int32)
    bin_loads = (onehot * w[..., None]).sum(1)
    lam = jnp.linspace(0.0, 8.0, b, dtype=jnp.float32)
    ones = jnp.ones((b,), jnp.float32)
    prev = jr.randint(keys[6], (b, n), -1, m)
    chains = (bin_loads, counts, jnp.clip(assign, 0), w, prev, lam, ones)
    got = compiled_run(
        lambda *a: move_delta_batch(*a[:7], active=a[7], interpret=False),
        *chains, act)
    want = move_delta_reference(*chains, active=act)
    check(np.array_equal(got >= MOVE_BLOCKED / 2, want >= MOVE_BLOCKED / 2),
          "move_delta_batch blocked-move mask != move_delta_reference")
    check(agrees(got, want), "move_delta_batch vs move_delta_reference")
    log(f"phase c kernels: select_slot_grid x3, lag_update_batch/single, "
        f"move_delta_batch compiled with tpu_custom_call and agree with "
        f"their oracles at N={n}")

    # the engine's kernel paths at the fused partition limit
    tr, ac = speeds[:, :, :N_FUSED], active[:, :, :N_FUSED]
    base = sim_config(telemetry=None)
    ref = sweep_lag(FUSED_POLICIES, tr, base, active=ac)
    for label, over, kernel in (
            ("use_kernel", dict(use_kernel=True), True),
            ("fused_steps=8", dict(fused_steps=8), False),
            ("fused_steps=8 fused_kernel", dict(fused_steps=8,
                                                fused_kernel=True), True)):
        cfg = dataclasses.replace(base, **over)
        run = lambda t_, a_, cfg=cfg: sweep_lag(FUSED_POLICIES, t_, cfg,
                                                active=a_)
        got = compiled_run(run, tr, ac) if kernel else run(tr, ac)
        for f in ("lag_total", "lag_max", "consumers", "migrations",
                  "unreadable"):
            check(agrees(getattr(got, f), getattr(ref, f)),
                  f"engine {label}: {f} disagrees with the default scan")
        log(f"phase c engine {label}: {FUSED_POLICIES} x {tr.shape} agree "
            f"with the default scan")


def phase_four_chips(speeds, active) -> None:
    import jax
    import numpy as np

    from repro import api
    from repro.lagsim.metrics import agrees

    devs = tuple(jax.devices())
    check(len(devs) == 4, f"--chips 4 needs four devices, found {len(devs)}")
    cfg = sim_config()
    pols = CPU_CHECKED
    multi = api.FleetRunner(api.FleetConfig(devices=devs))
    placed, _ = multi._device_put(speeds, active)      # the runner's layout
    homes = {s.device for s in placed.addressable_shards}
    check(len(homes) == 4, f"input shards on {len(homes)} devices, not 4")
    four, first = timed(api.simulate, speeds, policies=pols, active=active,
                        config=cfg, fleet=multi)
    one = api.simulate(speeds, policies=pols, active=active, config=cfg,
                       fleet=api.FleetRunner(api.FleetConfig(
                           devices=devs[:1])))
    for f in FIELDS:
        check(agrees(getattr(four, f), getattr(one, f)),
              f"4-device {f} disagrees with 1 device")
    for k in four.metrics:
        check(np.array_equal(np.isfinite(four.metrics[k]),
                             np.isfinite(one.metrics[k])),
              f"metric {k}: finiteness differs")
    log(f"phase four chips: {pols} x {speeds.shape} sharded over "
        f"{len(homes)} devices agrees with one device; first call "
        f"{first:.2f} s wall")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro import api

    log(f"compile cache: {api.use_compile_cache()}")
    labels, speeds, active = make_fleet(args.seed, B, T, N)
    if args.chips == 4:
        phase_four_chips(speeds, active)
    else:
        for name, phase, a in (
                ("a", phase_sweep, (labels, speeds, active)),
                ("b", phase_simulate, (speeds, active)),
                ("c", phase_kernels, (speeds, active, args.seed))):
            phase(*a)
            log(f"phase {name}: peak_bytes_in_use {peak_bytes(dev)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
