"""The drivers of the built-in entries, and the bases that new entries
extend.  A traffic mix's ``entry`` names a file ``bench/entries/<entry>.py``
whose ``DRIVER`` is one of these classes or a subclass:

* ``simulate``: the evaluator's fleet run.  ``repro.api.simulate`` of the
  mix's policies over fleets of groups made from the seed, back to back
  for the window.  ``eval_rate`` is the partition-steps of every call
  completed, over the time those calls took.
* ``pack``: the scaler's served decision.  ``repro.api.pack`` of one
  group's next step, closed loop, with ``prev`` the group's previous
  reply; the mix's groups are served in turn.  ``decide_ms`` is each
  call's latency on the caller's side, outputs on the host.

After the window each driver compares a sample of what the timed calls
produced, drawn from the seed, with ``bench/reference.py`` and returns
the numbers compared.  ``arith`` other than float64 puts the reference in
the program's place, computed in that precision: the control.

``Simulate`` has a seam wherever a configuration's semantics may differ,
each a short method or class attribute that a subclass overrides:
``call_kwargs`` (program inputs beyond the fleet), ``FIELDS`` (the
result fields compared), ``reference_policy`` (the reference of a policy
name), ``reference_run`` (the reference over the checked groups; its
keywords override the configuration's twin parameters) and
``extra_numbers`` (numbers compared beyond the default ones).  Each
driver's ``small`` gives its cells the size of the CPU tests in
``bench/tests``, which a subclass inherits.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import reference as ref
from bench.traffic import families

ANNOTATION = "bench.call"


def _rng(seed: int):
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32])


def _gap(got: np.ndarray, want: np.ndarray) -> float:
    """Largest ``|got - want|`` along the last axis over ``want``'s largest
    magnitude there (0 where both are all zero)."""
    scale = np.max(np.abs(want), axis=-1)
    diff = np.max(np.abs(got - want), axis=-1)
    safe = np.where(scale > 0, scale, 1.0)
    return float(np.max(np.where(scale > 0, diff / safe, diff)))


class Simulate:
    """``api.simulate`` of fleets of consumer groups, on ``devices`` (by
    default every device JAX sees); ``FleetRunner`` shards the groups
    over them."""

    FIELDS = ("lag_total", "lag_max", "consumers", "migrations",
              "unreadable")
    """The per-step result fields kept from each call and compared."""

    @classmethod
    def small(cls, config: dict, mix: dict) -> Tuple[dict, dict]:
        """The cell at the size of the CPU tests: one group a family, 16
        steps, two fleets, at most 24 partitions."""
        n = min(int(config["partitionsPerTopic"]), 24)
        return ({**config, "partitionsPerTopic": n},
                {**mix, "groups_per_family": 1, "steps": 16, "fleets": 2,
                 "check_per_family": 1})

    def __init__(self, config: dict, mix: dict, seed: int, devices=None):
        self.config, self.mix, self.seed = config, mix, int(seed)
        self.devices = None if devices is None else tuple(devices)
        self.n = int(config["partitionsPerTopic"])
        self.steps = int(mix["steps"])
        self.policies = tuple(mix["policies"])
        self.fams = tuple(mix["families"])
        self.per_family = int(mix["groups_per_family"])
        self.b = self.per_family * len(self.fams)
        self.outputs: List[Dict[str, np.ndarray]] = []   # kept groups only
        self.calls = 0

    # -- set-up ---------------------------------------------------------------

    def lag_config(self):
        from repro import api
        from repro.lagsim import LagSimConfig
        from repro.telemetry import default_rules

        over = dict(self.config["twin"])
        if self.mix.get("telemetry", True):
            over["telemetry"] = api.TelemetryConfig(
                record_frames=False, sketch=api.SketchConfig(),
                alerts=api.AlertConfig(rules=default_rules()))
        cp = self.config.get("control_plane")
        if cp is not None:
            over["control_plane"] = api.ControlPlaneConfig(**cp)
        return LagSimConfig(**over)

    def setup(self) -> None:
        import jax

        from repro import api
        from repro.fleet import FleetConfig, FleetRunner

        class KeepingRunner(FleetRunner):
            """The default runner; it also keeps its last raw result, whose
            per-step ``lag_max`` and ``unreadable`` ``api.simulate`` drops."""

            def simulate(self, *a, **kw):
                self.last = super().simulate(*a, **kw)
                return self.last

        self.api = api
        self.runner = KeepingRunner(FleetConfig(devices=self.devices))
        self.cfg = self.lag_config()
        n_fleets = int(self.mix.get("fleets", 1))
        self.fleets = []
        for k in range(n_fleets):
            _, sp, ac = families.fleet(
                self.seed * n_fleets + k, self.fams, self.per_family,
                self.steps, self.n, int(self.config["round_to"]))
            self.fleets.append((sp, ac))
        jax.block_until_ready(self.fleets)
        # the groups to check: one per family and fleet slot, from the seed
        rng = _rng(self.seed)
        per = int(self.mix["check_per_family"])
        self.checked = sorted(
            (int(rng.integers(n_fleets)),
             f * self.per_family + int(g))
            for f in range(len(self.fams))
            for g in rng.choice(self.per_family, per, replace=False))
        self.call(0)                    # warm: compile or load the program
        self.outputs.clear()
        self.calls = 0

    def call_kwargs(self, k: int) -> dict:
        """Keyword arguments that a call passes to ``api.simulate`` for
        fleet ``k``, beyond the fleet, its mask, the policies and the
        config: none by default."""
        return {}

    def call(self, i: int) -> None:
        k = i % len(self.fleets)
        sp, ac = self.fleets[k]
        self.api.simulate(sp, policies=self.policies, active=ac,
                          config=self.cfg, fleet=self.runner,
                          **self.call_kwargs(k))
        res = self.runner.last
        keep = [g for kk, g in self.checked if kk == k]
        if keep:
            self.outputs.append({"fleet": k, **{
                f: np.stack([getattr(res, f)[g] for g in keep], axis=1)
                for f in self.FIELDS}})
        self.calls += 1

    def work_per_call(self) -> int:
        return len(self.policies) * self.b * self.steps * self.n

    # -- the window -------------------------------------------------------------

    def window(self, seconds: float, tracer) -> Dict[str, float]:
        import jax

        t0 = time.perf_counter()
        i = 0
        while True:
            tracer.before(i)
            with jax.profiler.TraceAnnotation(ANNOTATION):
                self.call(i)
            i += 1
            tracer.after(i)
            if time.perf_counter() - t0 >= seconds:
                break
        took = time.perf_counter() - t0
        return {"eval_rate": self.calls * self.work_per_call() / took,
                "window_s": took}

    def attempted(self) -> int:
        return self.calls

    # -- the comparison ---------------------------------------------------------

    def reference_policy(self, name: str, arith: ref.Arith):
        """The reference object of policy ``name``, for ``reference_run``."""
        tw = self.config["twin"]
        cp = self.config.get("control_plane")
        if name in ref.PACKERS:
            return ref.PackerPolicy(name, float(tw["capacity"]), arith)
        if name == "KEDA_LAG_REAL":
            return ref.KedaLag(
                n=self.n, lag_threshold=float(tw["lag_threshold"]),
                patience=int(tw.get("scale_down_patience", 3)),
                poll=cp["polling_interval"],
                obs_delay=cp["observation_delay"],
                act_delay=cp["actuation_delay"],
                cooldown=cp["cooldown_period"],
                min_replicas=cp["min_replicas"],
                max_replicas=cp["max_replicas"],
                warmup=cp["warmup_steps"], ar=arith)
        raise ValueError(f"no reference for policy {name!r}")

    def reference_run(self, k: int, groups: List[int], policy,
                      arith: ref.Arith, **twin) -> Dict[str, np.ndarray]:
        """The reference of ``policy`` over fleet ``k``'s ``groups``, with
        the configuration's twin parameters as ``twin`` overrides them:
        per step ``[B, T]`` trajectories of at least ``FIELDS``."""
        tw = {**self.config["twin"], **twin}
        sp, ac = self.fleets[k]
        return ref.twin(np.asarray(sp)[groups], np.asarray(ac)[groups],
                        policy, dt=float(tw["dt"]),
                        capacity=float(tw["capacity"]),
                        migration_steps=int(tw["migration_steps"]), ar=arith)

    def _reference(self, arith: ref.Arith) -> Dict[tuple, Dict]:
        out = {}
        for k in sorted({kk for kk, _ in self.checked}):
            groups = [g for kk, g in self.checked if kk == k]
            for p in self.policies:
                out[(k, p)] = self.reference_run(
                    k, groups, self.reference_policy(p, arith), arith)
        return out

    def extra_numbers(self, pairs: List[tuple]) -> Dict[str, float]:
        """Numbers compared beyond the default ones, from ``pairs`` of
        ``(fleet, policy, got, want)`` (``FIELDS`` to ``[B, T]`` arrays),
        one pair for each call's checked groups and policy; each number is
        lower-is-better and has a limit in the mix.  None by default."""
        return {}

    def numbers(self, control: Optional[ref.Arith] = None
                ) -> Tuple[Dict[str, float], int]:
        """``(numbers compared, calls whose checked decisions disagree)``;
        every number is lower-is-better.  With ``control`` the program's
        outputs are replaced by the reference in that precision."""
        want = self._reference(ref.PRECISIONS["float64"])
        got_ref = None if control is None else self._reference(control)
        off = {"decisions_off": 0, "unreadable_off": 0}
        gaps = {"lag_gap": 0.0, "lag_max_gap": 0.0}
        clamp = bad = 0
        cp = self.config.get("control_plane")
        pairs = []
        for out in self.outputs:
            k = out["fleet"]
            wrong = off["decisions_off"] + off["unreadable_off"]
            for pi, p in enumerate(self.policies):
                w = want[(k, p)]
                if got_ref is not None:
                    g = got_ref[(k, p)]
                else:
                    g = {f: out[f][pi] for f in self.FIELDS}
                pairs.append((k, p, g, w))
                off["decisions_off"] += int(np.sum(
                    (g["consumers"] != w["consumers"])
                    | (g["migrations"] != w["migrations"])))
                off["unreadable_off"] += int(np.sum(
                    g["unreadable"] != w["unreadable"]))
                gaps["lag_gap"] = max(gaps["lag_gap"],
                                      _gap(g["lag_total"], w["lag_total"]))
                gaps["lag_max_gap"] = max(gaps["lag_max_gap"],
                                          _gap(g["lag_max"], w["lag_max"]))
                if cp is not None:
                    c = g["consumers"][:, cp["actuation_delay"]:]
                    clamp += int(np.sum((c < cp["min_replicas"])
                                        | (c > cp["max_replicas"])))
            bad += int(off["decisions_off"] + off["unreadable_off"] > wrong)
        nums = {**off, **gaps, **self.extra_numbers(pairs)}
        if cp is not None:
            nums["clamp_off"] = clamp
        return nums, bad


class Pack:
    """``api.pack`` decisions for groups served in turn, closed loop, on
    JAX's default device: the first of a cell's ``devices``."""

    @classmethod
    def small(cls, config: dict, mix: dict) -> Tuple[dict, dict]:
        """The cell at the size of the CPU tests: 40 steps, 80 decisions
        checked."""
        return config, {**mix, "steps": 40, "check_decisions": 80}

    def __init__(self, config: dict, mix: dict, seed: int, devices=None):
        self.config, self.mix, self.seed = config, mix, int(seed)
        self.n = int(config["partitionsPerTopic"])
        self.algorithm = mix["algorithm"]
        self.cap = float(config["twin"]["capacity"])
        self.records: List[tuple] = []   # (group, step, prev, assign, bins, r)
        self.latencies: List[float] = []

    def setup(self) -> None:
        import jax

        from repro import api

        self.api = api
        _, sp, _ = families.fleet(self.seed, tuple(self.mix["families"]), 1,
                                  int(self.mix["steps"]), self.n,
                                  int(self.config["round_to"]))
        self.speeds = np.asarray(jax.device_get(sp))       # [G, T, N]
        g = self.speeds.shape[0]
        self.prev = [np.full(self.n, ref.NEG, np.int32) for _ in range(g)]
        self.decide(0)                   # warm: no previous reply
        self.decide(g)                   # warm: with one
        self.records.clear()
        self.latencies.clear()
        self.prev = [np.full(self.n, ref.NEG, np.int32) for _ in range(g)]

    def decide(self, i: int) -> None:
        g_count, steps = self.speeds.shape[:2]
        g, t = i % g_count, (i // g_count) % steps
        row, prev = self.speeds[g, t], self.prev[g]
        t0 = time.perf_counter()
        out = self.api.pack(row, self.cap, algorithm=self.algorithm,
                            prev=prev, backend="jax")
        self.latencies.append(time.perf_counter() - t0)
        assign = np.fromiter((out.assignment[j] for j in range(self.n)),
                             np.int32, self.n)
        self.records.append((g, t, prev, assign, out.n_bins, out.rscore))
        self.prev[g] = assign

    def window(self, seconds: float, tracer) -> Dict[str, float]:
        import jax

        t0 = time.perf_counter()
        i = 0
        while True:
            tracer.before(i)
            with jax.profiler.TraceAnnotation(ANNOTATION):
                self.decide(i)
            i += 1
            tracer.after(i)
            if time.perf_counter() - t0 >= seconds:
                break
        lat = np.asarray(self.latencies) * 1e3
        return {"decide_ms.p50": float(np.percentile(lat, 50)),
                "decide_ms.p99": float(np.percentile(lat, 99)),
                "window_s": time.perf_counter() - t0}

    def attempted(self) -> int:
        return len(self.records)

    def numbers(self, control: Optional[ref.Arith] = None
                ) -> Tuple[Dict[str, float], int]:
        """Assignment, bin count and R-score of a seeded sample of the
        window's decisions against the reference, plus the packing
        guarantee on each sampled reply: ``(numbers, decisions wrong)``."""
        packer = ref.PACKERS[self.algorithm]
        want_ar = ref.PRECISIONS["float64"]
        k = min(int(self.mix["check_decisions"]), len(self.records))
        pick = np.sort(_rng(self.seed).choice(len(self.records), k,
                                              replace=False))
        nums = {"assign_off": 0, "bins_off": 0, "rscore_off": 0,
                "capacity_off": 0}
        bad = 0
        live = [True] * self.n
        for i in pick:
            g, t, prev, assign, bins, r = self.records[i]
            w = [float(x) for x in self.speeds[g, t]]
            pv = prev.tolist()
            want, want_bins = packer(w, live, pv, self.cap)
            want_r = ref.rscore(pv, want, w, self.cap)
            if control is not None:
                wr = [control.r(x) for x in w]
                got, bins = packer(wr, live, pv, self.cap, control.r)
                r = ref.rscore(pv, got, wr, self.cap, control.r)
                assign = np.asarray(got)
            before = sum(nums.values())
            nums["assign_off"] += int(not np.array_equal(assign, want))
            nums["bins_off"] += int(bins != want_bins)
            nums["rscore_off"] += int(r != want_r)
            loads: Dict[int, List[float]] = {}
            for j, c in enumerate(assign.tolist()):
                loads.setdefault(c, []).append(w[j])
            nums["capacity_off"] += int(
                any(c < 0 for c in loads)
                or any(len(v) > 1 and sum(v) > self.cap
                       for v in loads.values()))
            bad += int(sum(nums.values()) > before)
        return nums, bad

