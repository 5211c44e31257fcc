"""Entry ``crash``: ``api.simulate`` of fleets of consumer groups whose
consumers die without leaving the group.

Node losses are drawn from the seed, per fleet, once at set-up: in each
group, from the mix's ``first_loss_step`` on, one of ``nodes`` nodes is
lost with probability ``loss_per_step`` a step, drawn uniformly, and a
loss crashes every consumer id on that node (id ``c`` lives on node
``c mod nodes``).  The program gets the schedule as ``crash``; the plain
reference below (``twin_with_crashes``) replays the same schedule in
NumPy, with the packers, drain and sums of ``bench/reference.py``, and
calls no program code.  Per step ``t`` of a group, in this order:

1. expel: each consumer id whose session timeout ends at ``t`` loses its
   partitions, which go unowned (``NEG``); the id is free again;
2. crash: each scheduled id that an active partition holds and that is
   alive dies for steps ``t .. t + S - 1`` (``S = orphan_steps``), and
   every partition assigned to it is orphaned for the same steps;
3. the policy's step, and migration downtime for moved partitions;
4. orphaned = inside an orphan window, or placed on a dead id; orphans
   are unread and count as unreadable;
5. the drain.
"""
import functools

import numpy as np

from bench import reference as ref
from bench.drivers import Simulate
from bench.harness import BenchError


def node_losses(seed: int, k: int, b: int, steps: int, n: int, nodes: int,
                first: int, p: float) -> np.ndarray:
    """``bool[b, steps, 2n + 2]``: the consumer ids crashed at each step of
    fleet ``k``'s ``b`` groups."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 int(k), 0xC4A5])
    lost = rng.random((b, steps)) < p
    lost[:, :first] = False
    node = rng.integers(nodes, size=(b, steps))
    ids = np.arange(2 * n + 2)
    return lost[:, :, None] & (ids[None, None, :] % nodes
                               == node[:, :, None])


def twin_with_crashes(rates, active, crash, policy, *, dt, capacity,
                      migration_steps, orphan_steps, ar):
    """Run one policy over groups ``rates f32[B, T, N]`` under the crash
    schedule ``crash bool[B, T, 2N + 2]``, from empty backlogs.  Returns
    per-step ``[B, T]`` trajectories: ``lag_total``, ``lag_max``,
    ``consumers``, ``migrations``, ``unreadable``, ``orphaned`` and
    ``crashes`` (the ids that died)."""
    b, steps, n = rates.shape
    dtype = ar.dtype
    lag = np.zeros((b, n), dtype)
    assign = np.full((b, n), ref.NEG, np.int64)
    down = np.zeros((b, n), np.int64)
    dead_until = np.full((b, 2 * n + 2), -1, np.int64)
    orphan_until = np.zeros((b, n), np.int64)
    policy.init(b)
    out = {k: [] for k in ("lag_total", "lag_max", "consumers", "migrations",
                           "unreadable", "orphaned", "crashes")}
    for t in range(steps):
        act = active[:, t]
        rate = rates[:, t].astype(np.float64).astype(dtype)
        produced = np.where(act, rate * np.asarray(dt, dtype),
                            np.asarray(0.0, dtype)).astype(dtype)
        observed = (lag + produced).astype(dtype)
        assign = assign.copy()
        died = np.zeros(b, np.int64)
        for g in range(b):
            for c in np.flatnonzero(dead_until[g] == t):          # 1. expel
                assign[g][assign[g] == c] = ref.NEG
            held = set(assign[g][act[g]].tolist())
            for c in np.flatnonzero(crash[g, t]):                 # 2. crash
                if c in held and dead_until[g, c] <= t:
                    dead_until[g, c] = t + orphan_steps
                    orphan_until[g][assign[g] == c] = t + orphan_steps
                    died[g] += 1
        new, n_cons, storm = policy.step(t, rate, observed, assign, act)
        moved = (assign >= 0) & (new >= 0) & (new != assign)      # 3.
        down = np.where(moved, migration_steps, np.maximum(down - 1, 0))
        readable = (down == 0) & (new >= 0)
        blocked = down > 0
        if storm is not None:
            readable &= ~storm
            blocked |= storm & (new >= 0)
        on_dead = np.array([[c >= 0 and dead_until[g, c] > t          # 4.
                             for c in new[g]] for g in range(b)])
        orphaned = (orphan_until > t) | on_dead
        readable &= ~orphaned
        blocked |= orphaned
        lag = ref.drain(lag, produced, new, readable, act,                # 5.
                        capacity * dt, dtype)
        assign = new
        out["lag_total"].append(ref.rowsum(lag, dtype))
        out["lag_max"].append(np.max(lag, axis=1))
        out["consumers"].append(n_cons)
        out["migrations"].append(moved.sum(axis=1))
        out["unreadable"].append((blocked & act).sum(axis=1))
        out["orphaned"].append((orphaned & act).sum(axis=1))
        out["crashes"].append(died)
    return {k: np.stack(v, axis=1).astype(
        np.float64 if k.startswith("lag") else np.int64)
        for k, v in out.items()}


class NodeLossSimulate(Simulate):
    """``Simulate`` with node losses passed as ``crash``, the
    ``orphaned`` field compared exactly, and the reference above."""

    FIELDS = Simulate.FIELDS + ("orphaned",)

    @classmethod
    def small(cls, config, mix):
        """The CPU size keeps failures in every checked group: 40 steps,
        the first loss possible at step 5 and a loss rate of a quarter;
        ``reference_run`` raises where a checked group lost no consumer
        that somebody held."""
        config, mix = super().small(config, mix)
        return config, {**mix, "steps": 40, "first_loss_step": 5,
                        "loss_per_step": 0.25, "require_losses": True}

    @functools.cached_property
    def crashes(self):
        """One schedule per fleet, on the device like the fleets; made at
        set-up, by the warm call's ``call_kwargs``."""
        import jax.numpy as jnp

        return [jnp.asarray(node_losses(
            self.seed, k, self.b, self.steps, self.n, int(self.mix["nodes"]),
            int(self.mix["first_loss_step"]),
            float(self.mix["loss_per_step"])))
            for k in range(int(self.mix.get("fleets", 1)))]

    def call_kwargs(self, k):
        return {"crash": self.crashes[k]}

    def reference_run(self, k, groups, policy, arith, **twin):
        tw = {**self.config["twin"], **twin}
        sp, ac = self.fleets[k]
        out = twin_with_crashes(
            np.asarray(sp)[groups], np.asarray(ac)[groups],
            np.asarray(self.crashes[k])[groups], policy, dt=float(tw["dt"]),
            capacity=float(tw["capacity"]),
            migration_steps=int(tw["migration_steps"]),
            orphan_steps=int(tw["orphan_steps"]), ar=arith)
        lost = out["crashes"].sum(axis=1)
        if self.mix.get("require_losses") and not np.all(lost > 0):
            raise BenchError(f"fleet {k}: of checked groups {groups}, some "
                             f"lost no held consumer ({lost.tolist()})")
        return out

    def extra_numbers(self, pairs):
        return {"orphaned_off": sum(
            int(np.sum(g["orphaned"] != w["orphaned"]))
            for _, _, g, w in pairs)}


DRIVER = NodeLossSimulate
