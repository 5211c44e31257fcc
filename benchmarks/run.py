"""Benchmark driver: one section per paper table/figure, printing
``name,us_per_call,derived`` CSV rows (us_per_call = evaluation wall time
where meaningful, else 0; derived = the quantity the paper reports).

  fig6_cbs_*          Cardinal Bin Score per algorithm/delta   (Fig. 6/7)
  fig8_rscore_*       Average Rscore per algorithm/delta       (Fig. 8)
  fig9_pareto_*       Pareto-front membership per delta        (Fig. 9)
  tab6_capacity_*     consumer max-throughput calibration      (Table VI/Fig. 10)
  packer_latency_*    reassignment-decision latency            (Sec. III premise)
  lagsim_*            closed-loop lag SLO sweep + speedup      (Sec. VI-D claim)
  controlplane_*      scaler friction: delay x cooldown grid   (Sec. V scalers)
  opt_*               optimality gaps + frontier hypervolume   (Sec. II model /
                                                               2024 follow-up)
  fleet_*             bucketed/sharded fleet throughput        (ROADMAP scaling)
  roofline_*          dry-run roofline aggregates              (EXPERIMENTS §Roofline)
  adversarial_*       worst-case SLO envelope per policy       (robustness gate)

Sections self-register: each benchmark module owns its rows via
``benchmarks.sections.section(name, prefixes=..., bench_json=...)`` and
this driver just imports the modules (registration order = output order)
and replays the registry -- a section's rows cannot silently drift from
the module that computes them, and a row outside its declared prefixes
is an error.  Policy/algorithm names inside every section resolve
through ``repro.registry``.

Run:  PYTHONPATH=src:. python benchmarks/run.py
"""
from __future__ import annotations

from benchmarks import sections
from repro import api

# importing a benchmark module registers its sections; this order is the
# output order
from benchmarks import paper_eval          # noqa: F401  fig6/fig8/fig9
from benchmarks import capacity_calibration  # noqa: F401  tab6
from benchmarks import packer_latency      # noqa: F401  packer_latency
from benchmarks import lag_slo             # noqa: F401  lagsim (BENCH_lagsim.json)
from benchmarks import controlplane_bench  # noqa: F401  controlplane (BENCH_controlplane.json)
from benchmarks import optimality_gap      # noqa: F401  opt (BENCH_opt.json)
from benchmarks import fleet_bench         # noqa: F401  fleet (BENCH_fleet.json)
from benchmarks import roofline            # noqa: F401  roofline
from benchmarks import adversarial_bench   # noqa: F401  adversarial (BENCH_adversarial.json)


def main() -> None:
    api.use_compile_cache()
    sections.emit_all()


if __name__ == "__main__":
    main()
