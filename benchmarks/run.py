# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark entry point:  PYTHONPATH=src python -m benchmarks.run

Sections:
  kernels / simulator  — microbenchmarks (name, us_per_call, derived)
  paper figures        — quick-budget scheduler comparison per topology
                         (fig6 small/medium/large, fig8 log, fig10 wc)
  roofline             — summary from dry-run artifacts when present

Full-budget paper validation lives in the individual
``benchmarks.paper_*`` modules (--paper-budget)."""
from __future__ import annotations

import argparse
import sys

from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-paper", action="store_true",
                    help="only micro-benchmarks (fast)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    print("name,us_per_call,derived")

    from benchmarks.kernel_bench import run_all
    for name, us, derived in run_all():
        print(f"{name},{us:.1f},{derived}", flush=True)

    if not args.skip_paper:
        # minutes, not micro: trains a 32-lane fleet — skip on the fast path
        from benchmarks.fleet_bench import run_all as fleet_run_all
        for name, us, derived in fleet_run_all(fleet=32, epochs=300):
            print(f"{name},{us:.1f},{derived}", flush=True)

    if not args.skip_paper:
        from benchmarks.paper_common import Budget, compare_all
        budget = Budget.quick()
        for app, fig in [("cq_small", "fig6a"), ("cq_medium", "fig6b"),
                         ("cq_large", "fig6c"), ("log_stream", "fig8"),
                         ("word_count", "fig10")]:
            out = compare_all(app, budget, args.seed, verbose=False)
            print(f"paper_{fig}_{app}_default_ms,{out['default'] * 1e3:.0f},"
                  f"avg_tuple_time={out['default']:.3f}ms", flush=True)
            print(f"paper_{fig}_{app}_model_based_ms,"
                  f"{out['model_based'] * 1e3:.0f},"
                  f"avg_tuple_time={out['model_based']:.3f}ms")
            print(f"paper_{fig}_{app}_dqn_ms,{out['dqn'] * 1e3:.0f},"
                  f"avg_tuple_time={out['dqn']:.3f}ms")
            print(f"paper_{fig}_{app}_actor_critic_ms,"
                  f"{out['actor_critic'] * 1e3:.0f},"
                  f"avg_tuple_time={out['actor_critic']:.3f}ms;"
                  f"imp_vs_default={out['imp_vs_default']:.1%};"
                  f"imp_vs_model={out['imp_vs_model_based']:.1%}", flush=True)

    # roofline summary (if the dry-run artifacts exist)
    try:
        from benchmarks.roofline import load_all
        recs = [r for r in load_all() if r.get("status") == "ok"]
        for r in recs:
            t = r["_roofline"]
            tot = max(t["t_compute_s"], t["t_memory_s"], t["t_collective_s"])
            print(f"roofline_{r['arch']}_{r['shape']}_{r['mesh']},"
                  f"{tot * 1e6:.0f},"
                  f"bottleneck={t['bottleneck']};"
                  f"frac={t['roofline_fraction']:.3f}")
    except Exception as e:  # artifacts may not exist yet
        print(f"roofline_skipped,0,{type(e).__name__}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
