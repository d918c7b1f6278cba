"""Paper Fig 12: robustness to a +50% workload change at mid-run —
actor-critic vs model-based on the three large-scale topologies.

The trained AC agent re-schedules online after the shift; the model-based
scheduler re-runs its search with the new workload (as [25] would).  The
shift itself is just an EnvParams edit (``scale_rates``) against the same
env spec — no env rebuild, and further shifts at the same horizon reuse
the compiled program — the functional-core payoff."""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.paper_common import (Budget, make_env, run_actor_critic,
                                     run_model_based)
from repro.core import make_agent, run_online_fleet
from repro.dsdps import SchedulingEnv, scenarios
from repro.launch.compile_cache import enable_compile_cache

ART = pathlib.Path(__file__).resolve().parents[1] / "artifacts" / "paper"


def run(app: str, budget: Budget, seed: int = 0,
        shift_factor: float = 1.5) -> dict:
    env = make_env(app)
    # pre-train the agent fleet on the unshifted workload
    ac_lats0, _, (states, cfg) = run_actor_critic(env, budget, seed)
    mb_lat0, Xmb = run_model_based(env, budget, seed)

    # shifted scenario: both methods adapt.  For the DRL fleet the shift is
    # a traced-parameter change against the same env spec (no env rebuild);
    # constructed through the named-scenario module like every other fleet.
    shifted = scenarios.workload_shift(env, shift_factor)
    keys = jax.random.split(jax.random.PRNGKey(seed + 7), budget.n_seeds)
    states, hist = run_online_fleet(
        keys, env, make_agent("ddpg", env, cfg=cfg), states,
        T=max(budget.online_epochs // 3, 40),
        updates_per_epoch=budget.updates_per_epoch,
        env_params=shifted)
    w_new = shifted.base_rates
    ac_after = [float(env.evaluate(
        jnp.asarray(hist.final_assignment[f]), w_new, params=shifted))
        for f in range(budget.n_seeds)]
    # model-based: refit search under new workload using its old model —
    # [25] profiles the (shifted) system, so it sees the shifted env spec
    wl = dataclasses.replace(env.workload,
                             base_rates=tuple(r * shift_factor
                                              for r in env.workload.base_rates))
    env_shift = SchedulingEnv(env.topo, wl, cluster=env.cluster,
                              noise_sigma=env.noise_sigma, seed=env.seed)
    from repro.core.model_based import ModelBasedScheduler
    mb = ModelBasedScheduler(env_shift).fit(jax.random.PRNGKey(seed),
                                            n_samples=budget.mb_samples)
    mb_after = float(env_shift.evaluate(mb.schedule(w_new, sweeps=3), w_new))
    return {"app": app, "n_seeds": budget.n_seeds,
            "ac_before": float(np.mean(ac_lats0)),
            "ac_before_std": float(np.std(ac_lats0)),
            "mb_before": mb_lat0,
            "ac_after_shift": float(np.mean(ac_after)),
            "ac_after_shift_std": float(np.std(ac_after)),
            "ac_after_seeds": ac_after,
            "mb_after_shift": mb_after,
            "shift_factor": shift_factor}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper-budget", action="store_true")
    ap.add_argument("--apps", nargs="+",
                    default=["cq_large", "log_stream", "word_count"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    budget = Budget.paper() if args.paper_budget else Budget.quick()
    results = []
    for app in args.apps:
        out = run(app, budget, args.seed)
        results.append(out)
        print(f"[{app}] AC {out['ac_before']:.2f}±{out['ac_before_std']:.2f} "
              f"-> {out['ac_after_shift']:.2f}±{out['ac_after_shift_std']:.2f}ms "
              f"({out['n_seeds']} seeds), "
              f"model-based {out['mb_before']:.2f} -> {out['mb_after_shift']:.2f}ms "
              f"after +{(out['shift_factor'] - 1):.0%} workload "
              f"(paper Fig12 cq_large: AC 1.76 vs MB 2.17)", flush=True)
    ART.mkdir(parents=True, exist_ok=True)
    (ART / "fig12.json").write_text(json.dumps(results, indent=2))


if __name__ == "__main__":
    enable_compile_cache()
    main()
