"""The paper's control loop as a launcher: train a registry agent on a
DSDPS topology (or the TPU expert-placement env) and report the schedule.

Online learning runs as a FLEET: ``--fleet N`` independent lanes execute
in one jitted, vmapped scan (core/agent.run_online_fleet) and the final
latency is reported as mean ± std across lanes, with the best lane's
assignment printed.  ``--agent`` picks any registered control policy
(core.api.make_agent) and ``--scenario`` swaps the pure seed sweep for a
named heterogeneous params fleet — per-lane workload rates / stragglers /
noise in the same single program.  All scenario construction routes
through ``repro.dsdps.scenarios.build_for``, which also dispatches the
TPU expert-placement env's PlacementParams scenarios
(``--app placement --scenario one_slow_device``).  Agents initialize
under their lane's scenario (the model-based baseline profiles and fits
the lane's cluster — lane-correct speeds/services/noise, not the nominal
profile), and ``--broadcast-invariant`` keeps scenario-invariant params
leaves single-copy (per-leaf in_axes=None broadcasting).

Production scale-out: ``--sharded`` partitions the fleet axis over every
visible device (``launch.mesh.make_fleet_mesh``, shard_map under the
hood), and ``--checkpoint-dir DIR`` snapshots the fleet carries
asynchronously + atomically every ``--checkpoint-every`` epochs; a killed
run restarted with ``--resume`` picks up from the newest checkpoint,
re-placed against the current mesh (device counts may differ between
save and restore).  See docs/sharded_fleets.md.

Budget-aware fleets (docs/elastic_fleets.md): ``--early-stop`` attaches
the elastic lane lifecycle — lanes whose smoothed reward plateaus stop
early and the fleet compacts so converged scenarios stop paying compute —
and ``--scenario-search`` swaps training for a successive-halving search
over perturbed scenarios (wide fleet, bottom half pruned at each rung,
freed lanes refilled), writing the ranked leaderboard to
``--search-json``.

  PYTHONPATH=src python -m repro.launch.drl_control --app cq_small \
      --offline 2000 --epochs 300 --fleet 8
  PYTHONPATH=src python -m repro.launch.drl_control --app cq_small \
      --agent model_based --scenario one_slow_machine --fleet 4
  PYTHONPATH=src python -m repro.launch.drl_control --app placement \
      --scenario one_slow_device
  PYTHONPATH=src python -m repro.launch.drl_control --app cq_small \
      --fleet 8 --sharded --checkpoint-dir /tmp/fleet_ck --resume
  PYTHONPATH=src python -m repro.launch.drl_control --app cq_small \
      --scenario-search --fleet 8 --search-rungs 16,16,32
  PYTHONPATH=src python -m repro.launch.drl_control --app structural \
      --agent graph_policy --scenario dag_shapes --fleet 6
"""
from __future__ import annotations

import argparse
import contextlib
import sys
import time

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import init_distributed, make_fleet_mesh

if "--distributed" in sys.argv:
    # jax.distributed.initialize must run before ANY jax computation, and
    # some agent modules build jnp defaults at import time — so the
    # coordinator handshake happens here, ahead of the heavy imports
    # below (launch.mesh itself never touches device state on import)
    init_distributed()

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (agent_names, jamba_placement_env, make_agent,
                        reset_fleet_states, run_online_fleet)
from repro.core import ddpg as ddpg_lib
from repro.core.placement import PLACEMENT_SCENARIOS
from repro.checkpoint.fleet import FleetCheckpoint
from repro.dsdps import (SchedulingEnv, StructuralSchedulingEnv, apps,
                         lane_params, scenarios)
from repro.dsdps.apps import default_workload
from repro.sharding.fleet import fleet_size


def build_env(app: str):
    if app == "placement":
        return jamba_placement_env()
    if app == "structural":
        # chain / diamond / wide-fanout padded into one envelope: the
        # DAG-shape fleet (--scenario dag_shapes varies topology per lane)
        return StructuralSchedulingEnv(apps.structural_topologies())
    topo = apps.ALL_APPS[app]()
    return SchedulingEnv(topo, default_workload(topo))


def main(argv: list[str] | None = None) -> dict:
    """Run the launcher on ``argv`` (default: the command line).

    Returns a summary for in-process callers: ``sharded_devices`` (the
    data-axis devices the fleet ran over; 0 when it ran un-sharded),
    ``start_epoch``, ``epochs_run``, per-lane ``final_ms`` and
    ``round_robin_ms``, and with ``--serve`` the per-kind ``serve`` stats
    and ``serve_s`` (wall seconds of serving)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--app", default="cq_small",
                    choices=list(apps.ALL_APPS) + ["placement", "structural"],
                    help="one Storm topology, the TPU expert-placement env, "
                         "or 'structural' — the envelope-padded DAG-shape "
                         "env over apps.STRUCTURAL_APPS (pairs with "
                         "--agent graph_policy / --scenario dag_shapes)")
    ap.add_argument("--agent", default="ddpg", choices=list(agent_names()),
                    help="registered control policy (core.api.make_agent)")
    ap.add_argument("--scenario", default=None,
                    choices=sorted(set(scenarios.SCENARIOS)
                                   | set(scenarios.STRUCTURAL_SCENARIOS)
                                   | set(PLACEMENT_SCENARIOS)),
                    help="heterogeneous params fleet instead of a pure "
                         "seed sweep (EnvParams for DSDPS apps, "
                         "PlacementParams for --app placement; the "
                         "structure-varying dag_shapes needs "
                         "--app structural)")
    ap.add_argument("--broadcast-invariant", action="store_true",
                    help="keep scenario-invariant params leaves single-copy "
                         "(per-leaf in_axes=None broadcast in the vmap)")
    ap.add_argument("--offline", type=int, default=2000,
                    help="offline random-action samples (paper: 10,000; "
                         "ddpg only)")
    ap.add_argument("--offline-updates", type=int, default=500)
    ap.add_argument("--epochs", type=int, default=300)
    ap.add_argument("--fleet", type=int, default=4,
                    help="independent online-learning lanes, batched in one "
                         "XLA program")
    ap.add_argument("--k", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sharded", action="store_true",
                    help="partition the fleet axis over every visible "
                         "device (launch.mesh.make_fleet_mesh + shard_map); "
                         "--fleet must be a multiple of the device count")
    ap.add_argument("--distributed", action="store_true",
                    help="multi-host fleet: join a jax.distributed job "
                         "(coordinator/rank from REPRO_COORDINATOR / "
                         "REPRO_NUM_PROCESSES / REPRO_PROCESS_ID, see "
                         "launch.mesh.init_distributed) and shard the "
                         "fleet over a PROCESS-SPANNING mesh; every "
                         "process runs this same command "
                         "(repro.launch.multihost spawns localhost jobs)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="directory for async atomic fleet checkpoints "
                         "(FleetCheckpoint); enables crash recovery")
    ap.add_argument("--checkpoint-every", type=int, default=50,
                    help="checkpoint cadence in decision epochs")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the newest checkpoint in "
                         "--checkpoint-dir (re-placed against the current "
                         "mesh) instead of starting fresh")
    ap.add_argument("--early-stop", action="store_true",
                    help="elastic lane lifecycle: stop lanes whose smoothed "
                         "reward plateaus and compact the fleet so "
                         "converged scenarios stop paying compute "
                         "(repro.fleet.lifecycle, docs/elastic_fleets.md)")
    ap.add_argument("--scenario-search", action="store_true",
                    help="successive-halving search over perturbed "
                         "scenarios instead of training: --fleet "
                         "candidates seeded from --scenario (default "
                         "mixed), bottom half pruned at each rung, freed "
                         "lanes refilled; prints and saves the ranked "
                         "leaderboard")
    ap.add_argument("--search-rungs", default="16,16,32",
                    help="comma-separated epochs per successive-halving "
                         "rung")
    ap.add_argument("--search-json", default="artifacts/scenario_search.json",
                    help="leaderboard artifact path for --scenario-search")
    ap.add_argument("--serve", type=int, default=0, metavar="N",
                    help="after training, serve N synthetic decision "
                         "requests from the best lane's trained policy "
                         "through the batched serving control plane — "
                         "every training lane's scenario becomes a "
                         "registered cluster (repro.serve.control, "
                         "docs/serving.md)")
    ap.add_argument("--guards", action="store_true",
                    help="run the online-learning phase under the runtime "
                         "tracing-discipline guards (repro.diagnostics): "
                         "implicit-transfer guard, jit-cache-miss sentinel, "
                         "chunk-boundary NaN/Inf sweeps "
                         "(docs/static_analysis.md)")
    args = ap.parse_args(argv)
    if args.fleet < 1:
        ap.error("--fleet must be >= 1")
    if args.distributed:
        if args.serve:
            ap.error("--serve drives a single-process control plane; run "
                     "it without --distributed")
        if args.scenario_search:
            ap.error("--scenario-search runs its own single-process rung "
                     "fleets; drop --distributed")
        # already joined at import time (see module top); a no-op
        # single-process run (no coordinator configured) degrades to
        # --sharded over the local devices.  Idempotent re-call keeps
        # programmatic main() invocations honest too.
        init_distributed()
        if jax.process_index() != 0:
            # one report per job: non-zero ranks run the same program but
            # stay quiet (their results are identical by construction)
            import os
            import sys
            sys.stdout = open(os.devnull, "w")
    if args.agent == "model_based" and args.app == "placement":
        ap.error("model_based profiles a DSDPS cluster; use it with the "
                 "Storm apps")
    if args.agent == "graph_policy" and args.app == "placement":
        ap.error("graph_policy message-passes over a topology DAG; use it "
                 "with the Storm apps or --app structural")
    if args.agent in ("rate_control", "auto_tune"):
        ap.error(f"{args.agent} is a serving-side decision policy (its "
                 f"actions are not placements) — it runs behind the "
                 f"serving control plane: repro.launch.serve_control, or "
                 f"--serve N after training (docs/serving.md)")
    if args.serve and args.app == "placement":
        ap.error("--serve drives the DSDPS control plane; use it with the "
                 "Storm apps")
    if args.serve and args.agent not in ("ddpg", "round_robin"):
        ap.error(f"--serve needs an agent that decides from (s_vec, "
                 f"cluster params) alone; {args.agent}'s select reads the "
                 f"live EnvState (see docs/serving.md)")
    if args.scenario_search:
        for flag, on in (("--sharded", args.sharded),
                         ("--checkpoint-dir", args.checkpoint_dir),
                         ("--resume", args.resume),
                         ("--early-stop", args.early_stop)):
            if on:
                ap.error(f"--scenario-search does not support {flag}: the "
                         f"search runs its own un-sharded, un-checkpointed "
                         f"rung fleets (--offline/--epochs are ignored too "
                         f"— rung lengths come from --search-rungs)")

    env = build_env(args.app)
    if args.scenario and args.scenario not in scenarios.scenario_names(env):
        ap.error(f"scenario {args.scenario!r} is not defined for "
                 f"--app {args.app}; "
                 f"known: {scenarios.scenario_names(env)}")
    overrides = {"k_nn": args.k} if args.agent == "ddpg" else {}
    agent = make_agent(args.agent, env, **overrides)
    key = jax.random.PRNGKey(args.seed)

    if args.scenario_search:
        from repro.fleet.lifecycle import search_scenarios
        rungs = tuple(int(x) for x in args.search_rungs.split(",") if x)
        if args.fleet < 2:
            ap.error("--scenario-search needs --fleet >= 2")
        print(f"successive-halving scenario search: {args.fleet} candidates "
              f"seeded from {args.scenario or 'mixed'!r}, rungs {rungs} ...")
        lb = search_scenarios(env, agent,
                              scenario=args.scenario or "mixed",
                              fleet=args.fleet, rungs=rungs, seed=args.seed)
        print(f"\nrank  cand  rung  epochs  eval_reward  survived")
        for rank, e in enumerate(lb.entries):
            print(f"{rank:4d}  {e.cand:4d}  {e.rung:4d}  {e.epochs:6d}  "
                  f"{e.score:11.4f}  {e.survived}")
        print(f"\ntotal lane-epochs executed: {lb.total_lane_epochs} "
              f"(fixed grid over every candidate would be "
              f"{len(lb.entries) * sum(rungs)})")
        path = lb.save(args.search_json)
        print(f"wrote {path}")
        return {"leaderboard": str(path)}
    env_params = (scenarios.build_for(
        env, args.scenario, args.fleet,
        broadcast_invariant=args.broadcast_invariant)
        if args.scenario else None)
    # lanes initialize under their own scenario: the model-based baseline
    # profiles and fits the lane's cluster, not the nominal one
    states = agent.init_fleet(key, args.fleet, env_params=env_params,
                              env=env)

    if args.distributed:
        mesh = make_fleet_mesh(spanning=True)
    elif args.sharded:
        mesh = make_fleet_mesh()
    else:
        mesh = None
    if mesh is not None and args.fleet % fleet_size(mesh) != 0:
        # elastic degradation: a checkpoint may be resumed on a machine
        # whose device count no longer divides the fleet — run un-sharded
        # rather than dying in shard_fleet's divisibility check
        print(f"--fleet {args.fleet} does not divide the "
              f"{fleet_size(mesh)} data-axis devices; falling back to the "
              f"un-sharded vmap runner")
        mesh = None
    ck = (FleetCheckpoint(args.checkpoint_dir, every=args.checkpoint_every)
          if args.checkpoint_dir else None)
    keys = jax.random.split(jax.random.fold_in(key, 2), args.fleet)
    env_states, start_epoch, restored, lane_ids = None, 0, False, None
    if args.resume:
        if ck is None:
            ap.error("--resume needs --checkpoint-dir")
        if ck.latest_epoch() is not None:
            like_env = reset_fleet_states(keys, env, env_params)
            if ck.has_lane_map():
                # elastic-lifecycle snapshot: the saved fleet is COMPACTED
                # (possibly padded with passenger lanes) — restore through
                # the lane map, drop passengers, and subset the scenario
                # fleet to the surviving original lanes
                if not args.early_stop:
                    ap.error(f"{ck.directory} holds elastic-lifecycle "
                             f"(compacted) snapshots; resume with "
                             f"--early-stop")
                from repro.fleet.lifecycle import restore_elastic
                (start_epoch, keys, states, env_states, env_params,
                 lane_ids) = restore_elastic(
                    ck, states, like_env, keys, env_params=env_params,
                    ref=(env.default_params() if env_params is not None
                         else None))
                restored = True
                if mesh is not None and \
                        int(keys.shape[0]) % fleet_size(mesh) != 0:
                    print(f"{int(keys.shape[0])} surviving lane(s) do not "
                          f"divide the {fleet_size(mesh)} data-axis "
                          f"devices; falling back to the un-sharded vmap "
                          f"runner")
                    mesh = None
                print(f"resuming compacted elastic fleet from epoch "
                      f"{start_epoch}: {len(lane_ids)} surviving lane(s) "
                      f"{lane_ids.tolist()} ({ck.directory})")
            else:
                start_epoch, states, env_states, keys = ck.restore(
                    states, like_env, keys, mesh=mesh)
                restored = True
                print(f"resuming from checkpoint epoch {start_epoch} "
                      f"({ck.directory})")
        if start_epoch >= args.epochs:
            print(f"checkpoint already at epoch {start_epoch} >= "
                  f"--epochs {args.epochs}; nothing left to run")
            return {"start_epoch": start_epoch, "epochs_run": 0}

    # offline pretraining only seeds a FRESH run: restored lanes already
    # carry their replay buffers and trained networks
    if not restored and args.agent == "ddpg" and args.offline > 0:
        print(f"offline pretraining {args.fleet} lanes on {args.offline} "
              f"random transitions each ...")
        states = ddpg_lib.offline_pretrain_fleet(
            jax.random.split(jax.random.fold_in(key, 1), args.fleet),
            states, agent.cfg, env,
            n_samples=args.offline, n_updates=args.offline_updates,
            env_params=env_params)

    fleet_now = int(jnp.asarray(keys).shape[0])
    scen = f" ({args.scenario} scenario fleet)" if args.scenario else ""
    where = (f" sharded over {mesh.devices.size} devices" if mesh is not None
             else "")
    stop = " with per-lane early stopping" if args.early_stop else ""
    print(f"online learning: {args.agent} fleet of {fleet_now} x "
          f"{args.epochs - start_epoch} decision epochs in one batched "
          f"scan{scen}{where}{stop} ...")
    if args.guards:
        from repro.core import agent as agent_mod
        from repro.diagnostics import guards
        region = guards(track=(agent_mod._fleet_program,
                               agent_mod._fleet_program_sharded,
                               agent_mod._fleet_program_sharded_donated),
                        label="drl_control")
    else:
        region = contextlib.nullcontext(None)
    with region as g:
        if args.early_stop:
            from repro.fleet.lifecycle import StopRule, run_online_fleet_elastic
            result = run_online_fleet_elastic(
                keys, env, agent, states, T=args.epochs - start_epoch,
                rule=StopRule(), env_params=env_params, env_states=env_states,
                mesh=mesh, checkpoint=ck, start_epoch=start_epoch,
                lane_ids=lane_ids)
            states, hist = result.states, result.history
            lanes = (f" (original lanes {result.lane_ids.tolist()})"
                     if lane_ids is not None else "")
            print(f"early stopping: per-lane epochs "
                  f"{result.epochs_run.tolist()}{lanes} "
                  f"— {result.executed_lane_epochs} lane-epochs executed vs "
                  f"{result.fixed_grid_lane_epochs} fixed-grid "
                  f"({result.savings:.0%} saved)")
        else:
            states, hist = run_online_fleet(
                keys, env, agent, states, T=args.epochs - start_epoch,
                env_params=env_params, env_states=env_states, mesh=mesh,
                checkpoint=ck, start_epoch=start_epoch)
    if g is not None:
        print(f"guards: clean — {g.counter.compiles} fleet-program "
              f"compilation(s) {g.counter.per_target()}, no implicit "
              f"transfers, no non-finite carries")
    if ck is not None:
        ck.close()

    # score every lane under the scenario it actually ran (round-robin too,
    # so the improvement column compares like with like per lane)
    finals, rrs = [], []
    X_rr = env.round_robin_assignment()
    n_lanes = int(np.asarray(hist.final_assignment).shape[0])
    for f in range(n_lanes):
        if env_params is not None:
            lane_p = lane_params(env_params, env.default_params(), f)
            w_f = (lane_p.base_rates if hasattr(lane_p, "base_rates")
                   else lane_p.base_load)
        else:
            lane_p = None
            w_f = (env.workload.init() if hasattr(env, "workload")
                   else env._base_load)
        X_f = jnp.asarray(hist.final_assignment[f])
        finals.append(float(env.evaluate(X_f, w_f, params=lane_p)
                            if lane_p is not None
                            else env.evaluate(X_f, w_f)))
        rrs.append(float(env.evaluate(X_rr, w_f, params=lane_p)
                         if lane_p is not None
                         else env.evaluate(X_rr, w_f)))
    finals, rrs = np.asarray(finals), np.asarray(rrs)
    # "best" is the lane with the largest improvement over ITS round-robin
    # score, so the printed latency, improvement, and assignment agree even
    # when lanes run heterogeneous scenarios
    best = int((finals / rrs).argmin())
    print(f"\nfinal latency {finals.mean():.3f} ± {finals.std():.3f} ms "
          f"over {n_lanes} lanes "
          f"(best lane {best}: {finals[best]:.3f} ms)   "
          f"round-robin {rrs.mean():.3f} ms   "
          f"improvement {1 - finals.mean() / rrs.mean():.1%} mean / "
          f"{1 - finals[best] / rrs[best]:.1%} best")
    print("best assignment (executor -> machine):",
          hist.final_assignment[best].argmax(-1).tolist())
    summary = {"sharded_devices": (int(mesh.devices.size)
                                   if mesh is not None else 0),
               "start_epoch": start_epoch,
               "epochs_run": args.epochs - start_epoch,
               "final_ms": finals.tolist(),
               "round_robin_ms": rrs.tolist()}

    if args.serve:
        # serve the TRAINED policy through the batched control plane: the
        # best lane's agent state answers placement requests, each
        # training lane's scenario is a registered live cluster, and the
        # rate_control / auto_tune planes ride along (docs/serving.md)
        from repro.launch.serve_control import (build_service,
                                                synthetic_requests)
        best_state = jax.tree.map(lambda x: x[best], states)
        svc = build_service(env, seed=args.seed, n_slots=min(8, args.serve),
                            placement_agent=agent,
                            placement_state=best_state)
        for f in range(n_lanes):
            svc.register_cluster(
                f"lane-{f}",
                lane_params(env_params, env.default_params(), f)
                if env_params is not None else None)
        for r in synthetic_requests(env, svc, args.serve, seed=args.seed):
            svc.submit(r)
        print(f"\nserving {args.serve} decision requests from the trained "
              f"policy across {n_lanes} cluster(s) ...")
        t0 = time.perf_counter()
        served = svc.run(jax.random.fold_in(key, 3))
        summary["serve_s"] = time.perf_counter() - t0
        summary["serve"] = svc.decision_stats()
        for kind, stats in summary["serve"].items():
            print(f"  {kind:13s} n={stats['n']:4d}  "
                  f"p50 {stats['p50_ms']:8.3f} ms  "
                  f"p99 {stats['p99_ms']:8.3f} ms")
        assert len(served) == args.serve
    return summary


if __name__ == "__main__":
    enable_compile_cache()
    main()
