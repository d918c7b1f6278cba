"""Fleet-runner microbenchmark: online-learning epochs/sec, sequential
legacy Python loop vs the fully-jitted fleet-batched scan — and, with
``--scenario-batched``, the scenario-batched fleet where every lane carries
its own EnvParams (heterogeneous workload rates × service jitter × noise ×
stragglers) vmapped through the same one-XLA-program runner.
``--sharded`` additionally times the mesh-sharded fleet
(``run_online_fleet(..., mesh=launch.mesh.make_fleet_mesh())``): the fleet
axis partitioned over every visible device via shard_map, recorded as
lane-epochs/sec next to the single-device vmap row.  ``--lifecycle`` times
the elastic lane lifecycle (repro/fleet/lifecycle.py) against the fixed
grid on a plateauing fleet: total lane-epochs executed, the savings
fraction, elastic-vs-fixed lane-epochs/sec, and the final-reward gap.
``--graph`` runs the structural (DAG-shape) fleet: graph_policy vs ddpg
on the same ``dag_shapes`` scenario lanes — different topologies padded
into one envelope and trained as ONE program (compile-once asserted
under the diagnostics guards; per-topology tail-latency parity >= 0.95
asserted in full runs).

The paper's credibility hinges on seed-swept online-learning curves; this
bench shows why that is now affordable — one vmapped scan executes the
whole fleet as a single XLA program (target: ≥ 10× lane-epochs/sec over
the per-epoch Python loop), and scenario heterogeneity rides as traced
parameters: the stacked-params program compiles once, then any scenario
edit (new rates, stragglers, noise levels) reuses the executable.

  PYTHONPATH=src python -m benchmarks.fleet_bench [--fleet 32] [--epochs 300]
      [--scenario-batched] [--sharded] [--json artifacts/fleet_bench.json]

Rows are ``name,us_per_call,derived`` — the benchmarks.run CSV schema
(us_per_call = microseconds per lane-epoch); the same rows are written to
the JSON artifact."""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import time

if "--multihost-worker" in sys.argv:
    # workers of a `--multihost` run must join jax.distributed before ANY
    # jax computation — and some agent modules build jnp defaults at
    # import time — so the handshake happens ahead of the imports below
    from repro.launch.mesh import init_distributed
    init_distributed()

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ddpg as ddpg_lib
from repro.core import make_agent
from repro.core.agent import run_online_ddpg_python, run_online_fleet
from repro.core.ddpg import DDPGConfig
from repro.dsdps import SchedulingEnv, apps, scenarios
from repro.dsdps.apps import default_workload
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_fleet_mesh

DEFAULT_JSON = pathlib.Path(__file__).resolve().parents[1] / "artifacts" / \
    "fleet_bench.json"


def _params_bytes(params) -> int:
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(params))


def provenance(mesh_shape=None, agent=None) -> dict:
    """Where this row was measured: pinned on every JSON row so numbers
    from different machines / backends / process topologies never get
    compared as like-for-like by accident.  ``agent`` additionally pins
    WHICH agent kind produced the row (the --streaming rows compare agent
    kinds, so the name must survive into the artifact)."""
    out = {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "process_count": jax.process_count(),
        "device_count": jax.device_count(),
        "local_device_count": jax.local_device_count(),
    }
    if mesh_shape is not None:
        out["mesh_shape"] = [int(s) for s in mesh_shape]
    if agent is not None:
        out["agent"] = agent
    return out


def run_all(fleet: int = 32, epochs: int = 300, app: str = "cq_small",
            baseline_epochs: int = 40,
            scenario_batched: bool = False,
            broadcast_invariant: bool = False,
            sharded: bool = False,
            lifecycle: bool = False,
            guards_overhead: bool = False) -> list[tuple]:
    # the broadcast comparison is a variant OF the scenario-batched fleet
    scenario_batched = scenario_batched or broadcast_invariant
    topo = apps.ALL_APPS[app]()
    env = SchedulingEnv(topo, default_workload(topo))
    cfg = DDPGConfig(n_executors=env.N, n_machines=env.M,
                     state_dim=env.state_dim)
    agent = make_agent("ddpg", env, cfg=cfg)
    state = ddpg_lib.init_state(jax.random.PRNGKey(0), cfg)
    rows = []

    # sequential baseline: the legacy per-epoch Python loop (short run —
    # per-epoch cost is flat after the first few jit dispatches)
    run_online_ddpg_python(jax.random.PRNGKey(1), env, cfg, state, T=3)
    t0 = time.perf_counter()
    run_online_ddpg_python(jax.random.PRNGKey(1), env, cfg, state,
                           T=baseline_epochs)
    dt = time.perf_counter() - t0
    eps_python = baseline_epochs / dt
    rows.append((f"fleet_bench_{app}_python_loop", dt / baseline_epochs * 1e6,
                 f"epochs_per_sec={eps_python:.1f}"))

    # seed-only fleet: fleet × epochs lane-epochs in ONE jitted vmapped scan
    states = ddpg_lib.init_fleet(jax.random.PRNGKey(2), cfg, fleet)
    keys = jax.random.split(jax.random.PRNGKey(3), fleet)
    t0 = time.perf_counter()
    run_online_fleet(keys, env, agent, states, T=epochs)
    dt_cold = time.perf_counter() - t0              # includes compile
    t0 = time.perf_counter()
    run_online_fleet(keys, env, agent, states, T=epochs)
    dt_warm = time.perf_counter() - t0
    eps_warm = fleet * epochs / dt_warm
    eps_cold = fleet * epochs / dt_cold
    rows.append((f"fleet_bench_{app}_scan_f{fleet}_T{epochs}",
                 dt_warm / (fleet * epochs) * 1e6,
                 f"lane_epochs_per_sec={eps_warm:.1f};"
                 f"speedup_vs_python={eps_warm / eps_python:.1f}x;"
                 f"speedup_incl_compile={eps_cold / eps_python:.1f}x"))

    # per-lane memory: what one more lane costs — the replay buffer
    # dominates the carry, and this is the number that sizes 1000+-lane
    # sweeps against a device's HBM (ROADMAP: multi-host mega-fleets)
    carry_bytes = _params_bytes(states)
    replay_bytes = (_params_bytes(states.replay)
                    if hasattr(states, "replay") else 0)
    rows.append((f"fleet_bench_{app}_lane_memory_f{fleet}", 0.0,
                 f"carry_bytes_per_lane={carry_bytes // fleet};"
                 f"replay_bytes_per_lane={replay_bytes // fleet};"
                 f"net_bytes_per_lane={(carry_bytes - replay_bytes) // fleet};"
                 f"replay_fraction={replay_bytes / max(carry_bytes, 1):.3f};"
                 f"fleet_carry_bytes={carry_bytes}"))

    if guards_overhead:
        # the SAME seed-only fleet run, re-timed inside the runtime
        # tracing-discipline guards (repro.diagnostics.guards): implicit-
        # transfer guard + jit-cache-miss sentinel + non-finite sweeps at
        # chunk boundaries.  The program is already compiled from the row
        # above, so this isolates steady-state guard overhead against
        # dt_warm — the acceptance contract pins it under 5% on cq_small.
        from repro.core import agent as agent_mod
        from repro.diagnostics import guards
        with guards(track=(agent_mod._fleet_program,),
                    label="fleet_bench") as g:
            run_online_fleet(keys, env, agent, states, T=epochs)  # settle
            t0 = time.perf_counter()
            run_online_fleet(keys, env, agent, states, T=epochs)
            dt_g = time.perf_counter() - t0
            compiles = g.counter.compiles
        eps_g = fleet * epochs / dt_g
        overhead = dt_g / dt_warm - 1.0
        rows.append((f"fleet_bench_{app}_guards_f{fleet}_T{epochs}",
                     dt_g / (fleet * epochs) * 1e6,
                     f"guarded_lane_epochs_per_sec={eps_g:.1f};"
                     f"unguarded_lane_epochs_per_sec={eps_warm:.1f};"
                     f"guard_overhead_pct={overhead * 100:.2f};"
                     f"fleet_program_compiles_under_guard={compiles}"))

    if scenario_batched:
        # scenario-batched fleet: per-lane EnvParams (mixed stragglers /
        # diurnal rates / noise / service jitter) vmapped as traced inputs.
        # The stacked-params program compiles once (cold_s below); EDITING
        # the scenario values afterwards reuses the executable — that warm
        # path is what the second timing measures.
        env_params = scenarios.build("mixed", env, fleet)
        t0 = time.perf_counter()
        run_online_fleet(keys, env, agent, states, T=epochs,
                         env_params=env_params)
        dt_cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        run_online_fleet(keys, env, agent, states, T=epochs,
                         env_params=env_params)
        dt_warm = time.perf_counter() - t0
        eps_scen = fleet * epochs / dt_warm
        rows.append((f"fleet_bench_{app}_scenario_f{fleet}_T{epochs}",
                     dt_warm / (fleet * epochs) * 1e6,
                     f"lane_epochs_per_sec={eps_scen:.1f};"
                     f"vs_seed_only_fleet={eps_scen / eps_warm:.2f}x;"
                     f"speedup_vs_python={eps_scen / eps_python:.1f}x;"
                     f"cold_s={dt_cold:.2f}"))

        if broadcast_invariant:
            # same scenario fleet, but scenario-invariant leaves (routing /
            # flow_solve / tuple_bytes) kept single-copy and broadcast with
            # per-leaf in_axes=None — numerically identical to the stacked
            # run, minus the F×-duplicated params memory
            bc_params = scenarios.build("mixed", env, fleet,
                                        broadcast_invariant=True)
            run_online_fleet(keys, env, agent, states, T=epochs,
                             env_params=bc_params)   # compile
            t0 = time.perf_counter()
            run_online_fleet(keys, env, agent, states, T=epochs,
                             env_params=bc_params)
            dt_bc = time.perf_counter() - t0
            eps_bc = fleet * epochs / dt_bc
            rows.append((f"fleet_bench_{app}_broadcast_f{fleet}_T{epochs}",
                         dt_bc / (fleet * epochs) * 1e6,
                         f"lane_epochs_per_sec={eps_bc:.1f};"
                         f"vs_stacked_scenario={eps_bc / eps_scen:.2f}x;"
                         f"params_bytes_stacked={_params_bytes(env_params)};"
                         f"params_bytes_broadcast={_params_bytes(bc_params)}"))

    if sharded:
        # mesh-sharded fleet: the SAME runner with the fleet axis
        # partitioned over every visible device (shard_map over the data
        # axis of launch.mesh.make_fleet_mesh()).  On a 1-device host this
        # measures the sharding machinery's overhead against the plain
        # vmap row; on a real mesh it is the fleet-capacity scaling row.
        # Carries are donated on accelerator meshes, so hand the program
        # fresh copies each call.
        mesh = make_fleet_mesh()
        n_dev = mesh.devices.size

        def fresh():
            return jax.tree.map(jnp.array, states)

        run_online_fleet(keys, env, agent, fresh(), T=epochs,
                         mesh=mesh)                  # compile
        t0 = time.perf_counter()
        run_online_fleet(keys, env, agent, fresh(), T=epochs, mesh=mesh)
        dt_sh = time.perf_counter() - t0
        eps_sh = fleet * epochs / dt_sh
        rows.append((f"fleet_bench_{app}_sharded_f{fleet}_T{epochs}_d{n_dev}",
                     dt_sh / (fleet * epochs) * 1e6,
                     f"lane_epochs_per_sec={eps_sh:.1f};"
                     f"vmap_lane_epochs_per_sec={eps_warm:.1f};"
                     f"vs_vmap={eps_sh / eps_warm:.2f}x;"
                     f"devices={n_dev}"))

    if lifecycle:
        # elastic lane lifecycle vs fixed grid on a PLATEAUING fleet: the
        # round-robin baseline's reward plateaus by construction, so what
        # this row measures is the stopping rule's detection latency and
        # the lane-epochs the compacting runner then refuses to pay —
        # executed_lane_epochs strictly below the fixed grid with final
        # eval rewards matching within tolerance (the ISSUE-5 acceptance
        # contract; the bit-exactness side is pinned in
        # tests/test_lifecycle.py).
        from repro.fleet.lifecycle import StopRule, run_online_fleet_elastic
        rr = make_agent("round_robin", env)
        rr_states = rr.init_fleet(jax.random.PRNGKey(5), fleet)
        rule = StopRule(window=max(2, epochs // 16), rel_tol=0.02,
                        min_epochs=max(4, epochs // 8),
                        check_every=max(4, epochs // 8))
        run_online_fleet(keys, env, rr, rr_states, T=epochs)      # compile
        t0 = time.perf_counter()
        _, h_fix = run_online_fleet(keys, env, rr, rr_states, T=epochs)
        dt_fix = time.perf_counter() - t0
        run_online_fleet_elastic(keys, env, rr, rr_states, epochs,
                                 rule=rule)                       # compile
        t0 = time.perf_counter()
        res = run_online_fleet_elastic(keys, env, rr, rr_states, epochs,
                                       rule=rule)
        dt_el = time.perf_counter() - t0
        eps_fix = fleet * epochs / dt_fix
        eps_el = res.executed_lane_epochs / dt_el
        k = max(1, min(rule.window, epochs))
        gap = float(np.abs(res.history.rewards[:, -k:].mean(axis=1)
                           - np.asarray(h_fix.rewards)[:, -k:].mean(axis=1)
                           ).max())
        rows.append((f"fleet_bench_{app}_lifecycle_f{fleet}_T{epochs}",
                     dt_el / max(res.executed_lane_epochs, 1) * 1e6,
                     f"executed_lane_epochs={res.executed_lane_epochs};"
                     f"fixed_grid_lane_epochs={res.fixed_grid_lane_epochs};"
                     f"savings={res.savings:.2f};"
                     f"elastic_lane_epochs_per_sec={eps_el:.1f};"
                     f"fixed_lane_epochs_per_sec={eps_fix:.1f};"
                     f"elastic_wall_s={dt_el:.3f};fixed_wall_s={dt_fix:.3f};"
                     f"final_reward_gap={gap:.5f}"))

        # successive-halving scenario search: how many lane-epochs the
        # rung/prune/refill discipline spends vs a fixed grid over every
        # candidate it ever launched
        from repro.fleet.lifecycle import search_scenarios
        s_fleet = min(fleet, 8)
        rung = max(2, epochs // 8)
        t0 = time.perf_counter()
        lb = search_scenarios(env, rr, fleet=s_fleet,
                              rungs=(rung, rung, 2 * rung),
                              eval_window=max(2, rung // 2), seed=0)
        dt_s = time.perf_counter() - t0
        fixed_grid = len(lb.entries) * sum(lb.rungs)
        rows.append((f"fleet_bench_{app}_search_f{s_fleet}_r{rung}",
                     dt_s / max(lb.total_lane_epochs, 1) * 1e6,
                     f"candidates={len(lb.entries)};"
                     f"total_lane_epochs={lb.total_lane_epochs};"
                     f"fixed_grid_lane_epochs={fixed_grid};"
                     f"best_eval_reward={lb.entries[0].score:.4f};"
                     f"wall_s={dt_s:.3f}"))
    return rows


# --------------------------------------------------------------------------
# streaming lanes: replay-free Stream Q(λ)/AC(λ) vs the replay agents
# --------------------------------------------------------------------------
HBM_BUDGET_GIB = 16.0    # reference accelerator memory for the width ceiling


def _replay_bytes(states) -> int:
    return _params_bytes(states.replay) if hasattr(states, "replay") else 0


def _trace_bytes(states) -> int:
    total = 0
    for leaf in ("z", "z_actor", "z_critic"):
        if hasattr(states, leaf):
            total += _params_bytes(getattr(states, leaf))
    return total


def run_streaming(fleet: int = 4, epochs: int = 300,
                  app: str = "cq_small") -> list[tuple]:
    """The replay-free streaming story in three rows per agent pair:

    * parity — final smoothed (per-lane min-max-normalized, filtfilt)
      reward of the streaming fleet over the replay fleet, same seeds,
      plus warm lane-epochs/sec for both;
    * memory — per-lane carry bytes side by side (streaming lanes report
      ZERO replay bytes; the carry is nets + traces + the Welford
      normalizer) and the shrink factor;
    * width ceiling — how many lanes of each kind fit a reference
      HBM_BUDGET_GIB accelerator, i.e. the fleet-width cap moving.

    Every row's provenance block carries the streaming agent kind."""
    topo = apps.ALL_APPS[app]()
    env = SchedulingEnv(topo, default_workload(topo))
    rows = []
    budget = int(HBM_BUDGET_GIB * 2**30)
    for replay_name, stream_name in (("dqn", "stream_q"),
                                     ("ddpg", "stream_ac")):
        results = {}
        for name in (replay_name, stream_name):
            agent = make_agent(name, env)
            states = agent.init_fleet(jax.random.PRNGKey(0), fleet)
            keys = jax.random.split(jax.random.PRNGKey(1), fleet)
            run_online_fleet(keys, env, agent, states, T=epochs)  # compile
            t0 = time.perf_counter()
            _, hist = run_online_fleet(keys, env, agent, states, T=epochs)
            dt = time.perf_counter() - t0
            k = max(1, min(20, epochs // 4))
            results[name] = {
                "final": float(hist.smoothed_rewards()[:, -k:].mean()),
                "eps": fleet * epochs / dt,
                "carry": _params_bytes(states) // fleet,
                "replay": _replay_bytes(states) // fleet,
                "traces": _trace_bytes(states) // fleet,
            }
        rep, st = results[replay_name], results[stream_name]
        parity = st["final"] / max(rep["final"], 1e-9)
        rows.append((
            f"fleet_bench_{app}_streaming_{stream_name}_vs_{replay_name}"
            f"_f{fleet}_T{epochs}",
            1e6 / st["eps"],
            f"parity_final_smoothed={parity:.3f};"
            f"{stream_name}_final={st['final']:.4f};"
            f"{replay_name}_final={rep['final']:.4f};"
            f"{stream_name}_lane_epochs_per_sec={st['eps']:.1f};"
            f"{replay_name}_lane_epochs_per_sec={rep['eps']:.1f}",
            provenance(agent=stream_name)))
        shrink = rep["carry"] / max(st["carry"], 1)
        rows.append((
            f"fleet_bench_{app}_streaming_memory_{stream_name}_f{fleet}",
            0.0,
            f"carry_bytes_per_lane={st['carry']};"
            f"replay_bytes_per_lane={st['replay']};"
            f"trace_bytes_per_lane={st['traces']};"
            f"{replay_name}_carry_bytes_per_lane={rep['carry']};"
            f"{replay_name}_replay_bytes_per_lane={rep['replay']};"
            f"carry_shrink_vs_{replay_name}={shrink:.1f}x",
            provenance(agent=stream_name)))
        width_replay = budget // max(rep["carry"], 1)
        width_stream = budget // max(st["carry"], 1)
        rows.append((
            f"fleet_bench_{app}_fleet_width_ceiling_{stream_name}",
            0.0,
            f"hbm_budget_gib={HBM_BUDGET_GIB:.0f};"
            f"max_fleet_width_{replay_name}={width_replay};"
            f"max_fleet_width_{stream_name}={width_stream};"
            f"widening={width_stream / max(width_replay, 1):.1f}x",
            provenance(agent=stream_name)))
    return rows


# --------------------------------------------------------------------------
# structural (DAG-shape) fleets: graph_policy vs ddpg across topologies
# --------------------------------------------------------------------------
def run_graph(fleet: int = 6, epochs: int = 300,
              smoke: bool = False) -> list[tuple]:
    """The Decima-style structural story: ONE fleet trains across
    *different DAGs* (chain / diamond / wide fan-out padded into a common
    envelope, ``scenarios.dag_shapes``) in a single XLA program, and the
    graph policy's message passing is compared against the flat-vector
    ddpg baseline on the SAME lanes.

    Two contracts are asserted here (they are what the CI graph smoke
    lane pins):

    * compile-once — despite three heterogeneous graph structures, the
      fleet program compiles exactly once (structure rides as traced
      GraphEnvParams leaves, checked under repro.diagnostics.guards);
    * parity (full runs only) — per-topology BEST-lane tail latency of
      the graph fleet within 0.95x of ddpg's on the same scenarios (the
      fleet is a parallel seed sweep; the deployed policy is the best
      lane, drl_control's reporting convention)."""
    from repro.core import agent as agent_mod
    from repro.diagnostics import guards
    from repro.dsdps.structural import StructuralSchedulingEnv

    env = StructuralSchedulingEnv(apps.structural_topologies())
    n_topos = len(env.topologies)
    env_params = scenarios.build_for(env, "dag_shapes", fleet)
    keys = jax.random.split(jax.random.PRNGKey(1), fleet)
    k = max(1, min(20, epochs // 4))
    results = {}
    compiles = None
    for name in ("graph_policy", "ddpg", "round_robin"):
        agent = make_agent(name, env)
        states = agent.init_fleet(jax.random.PRNGKey(0), fleet,
                                  env_params=env_params, env=env)
        if name == "graph_policy":
            # cold + warm run under the tracing-discipline guards: the
            # heterogeneous-DAG fleet must compile exactly once
            with guards(track=(agent_mod._fleet_program,),
                        label="fleet_bench_graph") as g:
                t0 = time.perf_counter()
                run_online_fleet(keys, env, agent, states, T=epochs,
                                 env_params=env_params)
                dt_cold = time.perf_counter() - t0
                t0 = time.perf_counter()
                _, hist = run_online_fleet(keys, env, agent, states, T=epochs,
                                           env_params=env_params)
                dt = time.perf_counter() - t0
                compiles = g.counter.compiles
            if compiles != 1:
                raise SystemExit(
                    f"--graph: structural fleet compiled {compiles}x across "
                    f"two runs over {n_topos} DAG shapes (want exactly 1 — "
                    f"topology structure must ride as traced params, not "
                    f"static shapes)")
        else:
            run_online_fleet(keys, env, agent, states, T=epochs,
                             env_params=env_params)           # compile
            t0 = time.perf_counter()
            _, hist = run_online_fleet(keys, env, agent, states, T=epochs,
                                       env_params=env_params)
            dt = time.perf_counter() - t0
        results[name] = {
            "eps": fleet * epochs / dt,
            "tails": np.asarray(hist.latencies)[:, -k:].mean(axis=1),
        }
    g_res, d_res = results["graph_policy"], results["ddpg"]
    env_d = env.envelope
    rows = [(f"fleet_bench_graph_dag_shapes_f{fleet}_T{epochs}",
             1e6 / g_res["eps"],
             f"lane_epochs_per_sec={g_res['eps']:.1f};"
             f"ddpg_lane_epochs_per_sec={d_res['eps']:.1f};"
             f"fleet_program_compiles={compiles};"
             f"n_topologies={n_topos};"
             f"envelope=execs{env_d.max_execs}_edges{env_d.max_edges}"
             f"_spouts{env_d.max_spouts}_comps{env_d.max_components}"
             + (f";cold_s={dt_cold:.2f}" if not smoke else ""),
             provenance(agent="graph_policy"))]
    # per-topology parity: lane i runs topology i % n_topos, so grouping
    # lanes by residue compares the two agents on identical scenario sets.
    # The asserted number is BEST-lane parity — the fleet is a parallel
    # seed sweep and the deployed policy is the best lane (drl_control's
    # reporting convention); lane means ride along for transparency.
    per_topo, lanes = [], np.arange(fleet)
    for t, topo in enumerate(env.topologies):
        sel = lanes % n_topos == t
        g_best = float(g_res["tails"][sel].min())
        parity = float(d_res["tails"][sel].min()) / max(g_best, 1e-9)
        parity_mean = (float(d_res["tails"][sel].mean())
                       / max(float(g_res["tails"][sel].mean()), 1e-9))
        rr_lat = float(results["round_robin"]["tails"][sel].mean())
        per_topo.append((topo.name, parity, parity_mean, g_best, rr_lat))
    parity_min = min(p for _, p, _, _, _ in per_topo)
    rows.append((f"fleet_bench_graph_parity_f{fleet}_T{epochs}",
                 0.0,
                 f"parity_min_vs_ddpg={parity_min:.3f};" +
                 ";".join(f"{n}_best_parity={p:.3f};"
                          f"{n}_mean_parity={pm:.3f};"
                          f"{n}_best_tail_ms={gl:.3f};"
                          f"{n}_round_robin_ms={rl:.3f}"
                          for n, p, pm, gl, rl in per_topo),
                 provenance(agent="graph_policy")))
    if not smoke and parity_min < 0.95:
        raise SystemExit(
            f"--graph: per-topology best-lane tail-latency parity vs ddpg "
            f"fell to {parity_min:.3f} (< 0.95): "
            f"{[(n, round(p, 3)) for n, p, _, _, _ in per_topo]}")
    return rows


# --------------------------------------------------------------------------
# multi-host scaling: N localhost processes, one process-spanning mesh
# --------------------------------------------------------------------------
def run_multihost_worker(fleet: int, epochs: int, app: str,
                         worker_out: str | None) -> None:
    """One rank of a ``--multihost`` measurement: every process builds the
    SAME fleet from shared seeds, joins the process-spanning mesh, and
    times the spanning ``run_online_fleet`` between cross-process
    barriers; process 0 writes the result JSON for the driver."""
    from jax.experimental import multihost_utils

    from repro.launch.mesh import make_fleet_mesh
    topo = apps.ALL_APPS[app]()
    env = SchedulingEnv(topo, default_workload(topo))
    cfg = DDPGConfig(n_executors=env.N, n_machines=env.M,
                     state_dim=env.state_dim)
    agent = make_agent("ddpg", env, cfg=cfg)
    states = ddpg_lib.init_fleet(jax.random.PRNGKey(2), cfg, fleet)
    keys = jax.random.split(jax.random.PRNGKey(3), fleet)
    mesh = make_fleet_mesh(spanning=True)
    run_online_fleet(keys, env, agent, states, T=epochs, mesh=mesh)  # compile
    multihost_utils.sync_global_devices("fleet_bench_mh_warm")
    t0 = time.perf_counter()
    run_online_fleet(keys, env, agent, states, T=epochs, mesh=mesh)
    multihost_utils.sync_global_devices("fleet_bench_mh_done")
    dt = time.perf_counter() - t0
    if jax.process_index() == 0 and worker_out:
        pathlib.Path(worker_out).write_text(json.dumps({
            "lane_epochs_per_sec": fleet * epochs / dt,
            "wall_s": dt,
            "fleet": fleet, "epochs": epochs,
            "provenance": provenance(mesh.devices.shape),
        }))


def run_multihost(fleet: int = 32, epochs: int = 300, app: str = "cq_small",
                  smoke: bool = False, devices_per_proc: int = 2,
                  json_path: str = "") -> list[tuple]:
    """Drive the multi-host scaling sweep: for each process count spawn
    that many localhost workers (``repro.launch.multihost`` env wiring:
    REPRO_* vars + ``--xla_force_host_platform_device_count``), each
    running :func:`run_multihost_worker`, and record lane-epochs/sec
    plus the scaling factor against the single-process run.  On one
    machine the processes share the same cores, so the interesting
    number is the multi-process machinery's overhead staying small —
    on real multi-host fleets the same rows become capacity scaling."""
    from repro.launch.multihost import free_port, worker_env
    procs_list = (1, 2) if smoke else (1, 2, 4)
    max_dev = procs_list[-1] * devices_per_proc
    if fleet % max_dev != 0:
        raise SystemExit(
            f"--multihost needs --fleet divisible by "
            f"{max_dev} (= {procs_list[-1]} procs x {devices_per_proc} "
            f"devices); got {fleet}")
    rows, base_eps = [], None
    out_dir = pathlib.Path(json_path).parent if json_path \
        else pathlib.Path(".")
    for n in procs_list:
        coordinator = f"127.0.0.1:{free_port()}"
        out = out_dir / f".fleet_bench_mh_{n}.json"
        if out.exists():
            out.unlink()
        workers = []
        for pid in range(n):
            cmd = [sys.executable, "-m", "benchmarks.fleet_bench",
                   "--multihost-worker", "--fleet", str(fleet),
                   "--epochs", str(epochs), "--app", app, "--json", ""]
            if pid == 0:
                cmd += ["--worker-out", str(out)]
            workers.append(subprocess.Popen(
                cmd, env=worker_env(os.environ, coordinator, n, pid,
                                    devices_per_proc),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
        fail = []
        for pid, p in enumerate(workers):
            out_text, _ = p.communicate(timeout=1800)
            if p.returncode != 0:
                fail.append((pid, out_text))
        if fail:
            for pid, text in fail:
                print(f"----- multihost worker {pid}/{n} failed -----")
                print("\n".join(text.splitlines()[-30:]))
            raise SystemExit(f"--multihost: {len(fail)} worker(s) of the "
                             f"{n}-process run failed")
        payload = json.loads(out.read_text())
        out.unlink()
        eps = payload["lane_epochs_per_sec"]
        if base_eps is None:
            base_eps = eps
        rows.append((
            f"fleet_bench_{app}_multihost_p{n}_d{devices_per_proc}"
            f"_f{fleet}_T{epochs}",
            payload["wall_s"] / (fleet * epochs) * 1e6,
            f"lane_epochs_per_sec={eps:.1f};"
            f"scaling_vs_1proc={eps / base_eps:.2f}x;"
            f"processes={n};devices={n * devices_per_proc};"
            f"wall_s={payload['wall_s']:.3f}",
            payload["provenance"]))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fleet", type=int, default=32)
    ap.add_argument("--epochs", type=int, default=300)
    ap.add_argument("--app", default="cq_small")
    ap.add_argument("--baseline-epochs", type=int, default=40)
    ap.add_argument("--scenario-batched", action="store_true",
                    help="also time the params-vmapped heterogeneous-"
                         "scenario fleet (dsdps.scenarios 'mixed')")
    ap.add_argument("--broadcast-invariant", action="store_true",
                    help="also time the per-leaf broadcast variant of the "
                         "scenario-batched fleet (invariant leaves "
                         "single-copy, in_axes=None) and report stacked-vs-"
                         "broadcast lane-epochs/sec + params memory "
                         "(implies --scenario-batched)")
    ap.add_argument("--sharded", action="store_true",
                    help="also time the mesh-sharded fleet (fleet axis "
                         "over every visible device via shard_map, "
                         "launch.mesh.make_fleet_mesh) and record "
                         "lane-epochs/sec for vmap vs sharded")
    ap.add_argument("--lifecycle", action="store_true",
                    help="also time per-lane early stopping + compaction "
                         "vs the fixed grid on a plateauing fleet and "
                         "record executed lane-epochs, savings, and the "
                         "final-reward gap")
    ap.add_argument("--guards", action="store_true",
                    help="also re-time the seed-only fleet run inside the "
                         "runtime tracing-discipline guards "
                         "(repro.diagnostics.guards) and record the "
                         "steady-state overhead vs the unguarded warm run")
    ap.add_argument("--streaming", action="store_true",
                    help="also run the replay-free streaming lanes "
                         "(stream_q/stream_ac) against their replay "
                         "counterparts (dqn/ddpg) and record reward "
                         "parity, per-lane carry bytes (zero replay "
                         "bytes), and the fleet-width ceiling moving")
    ap.add_argument("--streaming-fleet", type=int, default=4,
                    help="fleet width of the --streaming comparison runs "
                         "(memory rows are per-lane, so small is fine)")
    ap.add_argument("--graph", action="store_true",
                    help="also run the structural (DAG-shape) fleet: "
                         "graph_policy vs ddpg on the same dag_shapes "
                         "scenario lanes (chain/diamond/wide-fanout padded "
                         "into one envelope), asserting the heterogeneous-"
                         "DAG fleet compiles exactly once and — in full "
                         "runs — per-topology tail-latency parity >= 0.95; "
                         "with --smoke this runs ONLY the small graph lane "
                         "(the CI graph smoke job)")
    ap.add_argument("--graph-fleet", type=int, default=6,
                    help="fleet width of the --graph comparison runs "
                         "(lanes round-robin over the structural "
                         "topologies, so a multiple of 3 covers them "
                         "evenly)")
    ap.add_argument("--multihost", action="store_true",
                    help="also run the multi-host scaling sweep: launch "
                         "1/2/4 localhost worker processes joined into one "
                         "jax.distributed job over a process-spanning "
                         "fleet mesh (CPU device emulation) and record "
                         "lane-epochs/sec + scaling per process count")
    ap.add_argument("--smoke", action="store_true",
                    help="shrink the --multihost sweep to 1/2 processes "
                         "(the CI multihost-smoke job); with --graph, run "
                         "only a small structural lane (the CI graph "
                         "smoke job)")
    ap.add_argument("--multihost-devices", type=int, default=2,
                    help="emulated CPU devices per worker process in the "
                         "--multihost sweep")
    ap.add_argument("--multihost-worker", action="store_true",
                    help=argparse.SUPPRESS)       # internal: one mh rank
    ap.add_argument("--worker-out", default=None,
                    help=argparse.SUPPRESS)       # internal: rank-0 result
    ap.add_argument("--json", default=str(DEFAULT_JSON),
                    help="benchmark JSON artifact path ('' disables)")
    args = ap.parse_args()
    if args.multihost and jax.default_backend() != "cpu":
        # the sweep's workers are forced onto JAX_PLATFORMS=cpu with
        # emulated host devices, and this process would hold the chip:
        # its rows would mix CPU emulation with chip timings
        ap.error("--multihost emulates hosts on the CPU (workers run with "
                 "JAX_PLATFORMS=cpu and forced host devices); run it with "
                 f"JAX_PLATFORMS=cpu, not on {jax.default_backend()}")
    if args.multihost_worker:
        run_multihost_worker(args.fleet, args.epochs, args.app,
                             args.worker_out)
        return
    graph_only = args.graph and args.smoke
    rows = [] if graph_only else run_all(
        args.fleet, args.epochs, args.app, args.baseline_epochs,
        args.scenario_batched, args.broadcast_invariant,
        args.sharded, args.lifecycle, args.guards)
    if args.streaming and not graph_only:
        rows += run_streaming(args.streaming_fleet, args.epochs, args.app)
    if args.graph:
        rows += run_graph(3 if args.smoke else args.graph_fleet,
                          8 if args.smoke else args.epochs, smoke=args.smoke)
    if args.multihost:
        rows += run_multihost(args.fleet, args.epochs, args.app,
                              smoke=args.smoke,
                              devices_per_proc=args.multihost_devices,
                              json_path=args.json)
    print("name,us_per_call,derived")
    for row in rows:
        name, us, derived = row[:3]
        print(f"{name},{us:.1f},{derived}", flush=True)
    if args.json:
        out = pathlib.Path(args.json)
        out.parent.mkdir(parents=True, exist_ok=True)
        prov = provenance()
        out.write_text(json.dumps(
            [{"name": r[0], "us_per_call": round(r[1], 2), "derived": r[2],
              "provenance": (r[3] if len(r) > 3 else prov)}
             for r in rows], indent=2))
        print(f"wrote {out}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
