"""Control-plane serving microbenchmark: decision latency + throughput.

Times three ways of answering the same request load — N concurrent
per-cluster decision asks against a fixed registry of heterogeneous live
clusters (perturbed EnvParams) — with the trained-policy serving path of
``repro/serve/control.py``:

* ``sequential`` — one jitted ``Agent.select`` dispatch per request
  (:func:`~repro.serve.control.single_select_program`), the per-cluster
  baseline a naive service would run;
* ``batched`` — the :class:`~repro.serve.control.ControlPlane` slot
  scheduler: FIFO admission into a fixed slot pool, every active slot
  served in ONE vmapped dispatch that gathers each slot's cluster row
  from the broadcast-invariant params stack;
* ``batched_donated`` — the same plane with the per-step key/state-vector
  buffers donated (accelerator backends only; donation is a no-op on CPU
  and the row is marked ``donated=inactive_on_cpu``).

Every request in every path is "submitted" at t0, so queueing delay —
not just compute — lands in the reported p50/p99, exactly as a live
service would bill it.  The bench ASSERTS the acceptance contract: the
batched plane's decisions bit-match the per-cluster single selects
(explore=False) request-for-request, and batched is strictly faster per
decision than sequential.

  PYTHONPATH=src python -m benchmarks.serve_bench [--clusters 6]
      [--requests 96] [--slots 8] [--smoke]
      [--json artifacts/serve_bench.json]

Rows are ``name,us_per_call,derived`` — the benchmarks.run CSV schema
(us_per_call = microseconds per decision); the same rows are written to
the JSON artifact."""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax
import numpy as np

from repro.core import make_agent
from repro.dsdps import SchedulingEnv, apps, scenarios
from repro.dsdps.apps import default_workload
from repro.launch.compile_cache import enable_compile_cache
from repro.serve.control import (ControlPlane, DecisionRequest,
                                 latency_stats, single_select_program)

DEFAULT_JSON = pathlib.Path(__file__).resolve().parents[1] / "artifacts" / \
    "serve_bench.json"


def _request_load(env, cluster_names, n_requests: int, seed: int = 0):
    """(rid, cluster, s_vec) triples — random feasible assignments +
    lognormal-jittered spout loads, round-robined over the clusters."""
    rng = np.random.default_rng(seed)
    load = []
    for rid in range(n_requests):
        X = np.eye(env.M, dtype=np.float32)[rng.integers(0, env.M, env.N)]
        w = np.exp(rng.normal(0.0, 0.25, env.workload.num_spouts))
        s_vec = np.concatenate([X.reshape(-1), w.astype(np.float32)])
        load.append((rid, cluster_names[rid % len(cluster_names)], s_vec))
    return load


def _run_sequential(agent, state, params_by_name, load, key):
    """One jitted select per request; every request submitted at t0."""
    prog = single_select_program(agent, False)
    rid0, c0, s0 = load[0]
    key, kw = jax.random.split(key)
    np.asarray(prog(kw, state, s0, params_by_name[c0]))       # warm/compile
    actions, lats = {}, []
    t0 = time.perf_counter()
    for rid, c, s in load:
        key, k = jax.random.split(key)
        actions[rid] = np.asarray(prog(k, state, s, params_by_name[c]))
        lats.append((time.perf_counter() - t0) * 1e3)
    wall = time.perf_counter() - t0
    return actions, lats, wall


def _run_batched(env, agent, state, params_by_name, load, key,
                 n_slots: int, donate: bool):
    """The ControlPlane slot scheduler over the same load, warmed first."""
    plane = ControlPlane(env, agent, state, kind="placement",
                         n_slots=n_slots, donate=donate)
    for name, p in params_by_name.items():
        plane.register_cluster(name, p)
    key, kw = jax.random.split(key)
    for rid, c, s in load[:n_slots]:                          # warm/compile
        plane.submit(DecisionRequest(rid=-1 - rid, cluster=c, s_vec=s))
    plane.run(kw)
    plane.reset_stats()
    reqs = [DecisionRequest(rid=rid, cluster=c, s_vec=s)
            for rid, c, s in load]
    t0 = time.perf_counter()
    for r in reqs:
        plane.submit(r)
    done = plane.run(key)
    wall = time.perf_counter() - t0
    actions = {r.rid: np.asarray(r.action) for r in done}
    return actions, list(plane._latencies_ms), wall


def run_all(app: str = "cq_small", clusters: int = 6, requests: int = 96,
            slots: int = 8, seed: int = 0) -> list[tuple]:
    topo = apps.ALL_APPS[app]()
    env = SchedulingEnv(topo, default_workload(topo))
    agent = make_agent("ddpg", env, k_nn=8)
    state = agent.init(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)
    params_by_name = {}
    for c in range(clusters):
        key, k = jax.random.split(key)
        params_by_name[f"cluster-{c}"] = scenarios.sample_perturbed(env, k)
    load = _request_load(env, list(params_by_name), requests, seed)
    rows = []
    key, k_seq, k_bat, k_don = jax.random.split(key, 4)

    seq_actions, seq_lats, seq_wall = _run_sequential(
        agent, state, params_by_name, load, k_seq)
    seq = latency_stats(seq_lats)
    rows.append((f"serve_bench_{app}_sequential_c{clusters}_r{requests}",
                 seq_wall / requests * 1e6,
                 f"decisions_per_sec={requests / seq_wall:.0f};"
                 f"p50_ms={seq['p50_ms']:.3f};p99_ms={seq['p99_ms']:.3f}"))

    bat_actions, bat_lats, bat_wall = _run_batched(
        env, agent, state, params_by_name, load, k_bat, slots, donate=False)
    bat = latency_stats(bat_lats)
    bitmatch = len(bat_actions) == requests and all(
        np.array_equal(bat_actions[rid], seq_actions[rid])
        for rid, _, _ in load)
    rows.append((f"serve_bench_{app}_batched_s{slots}_c{clusters}"
                 f"_r{requests}",
                 bat_wall / requests * 1e6,
                 f"decisions_per_sec={requests / bat_wall:.0f};"
                 f"p50_ms={bat['p50_ms']:.3f};p99_ms={bat['p99_ms']:.3f};"
                 f"speedup_vs_sequential={seq_wall / bat_wall:.1f}x;"
                 f"bitmatch_vs_sequential={'ok' if bitmatch else 'FAIL'}"))

    donate = jax.default_backend() != "cpu"
    don_actions, don_lats, don_wall = _run_batched(
        env, agent, state, params_by_name, load, k_don, slots, donate=donate)
    don = latency_stats(don_lats)
    don_bitmatch = len(don_actions) == requests and all(
        np.array_equal(don_actions[rid], seq_actions[rid])
        for rid, _, _ in load)
    rows.append((f"serve_bench_{app}_batched_donated_s{slots}_c{clusters}"
                 f"_r{requests}",
                 don_wall / requests * 1e6,
                 f"decisions_per_sec={requests / don_wall:.0f};"
                 f"p50_ms={don['p50_ms']:.3f};p99_ms={don['p99_ms']:.3f};"
                 f"speedup_vs_sequential={seq_wall / don_wall:.1f}x;"
                 f"donated={'active' if donate else 'inactive_on_cpu'};"
                 f"bitmatch_vs_sequential="
                 f"{'ok' if don_bitmatch else 'FAIL'}"))

    # the acceptance contract, enforced where it is measured
    if not (bitmatch and don_bitmatch):
        raise AssertionError(
            "batched decisions do not bit-match the per-cluster single "
            "selects (explore=False) — see the FAIL row above")
    if bat_wall >= seq_wall:
        raise AssertionError(
            f"batched serving is not strictly faster per decision: "
            f"batched {bat_wall / requests * 1e6:.1f} us vs sequential "
            f"{seq_wall / requests * 1e6:.1f} us")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--app", default="cq_small", choices=list(apps.ALL_APPS))
    ap.add_argument("--clusters", type=int, default=6)
    ap.add_argument("--requests", type=int, default=96)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (<= 3 clusters, 24 requests, "
                         "4 slots)")
    ap.add_argument("--json", default=str(DEFAULT_JSON),
                    help="benchmark JSON artifact path ('' disables)")
    args = ap.parse_args()
    if args.smoke:
        args.clusters = min(args.clusters, 3)
        args.requests = min(args.requests, 24)
        args.slots = min(args.slots, 4)
    rows = run_all(args.app, args.clusters, args.requests, args.slots,
                   args.seed)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}", flush=True)
    if args.json:
        out = pathlib.Path(args.json)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(
            [{"name": n, "us_per_call": round(us, 2), "derived": d}
             for n, us, d in rows], indent=2))
        print(f"wrote {out}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
