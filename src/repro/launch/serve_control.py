"""Serving control-plane launcher: batched decisions for many clusters.

Builds a :class:`~repro.serve.control.ControlService` over the requested
decision kinds (``core/spaces.py`` action spaces — placement is served by
a fresh or supplied agent, rate_control / auto_tune by their registered
policy agents), registers ``--clusters`` perturbed live clusters
(``dsdps.scenarios.sample_perturbed``), drives a synthetic request load
through it, and reports per-kind p50/p99 decision latency and
decisions/sec.  ``--guards`` runs steady-state serving under the runtime
tracing-discipline guards with a CompileCounter assertion that NO
recompilation happens after warmup.

  PYTHONPATH=src python -m repro.launch.serve_control --app cq_small \\
      --clusters 6 --requests 48 --slots 8 --guards
  PYTHONPATH=src python -m repro.launch.serve_control \\
      --kinds placement,rate_control --clusters 3 --requests 24

``drl_control --serve N`` reuses :func:`build_service` /
:func:`synthetic_requests` to serve N decisions from the freshly TRAINED
policy, with each training lane's scenario registered as a cluster."""
from __future__ import annotations

import argparse
import contextlib
import time

import jax
import numpy as np

from repro.core import make_agent, spaces
from repro.dsdps import SchedulingEnv, apps, scenarios
from repro.dsdps.apps import default_workload
from repro.launch.compile_cache import enable_compile_cache
from repro.serve.control import ControlPlane, ControlService, DecisionRequest

DEFAULT_KINDS = ("placement", "rate_control", "auto_tune")


def build_service(env, kinds=DEFAULT_KINDS, n_slots: int = 8, seed: int = 0,
                  placement_agent=None, placement_state=None,
                  donate: bool | None = None) -> ControlService:
    """One plane per decision kind; each kind's registered default agent,
    except ``placement`` which may be served by a supplied (trained)
    agent + state."""
    planes = {}
    for kind in kinds:
        space = spaces.action_space(kind)
        if kind == "placement" and placement_agent is not None:
            ag, st = placement_agent, placement_state
        else:
            overrides = {"k_nn": 8} if space.default_agent == "ddpg" else {}
            ag = make_agent(space.default_agent, env, **overrides)
            st = ag.init(jax.random.PRNGKey(seed))
        planes[kind] = ControlPlane(env, ag, st, kind=kind, n_slots=n_slots,
                                    explore=False, donate=donate)
    return ControlService(planes)


def synthetic_requests(env, svc: ControlService, n_requests: int,
                       seed: int = 0) -> list[DecisionRequest]:
    """A request mix round-robining over the service's clusters and
    kinds: random feasible assignments + lognormal-jittered spout loads,
    encoded exactly as ``SchedulingEnv.state_vector`` would."""
    rng = np.random.default_rng(seed)
    kinds = svc.kinds
    names = svc.planes[kinds[0]].clusters
    reqs = []
    for rid in range(n_requests):
        X = np.eye(env.M, dtype=np.float32)[rng.integers(0, env.M, env.N)]
        w_norm = np.exp(rng.normal(0.0, 0.25, env.workload.num_spouts))
        s_vec = np.concatenate([X.reshape(-1),
                                w_norm.astype(np.float32)])
        reqs.append(DecisionRequest(rid=rid,
                                    cluster=names[rid % len(names)],
                                    s_vec=s_vec,
                                    kind=kinds[rid % len(kinds)]))
    return reqs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--app", default="cq_small", choices=list(apps.ALL_APPS))
    ap.add_argument("--kinds", default=",".join(DEFAULT_KINDS),
                    help="comma-separated decision kinds "
                         f"(registered: {spaces.action_space_names()})")
    ap.add_argument("--clusters", type=int, default=4,
                    help="live clusters to register (perturbed scenarios)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4,
                    help="batch slots per decision plane")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--guards", action="store_true",
                    help="serve the steady state under the runtime "
                         "tracing-discipline guards + assert zero "
                         "post-warmup recompilation")
    args = ap.parse_args()
    kinds = tuple(k for k in args.kinds.split(",") if k)
    for k in kinds:
        if k not in spaces.action_space_names():
            ap.error(f"unknown decision kind {k!r}; "
                     f"registered: {spaces.action_space_names()}")
    if args.clusters < 1 or args.requests < 1:
        ap.error("--clusters and --requests must be >= 1")

    topo = apps.ALL_APPS[args.app]()
    env = SchedulingEnv(topo, default_workload(topo))
    svc = build_service(env, kinds, n_slots=args.slots, seed=args.seed)
    key = jax.random.PRNGKey(args.seed)
    for c in range(args.clusters):
        key, k = jax.random.split(key)
        svc.register_cluster(f"cluster-{c}",
                             scenarios.sample_perturbed(env, k))
    print(f"serving {len(kinds)} decision kind(s) {list(kinds)} for "
          f"{args.clusters} clusters, {args.slots} slots/plane ...")

    reqs = synthetic_requests(env, svc, args.requests, seed=args.seed)
    for r in reqs:
        svc.submit(r)
    key, k_warm = jax.random.split(key)
    warm = svc.step(k_warm)              # warmup: one compile per plane
    if args.guards:
        from repro.diagnostics import guards
        region = guards(track=svc.programs(), label="serve_control")
    else:
        region = contextlib.nullcontext(None)
    t0 = time.perf_counter()
    with region as g:
        served = svc.run(key)
    wall = time.perf_counter() - t0
    if g is not None:
        g.counter.assert_compiles(0)
        print("guards: clean — steady-state serving recompiled nothing, "
              "no implicit transfers")

    steady = len(served) - len(warm)
    print(f"served {len(served)}/{args.requests} decisions "
          f"({steady} post-warmup in {wall * 1e3:.1f} ms = "
          f"{steady / wall:.0f} decisions/sec)")
    for kind, stats in svc.decision_stats().items():
        print(f"  {kind:13s} n={stats['n']:4d}  "
              f"p50 {stats['p50_ms']:8.3f} ms  "
              f"p99 {stats['p99_ms']:8.3f} ms  "
              f"mean {stats['mean_ms']:8.3f} ms")


if __name__ == "__main__":
    enable_compile_cache()
    main()
