"""Serving job: decisions for many live clusters under open-loop arrivals.

The system under test is the program's ``placement`` decision plane,
built exactly as ``launch.serve_control.build_service`` builds it, with
the traffic file's clusters registered from the seed as
``serve_control`` registers them.  Requests arrive on a schedule drawn
from the seed (``bench/traffic.py``) that never waits for the plane; each
is timed from the moment it was due to the return of the
``ControlPlane.step`` that answered it.  After the window every request
due in it is waited for, up to a minute, and a sample of the decisions
drawn from the seed is compared with the reference's own choice.
"""
from __future__ import annotations

import math
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import reference  # noqa: E402
import stats      # noqa: E402
import traffic as gen  # noqa: E402

LATE_WAIT_S = 60.0          # how long past the window's close answers count


class Job:
    def __init__(self, cfg: dict, cell: dict, seed: int, chips: int,
                 seconds: float, trace: bool, log):
        self.cfg, self.cell, self.seed, self.chips, self.log = \
            cfg, cell, seed, chips, log
        self.horizon = float(cell["trace"]["seconds"]) if trace else seconds
        self.plane = None

    # -- set-up -------------------------------------------------------------
    def setup(self) -> None:
        import jax
        from repro.dsdps import SchedulingEnv, apps, scenarios
        from repro.launch.serve_control import build_service
        topo = apps.ALL_APPS[self.cfg["app"]]()
        env = SchedulingEnv(topo, apps.default_workload(topo))
        self.dep = reference.Deployment(self.cfg)
        if (env.N, env.M, env.state_dim) != (self.dep.N, self.dep.M,
                                             self.dep.state_dim):
            raise SystemExit(f"the program's {self.cfg['app']} is not the "
                             f"configured deployment")
        plane_cfg = self.cell["plane"]
        svc = build_service(env, kinds=(plane_cfg["kind"],),
                            n_slots=plane_cfg["n_slots"], seed=self.seed)
        self.plane = svc.planes[plane_cfg["kind"]]
        if self.plane.agent.cfg.k_nn != plane_cfg["k_nn"]:
            raise SystemExit("the plane's K differs from the traffic file's")
        key = jax.random.PRNGKey(self.seed)
        self.names = []
        for c in range(self.cell["clusters"]):
            key, k = jax.random.split(key)
            self.names.append(f"cluster-{c}")
            self.plane.register_cluster(self.names[-1],
                                        scenarios.sample_perturbed(env, k))
        rng = np.random.default_rng([self.seed, 12])
        req = self.cell["requests"]
        self.due = gen.arrivals(rng, self.cell["arrivals"], self.horizon)
        n = len(self.due) + self.cell["warmup_requests"]
        self.X, self.w = gen.placement_states(
            rng, n, self.dep.N, self.dep.M, self.dep.S, req["load_sigma"])
        self.keys = np.asarray(jax.random.split(
            jax.random.fold_in(jax.random.PRNGKey(self.seed), 7), 4096))
        self.step_no = 0
        # warm-up: every shape the window uses (the plane's one program
        # and its host path), on requests that are not the window's
        warm = list(range(len(self.due), n))
        for rid in warm:
            self._submit(rid)
        while self.plane.pending:
            self._step()
        self.plane.reset_stats()

    def _submit(self, rid: int) -> None:
        from repro.serve.control import DecisionRequest
        self.plane.submit(DecisionRequest(
            rid=rid, cluster=self.names[rid % len(self.names)],
            s_vec=gen.state_vector(self.X[rid], self.w[rid], self.dep.M)))

    def _step(self):
        served = self.plane.step(self.keys[self.step_no % len(self.keys)])
        self.step_no += 1
        return served

    # -- the measured window ---------------------------------------------------
    def _serve(self, seconds: float, annotate=None) -> dict:
        """Offer the requests due in [0, seconds) on schedule, serve them,
        and wait for the stragglers; returns latencies and counters."""
        due = self.due[self.due < seconds]
        n = len(due)
        rng = np.random.default_rng([self.seed, 13])
        sample = set(rng.choice(n, size=min(n, self.cell["check"]["sample"]),
                                replace=False).tolist())
        answered = [None] * n
        times = np.zeros(n)
        submitted = np.zeros(n)
        kept = {}
        dispatches = served_total = 0
        step_s = []
        plane, clock = self.plane, time.perf_counter
        i = 0
        t0 = clock()
        while True:
            now = clock() - t0
            while i < n and due[i] <= now:
                self._submit(i)
                submitted[i] = clock() - t0
                i += 1
            if plane.pending:
                a = clock()
                if annotate:
                    with annotate("bench.step"):
                        served = self._step()
                else:
                    served = self._step()
                b = clock()
                if served:
                    dispatches += 1
                    served_total += len(served)
                    step_s.append(b - a)
                for r in served:
                    rid = r.rid
                    if answered[rid] is None:
                        answered[rid] = 1
                        times[rid] = b - t0
                    else:
                        answered[rid] += 1
                    if rid in sample and rid not in kept:
                        kept[rid] = np.array(r.action)
            elif i < n:
                gap = due[i] - now
                if gap > 2e-3:
                    time.sleep(gap - 1e-3)
            else:
                break
            if now > seconds + LATE_WAIT_S:
                break
        lat = stats.due_latencies_ms(
            due, [times[r] if answered[r] else None for r in range(n)])
        late = (submitted[:i] - due[:i]) * 1e3
        self.log(f"generator lateness ms: p50 {np.percentile(late, 50):.4f} "
                 f"p99 {np.percentile(late, 99):.4f} over {i} requests")
        self.log(f"answered {sum(1 for a in answered if a)} of {n}; "
                 f"{dispatches} dispatches, {served_total} decisions")
        self._result = {"n": n, "answered": answered, "kept": kept}
        missing = sum(1 for a in answered if not a)
        return {"latencies_ms": lat, "attempted": n, "failed": missing,
                "counters": {"dispatches": dispatches,
                             "served": served_total,
                             "n_slots": plane.n_slots,
                             "step_s": step_s,
                             "program": plane.program}}

    def window(self) -> dict:
        out = self._serve(self.horizon)
        lat = out["latencies_ms"]
        p99 = stats.nearest_rank_percentile(lat, 99.0)
        self.log(f"decision latency ms: p50 "
                 f"{stats.nearest_rank_percentile(lat, 50.0):.4f} p99 "
                 f"{p99:.4f} over {len(lat)} decisions")
        return {"metrics": {"decision_p99_ms": p99 if math.isfinite(p99)
                            else None},
                "attempted": out["attempted"], "failed": out["failed"],
                "counters": out["counters"]}

    def traced(self, trace_dir: str) -> dict:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = int(self.cell["trace"]["python_tracer"])
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                out = self._serve(self.horizon,
                                  annotate=jax.profiler.TraceAnnotation)
        finally:
            jax.profiler.stop_trace()
        return out

    def release(self) -> None:
        self.plane = None

    # -- correctness ----------------------------------------------------------
    def check(self) -> list:
        """(name, value, limit) of every compared number: requests due in
        the window never answered, answered twice, and, over the sample,
        decisions that are not one-hot assignments, and the mean gap below
        the reference critic's best of its K candidates (a share of their
        spread of Q)."""
        import jax
        import jax.numpy as jnp
        res, lim = self._result, self.cell["check"]["limits"]
        missing = sum(1 for a in res["answered"] if not a)
        dup = sum(1 for a in res["answered"] if a and a > 1)
        rids = sorted(res["kept"])
        s = jnp.asarray(gen.state_vector(self.X[rids], self.w[rids],
                                         self.dep.M))
        chosen = jnp.asarray(np.stack([res["kept"][r] for r in rids]))
        agent = dict(self.cfg["agents"]["ddpg"], k_nn=self.cell["plane"]["k_nn"])
        st = jax.jit(lambda k: reference.init_ddpg(k, self.dep, agent))(
            jax.random.PRNGKey(self.seed))
        N, M, k = self.dep.N, self.dep.M, agent["k_nn"]
        q_gap, _, bad = jax.jit(jax.vmap(
            lambda sv, a: reference.score_choice(
                st["actor"], st["critic"], sv, a, k, N, M,
                agent["knn_pools"])))(s, chosen)
        return [("missing", missing, lim["missing"]),
                ("duplicates", dup, lim["duplicates"]),
                ("infeasible", int(np.asarray(bad).sum()),
                 lim["infeasible"]),
                ("q_gap_mean", float(jnp.mean(q_gap)), lim["q_gap_mean"])]
