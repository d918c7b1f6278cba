"""Training job: back-to-back fleet jobs, as a user's training sweep runs.

The system under test is ``core.agent.run_online_fleet``: a ``lanes``-wide
fleet of the traffic file's agent (``dqn`` or ``ddpg``) over its scenario,
``epochs`` decision epochs per job, each job continuing from the agent states the previous one
returned (its env states reset, as every launcher job's do).  Set-up
initializes the fleet from the seed through one jitted ``init_fleet``
and runs the first job, which compiles; that job is also the one the
reference follows.  The window runs whole jobs until ``--seconds`` have
passed and counts lane-epochs over the time from the first job's start to
the last job's return.
"""
from __future__ import annotations

import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import reference  # noqa: E402
import stats      # noqa: E402


class Job:
    def __init__(self, cfg: dict, cell: dict, seed: int, chips: int,
                 seconds: float, trace: bool, log):
        self.cfg, self.cell, self.seed, self.chips, self.log = \
            cfg, cell, seed, chips, log
        self.seconds = seconds
        self.states = None

    def setup(self) -> None:
        import jax
        from repro.core import make_agent
        from repro.dsdps import SchedulingEnv, apps, scenarios
        topo = apps.ALL_APPS[self.cfg["app"]]()
        self.env = env = SchedulingEnv(topo, apps.default_workload(topo))
        self.dep = reference.Deployment(self.cfg)
        a = self.agent_cfg = self.cfg["agents"][self.cell["agent"]]
        ddpg = a["name"] == "ddpg"
        self.agent = make_agent(a["name"], env,
                                **({"k_nn": a["k_nn"]} if ddpg else {}))
        c = self.agent.cfg
        lrs = ("lr_actor", "lr_critic") if ddpg else ("lr",)
        keys = ("batch", "buffer", "gamma", "tau", "reward_scale") + lrs
        if (env.N, env.M) != (self.dep.N, self.dep.M) or \
                [a[k] for k in keys] != [getattr(c, k) for k in keys] or \
                (a["eps_start"], a["eps_end"], a["eps_decay_epochs"]) != (
                    c.eps.eps_start, c.eps.eps_end, c.eps.decay_epochs):
            raise SystemExit("the program's agent or deployment is not the "
                             "configured one")
        F, self.T = self.cell["lanes"], self.cell["epochs"]
        sc = self.cell["scenario"]
        self.fleet = scenarios.build(sc["name"], env, F, seed=sc["seed"])
        base = jax.random.PRNGKey(self.seed)
        self.init_key = jax.random.fold_in(base, 1)
        self.run_key = jax.random.fold_in(base, 2)
        self.states = jax.jit(lambda k, p: self.agent.init_fleet(
            k, F, env_params=p, env=env))(self.init_key, self.fleet)
        self.job_no = 0
        rng = np.random.default_rng([self.seed, 21])
        # one lane of each of the scenario's four kinds (i % 4)
        self.lanes = [4 * int(rng.integers(0, F // 4)) + k for k in range(4)]
        hist = self._job()
        st = self.states
        T = self.T
        pick = lambda x: np.asarray(x[np.asarray(self.lanes)])  # noqa: E731
        acts = pick(st.replay.actions)[:, :T]
        self.first = {
            # what the program chose in each epoch, as its replay holds it
            "chosen": (acts.reshape(len(self.lanes), T, self.dep.N,
                                    self.dep.M) if ddpg
                       else acts[..., 0].astype(np.int32)),
            "latency": hist.latencies[self.lanes],
            "moved": hist.moved[self.lanes],
            "nets": [pick(x) for x in jax.tree.leaves(
                (st.actor, st.critic) if ddpg else st.qnet)],
            "keys": pick(self._keys(0)),
        }

    def _keys(self, job: int):
        import jax
        return jax.random.split(jax.random.fold_in(self.run_key, job),
                                self.cell["lanes"])

    def _job(self):
        from repro.core import run_online_fleet
        self.states, hist = run_online_fleet(
            self._keys(self.job_no), self.env, self.agent, self.states,
            T=self.T, env_params=self.fleet)
        self.job_no += 1
        return hist

    def _jobs(self, seconds: float = 0.0, count: int = 0,
              annotate=None) -> dict:
        """Run whole jobs, ``count`` of them or until ``seconds`` have
        passed (at least one), and time them to the last job's return."""
        import jax
        clock = time.perf_counter
        jobs, t0 = 0, clock()
        while jobs < count or (not count and (
                jobs == 0 or clock() - t0 < seconds)):
            if annotate:
                with annotate("bench.job"):
                    self._job()
            else:
                self._job()
            jobs += 1
        jax.block_until_ready(self.states)
        wall = clock() - t0
        lane_epochs = jobs * self.cell["lanes"] * self.T
        self.log(f"{jobs} jobs of {self.cell['lanes']} lanes x {self.T} "
                 f"epochs in {wall:.4f} s")
        return {"attempted": lane_epochs, "failed": 0,
                "counters": {"jobs": jobs, "epochs": self.T,
                             "lanes": self.cell["lanes"]},
                "metrics": {"lane_epochs_per_s": stats.rate_per_s(
                    lane_epochs, wall, self.chips)}}

    def window(self) -> dict:
        return self._jobs(seconds=self.seconds)

    def traced(self, trace_dir: str) -> dict:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = int(self.cell["trace"]["python_tracer"])
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                return self._jobs(count=self.cell["trace"]["jobs"],
                                  annotate=jax.profiler.TraceAnnotation)
        finally:
            jax.profiler.stop_trace()

    def release(self) -> None:
        self.states = None

    # -- correctness ----------------------------------------------------------
    def check(self) -> list:
        """The reference follows the first job of the sampled lanes,
        teacher-forced on the program's actions, and compares: the widest
        relative latency gap (env step), epochs whose moved-executor count
        differs and actions that are not one-hot (env step, select), the
        mean Q gap and distance gap of the chosen assignments (select),
        and the worst leaf's gap between the norms of the change of the
        online nets (update)."""
        import jax
        import jax.numpy as jnp
        f, lim = self.first, self.cell["check"]["limits"]
        lanes = np.asarray(self.lanes)
        lp = reference.lane_params(self.dep, self.cell["scenario"],
                                   self.cell["lanes"])
        lp = jax.tree.map(lambda x: x[lanes], lp)
        init_keys = jax.random.split(self.init_key, self.cell["lanes"])[lanes]
        agent = self.agent_cfg
        roll = jax.jit(jax.vmap(lambda lane, ik, rk, c: reference.rollout(
            self.dep, agent, lane, ik, rk, c)))
        (lat, moved, q_gap, d_gap, bad), st, g0 = roll(
            lp, init_keys, jnp.asarray(f["keys"]), jnp.asarray(f["chosen"]))
        init = reference.AGENTS[agent["name"]][0]
        st0 = jax.jit(jax.vmap(lambda k: init(k, self.dep, agent)))(init_keys)
        lat = np.asarray(lat)
        lat_gap = float(np.max(np.abs(f["latency"] - lat) / lat))
        moved_bad = int(np.sum(np.asarray(moved) != f["moved"]))
        upd, lane, leaf = update_gap(reference.online_leaves(st0),
                                     reference.online_leaves(st), f["nets"],
                                     np.asarray(g0))
        self.log(f"update_gap {upd:.6g} at lane {self.lanes[lane]} leaf {leaf}")
        # ddpg: the distance gap of the chosen assignments; dqn: exploring
        # epochs whose move is not the reference's random one
        second = (("d_gap_mean", float(np.mean(d_gap)))
                  if agent["name"] == "ddpg"
                  else ("explore_mismatch", int(np.sum(d_gap))))
        return [("lat_gap", lat_gap, lim["lat_gap"]),
                ("moved_mismatch", moved_bad, lim["moved_mismatch"]),
                ("infeasible", int(np.asarray(bad).sum()), lim["infeasible"]),
                ("q_gap_mean", float(np.nanmean(q_gap)), lim["q_gap_mean"]),
                (second[0], second[1], lim[second[0]]),
                ("update_gap", upd, lim["update_gap"])]


def update_gap(w0, ref, prog, grad0) -> tuple[float, int, int]:
    """Worst leaf, over the lanes, of |‖prog − w0‖ − ‖ref − w0‖| against the
    larger of ‖ref − w0‖ and the median leaf's.  Leaves whose first
    gradient in the reference is under a thousandth of the median leaf's
    move by round-off alone and are left out.  ``w0``/``ref``/``prog``
    are lists of [lanes, ...] arrays; ``grad0`` is [lanes, leaves].
    Returns (the gap, its lane's position, its leaf's index)."""
    worst, where = 0.0, (0, 0)
    for lane in range(grad0.shape[0]):
        moved = [float(np.linalg.norm(np.asarray(r[lane]) - np.asarray(w[lane])))
                 for w, r in zip(w0, ref)]
        got = [float(np.linalg.norm(np.asarray(p[lane]) - np.asarray(w[lane])))
               for w, p in zip(w0, prog)]
        med_g = float(np.median(grad0[lane]))
        med_m = float(np.median(moved))
        for i, (m, g) in enumerate(zip(moved, got)):
            if grad0[lane, i] < 1e-3 * med_g:
                continue
            gap = abs(g - m) / max(m, med_m)
            if gap > worst:
                worst, where = gap, (lane, i)
    return (worst, *where)
