"""Faults planted under the timed path, and the controls, for the tests
and for reading them on the chip.

Each plant is a context manager that patches the program in this process
(nothing on disk changes) and clears jax's caches on the way in and out,
so that programs traced before or under it are not reused.  The controls
break one guarantee the configuration states:

* serving: a decision is the nearest assignment to the actor's
  proto-action, with the critic's scoring of the K candidates skipped;
* training: every update draws half the stated minibatch, the mean taken
  over the rest.

Run on the chip (one process; prints one JSON line per reading):

  python3 bench/tests/plants.py --workload word_count.serve --seeds 1,2,3
  python3 bench/tests/plants.py --workload cq_large.train_dqn --seeds 1,2,3
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@contextlib.contextmanager
def patched(obj, name: str, value):
    import jax
    old = getattr(obj, name)
    setattr(obj, name, value)
    jax.clear_caches()
    try:
        yield
    finally:
        setattr(obj, name, old)
        jax.clear_caches()


# -- serving ------------------------------------------------------------------
def serve_answer_altered():
    """Every decision is altered: each executor moves to the next
    machine."""
    import jax.numpy as jnp
    from repro.serve import control
    orig = control.batched_select_program

    def program(*a, **k):
        inner = orig(*a, **k)

        def fn(*args):
            return jnp.roll(inner(*args), 1, axis=-1)
        fn.__name__ = inner.__name__
        return fn
    return patched(control, "batched_select_program", program)


def serve_half_batch():
    """Every step answers only the first half of the requests it served."""
    from repro.serve import control
    orig = control.ControlPlane.step

    def step(self, key):
        served = orig(self, key)
        return served[: (len(served) + 1) // 2]
    return patched(control.ControlPlane, "step", step)


# -- training -----------------------------------------------------------------
def train_state_unchanged():
    """Every fleet job returns the agent states it was given."""
    from repro.core import agent
    orig = agent.run_fleet_chunk

    def chunk(keys, states, *a, **k):
        out = orig(keys, states, *a, **k)
        return (states,) + tuple(out[1:])
    return patched(agent, "run_fleet_chunk", chunk)


def train_half_batch():
    """Every update draws half its minibatch and repeats it (the control:
    the mean is taken over half the stated H)."""
    import jax.numpy as jnp
    from repro.core import ddpg, dqn, replay
    orig = replay.replay_sample

    def sample(key, buf, batch):
        half = orig(key, buf, batch // 2)
        return tuple(jnp.concatenate([x, x]) for x in half)
    stack = contextlib.ExitStack()
    stack.enter_context(patched(dqn, "replay_sample", sample))
    stack.enter_context(patched(ddpg, "replay_sample", sample))
    return stack


def train_answer_altered():
    """Every chosen move (dqn) or assignment (ddpg) is altered where it is
    produced: the next move index; the first executor on the next
    machine."""
    import jax.numpy as jnp
    from repro.core import ddpg, dqn
    sel_q, sel_d = dqn.select_move, ddpg.select_action

    def move(key, state, cfg, *a, **k):
        return (sel_q(key, state, cfg, *a, **k) + 1) % cfg.num_actions

    def assign(*a, **k):
        x = sel_d(*a, **k)
        return x.at[0].set(jnp.roll(x[0], 1))
    stack = contextlib.ExitStack()
    stack.enter_context(patched(dqn, "select_move", move))
    stack.enter_context(patched(ddpg, "select_action", assign))
    return stack


SERVE_FAULTS = {"answer_altered": serve_answer_altered,
                "half_batch": serve_half_batch}
TRAIN_FAULTS = {"state_unchanged": train_state_unchanged,
                "half_batch": train_half_batch,
                "answer_altered": train_answer_altered}


def serve_control_readings(job) -> dict:
    """The serving control, put in the program's place over the window's
    sampled requests: the nearest assignment to each proto-action, scored
    by the reference like the program's decisions."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    sys.path.insert(0, str(BENCH))
    import reference
    import traffic as gen
    rids = sorted(job._result["kept"])
    s = jnp.asarray(gen.state_vector(job.X[rids], job.w[rids], job.dep.M))
    agent = dict(job.cfg["agents"]["ddpg"], k_nn=job.cell["plane"]["k_nn"])
    st = reference.init_ddpg(jax.random.PRNGKey(job.seed), job.dep, agent)
    N, M, k = job.dep.N, job.dep.M, agent["k_nn"]

    def one(sv):
        proto = reference.actor_proto(st["actor"], sv, N, M)
        nearest = jax.nn.one_hot(proto.argmax(1), M)
        return reference.score_choice(st["actor"], st["critic"], sv,
                                      nearest, k, N, M,
                                      agent["knn_pools"])[0]
    return {"q_gap_mean": float(np.mean(jax.jit(jax.vmap(one))(s)))}


# -- reading on the chip ------------------------------------------------------
def load_job(workload: str):
    """(Job class, configuration, traffic file, cell entry) of a cell; a
    cell held out of ``BENCHMARK.json`` is read from its files alone
    (configuration ``<config>.`` of its name, one chip)."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = workload.split(".", 1)[0]
    cell = {c["name"]: c for c in spec["workloads"]}.get(
        workload, {"name": workload, "config": config, "chips": 1})
    traffic = json.loads((BENCH / "workloads" / f"{workload}.json")
                         .read_text())
    cfg = json.loads((BENCH / "configs" / f"{cell['config']}.json")
                     .read_text())
    path = BENCH / "jobs" / f"{traffic['job']}.py"
    mod_spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.Job, cfg, traffic, cell


def read(workload: str, seed: int, seconds: float, plant=None, log=None):
    """One run of the cell (set-up, window, check) under ``plant``;
    returns its checks as {name: value} and the job."""
    Job, cfg, traffic, cell = load_job(workload)
    job = Job(cfg, traffic, seed, cell["chips"], seconds, False,
              log or (lambda *a: None))
    with plant() if plant else contextlib.nullcontext():
        job.setup()
        job.window()
    job.release()
    return {n: v for n, v, _ in job.check()}, job


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="read the planted faults and "
                                 "the control of a cell on this machine")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    serve = args.workload.endswith(".serve")
    faults = SERVE_FAULTS if serve else TRAIN_FAULTS
    for seed in (int(s) for s in args.seeds.split(",")):
        checks, job = read(args.workload, seed, args.seconds)
        print(json.dumps({"seed": seed, "plant": None, "checks": checks}),
              flush=True)
        if serve:
            print(json.dumps({"seed": seed, "plant": "control",
                              "checks": serve_control_readings(job)}),
                  flush=True)
        for name, plant in faults.items():
            checks, _ = read(args.workload, seed, args.seconds, plant)
            print(json.dumps({"seed": seed, "plant": name,
                              "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
