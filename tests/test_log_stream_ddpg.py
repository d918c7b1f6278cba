"""The paper's actor-critic agent on its log-stream deployment, against
the benchmark's plain reference (``bench/reference.py``), on the CPU.

The benchmark's files are loaded by path, as ``bench/tests`` loads them,
so that the comparison that decides the ``log_stream.train_ddpg`` cell's
``correct`` is part of these tests: the reference's latency model against
the simulator on the forking topology, one DDPG update against the
program's, and a small copy of the cell's training job, which passes its
check and fails it once the reward statistics are dropped by the update
again."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ddpg, make_agent
from repro.dsdps import SchedulingEnv, apps

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
CELL = "log_stream.train_ddpg"
# the CPU computes float32 products in full, so program and reference
# agree to round-off here (sound runs read under 1e-6 on every number)
CPU_LIMITS = {"lat_gap": 1e-5, "moved_mismatch": 0, "infeasible": 0,
              "q_gap_mean": 1e-3, "d_gap_mean": 1e-3, "update_gap": 1e-3}


def _load(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bench():
    """(the reference, the cell's job module, the configuration, the
    cell's traffic file)."""
    cell = json.loads((BENCH / "workloads" / f"{CELL}.json").read_text())
    job = _load(BENCH / "jobs" / f"{cell['job']}.py")   # puts bench/ on sys.path
    cfg = json.loads((BENCH / "configs" / "log_stream.json").read_text())
    return job.reference, job, cfg, cell


@pytest.fixture(scope="module")
def env():
    topo = apps.ALL_APPS["log_stream"]()
    return SchedulingEnv(topo, apps.default_workload(topo))


@pytest.mark.parametrize("seed,rate", [(0, 1.0), (1, 0.8), (2, 1.2),
                                       (3, 1.4)])
def test_reference_latency_is_the_simulators(bench, env, seed, rate):
    """The reference's queueing model gives the simulator's latency on the
    forking topology (both branches' completion joined by a max)."""
    reference, _, cfg, _ = bench
    dep = reference.Deployment(cfg)
    X = jax.nn.one_hot(jax.random.randint(jax.random.PRNGKey(seed),
                                          (env.N,), 0, env.M), env.M)
    w = env.default_params().base_rates * rate
    ref = reference.latency_ms(dep, X, w,
                               jnp.asarray(dep.service, jnp.float32),
                               jnp.asarray(dep.speed, jnp.float32))
    assert float(env.evaluate(X, w)) == float(ref)


def test_reference_ddpg_update_is_the_programs(bench, env):
    """One update of the reference and the program's ``update_step``, from
    the same state and replay, move the online nets alike."""
    reference, _, cfg, _ = bench
    a = cfg["agents"]["ddpg"]
    dep = reference.Deployment(cfg)
    agent = make_agent("ddpg", env, k_nn=a["k_nn"])
    key = jax.random.PRNGKey(3)
    prog = ddpg.init_state(key, agent.cfg)
    ref = reference.init_ddpg(key, dep, a)
    rng = np.random.default_rng(0)
    eye = np.eye(dep.M, dtype=np.float32)
    for _ in range(40):
        X, X2 = (eye[rng.integers(0, dep.M, dep.N)] for _ in range(2))
        w, w2 = (rng.uniform(0.8, 1.2, dep.S).astype(np.float32)
                 for _ in range(2))
        s = jnp.concatenate([X.reshape(-1), w])
        s2 = jnp.concatenate([X2.reshape(-1), w2])
        lat = float(rng.uniform(6.0, 10.0))
        prog = ddpg.store(prog, s, s2[:dep.N * dep.M], -lat, s2,
                          a["reward_scale"])
        ref = reference.store(ref, s, s2[:dep.N * dep.M], s2, lat,
                              a["reward_scale"])
    k = jax.random.PRNGKey(9)
    prog, _ = ddpg.update_step(k, prog, agent.cfg)
    ref, _ = reference.update_ddpg(k, ref, a, dep.N, dep.M)
    got = jax.tree.leaves((prog.actor, prog.critic))
    want = reference.online_leaves(ref)
    assert len(got) == len(want)
    for g, r in zip(got, want):
        # Adam turns the rounding of near-zero gradients into steps of up
        # to the learning rate; a few such entries differ by 1e-6..1e-5
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-5)
    assert int(prog.r_count) == int(ref["r_count"]) == 40


@contextlib.contextmanager
def _statistics_dropped():
    """The update rebuilds the state without the reward statistics, as
    it once did: every later reward is stored as 0."""
    orig = ddpg.update_step

    def update_step(key, state, cfg):
        new, aux = orig(key, state, cfg)
        return new._replace(**ddpg.DDPGState._field_defaults), aux
    ddpg.update_step = update_step
    jax.clear_caches()
    try:
        yield
    finally:
        ddpg.update_step = orig
        jax.clear_caches()


def _checks(bench, plant=None) -> dict:
    """{number: (value, CPU limit)} of a 4-lane, 20-epoch copy of the
    cell's job, run under ``plant``."""
    _, jobs, cfg, cell = bench
    small = dict(cell, lanes=4, epochs=20,
                 check=dict(cell["check"], limits=CPU_LIMITS))
    job = jobs.Job(cfg, small, 2 ** 31 + 23, 1, 0.0, False,
                    lambda *a: None)
    with plant() if plant else contextlib.nullcontext():
        job.setup()
        job.window()
    job.release()
    return {n: (v, lim) for n, v, lim in job.check()}


def test_small_training_job_passes_its_check(bench):
    checks = _checks(bench)
    assert set(checks) == set(CPU_LIMITS)
    assert all(v <= lim for v, lim in checks.values()), checks


def test_dropped_reward_statistics_fail_the_check(bench):
    checks = _checks(bench, _statistics_dropped)
    assert any(v > lim for v, lim in checks.values()), checks


class _Run:
    """What a metric reader sees: a hand-made trace of one fleet program
    run, 100-200 ns, whose ``%while.1`` holds the select's K-NN beam
    (10 ns), the target's beam (20 ns) and the rest of the target (30 ns),
    over 2 jobs of 5 epochs."""

    def __init__(self):
        from reduce_trace import Device, Trace
        ops = [(100, 200, "%while.1"), (110, 120, "%fusion.1"),
               (130, 150, "%fusion.2"), (160, 190, "%fusion.3")]
        dev = Device("/device:TPU:0", ops=ops,
                     modules=[(100, 200, "jit__fleet_fn(1)")])
        self.trace = Trace(devices=[dev], host=[(0, 1000, "bench.window")])
        self.counters = {"jobs": 2, "epochs": 5}


@pytest.mark.parametrize("metric,ns", [("knn_device_ms.train", 10 + 20),
                                       ("target_device_ms.train", 20 + 30)])
def test_subscope_readers_sum_every_path_that_holds_them(bench, monkeypatch,
                                                         metric, ns):
    import repro.diagnostics
    table = {"while.1": None, "fusion.1": "knn_projection",
             "fusion.2": "critic_target/knn_projection",
             "fusion.3": "critic_target"}
    monkeypatch.setattr(repro.diagnostics, "subscope_tables",
                        lambda: {"jit__fleet_fn": table})
    read = _load(BENCH / "metrics" / f"{metric}.py").read
    assert read(_Run()) == pytest.approx(ns / 1e6 / 10)
    del table["fusion.3"]              # 30 of the program's 100 ns unnamed
    assert read(_Run()) is None


def test_subscope_readers_give_none_without_the_table(bench, monkeypatch):
    """A program without sub-scopes, as the parent of this change, gives
    no reading, and the harness leaves the metric out."""
    import repro.diagnostics
    monkeypatch.delattr(repro.diagnostics, "subscope_tables")
    for metric in ("knn_device_ms.train", "target_device_ms.train"):
        assert _load(BENCH / "metrics" / f"{metric}.py").read(_Run()) is None
