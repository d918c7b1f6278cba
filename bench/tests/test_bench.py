"""CPU tests of the benchmark: metric arithmetic, the trace reduction on a
trace recorded on a TPU v5e, the refusal to run without a chip, the
reference against the program, and the planted faults and controls.

  JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import gzip
import json
import math
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))
sys.path.insert(0, str(ROOT / "src"))

import reduce_trace  # noqa: E402
import stats  # noqa: E402
import traffic  # noqa: E402

TRACE = BENCH / "data" / "serve_trace.xplane.pb.gz"


# -- metric arithmetic ---------------------------------------------------------
def test_nearest_rank_percentile():
    xs = list(range(1, 101))
    assert stats.nearest_rank_percentile(xs, 99.0) == 99
    assert stats.nearest_rank_percentile(xs, 50.0) == 50
    assert stats.nearest_rank_percentile([3.0], 99.0) == 3.0
    with pytest.raises(ValueError):
        stats.nearest_rank_percentile([], 50.0)


def test_p99_counts_from_due_time_and_unanswered_as_missing():
    due = [0.0, 0.1, 0.2, 0.3]
    # answered 5 ms after due, except the last, never answered
    lat = stats.due_latencies_ms(due, [0.005, 0.105, 0.205, None])
    assert lat[:3] == pytest.approx([5.0, 5.0, 5.0])
    assert math.isinf(lat[3])
    assert math.isinf(stats.nearest_rank_percentile(lat, 99.0))
    # with 200 answered requests, one missing sits beyond the 99th rank
    lat = stats.due_latencies_ms([0.0] * 200 + [0.0],
                                 [0.002] * 200 + [None])
    assert stats.nearest_rank_percentile(lat, 99.0) == pytest.approx(2.0)


def test_rate_is_taken_over_the_whole_window_per_chip():
    assert stats.rate_per_s(38400 * 5, 10.0, 1) == pytest.approx(19200.0)
    assert stats.rate_per_s(38400 * 5, 10.0, 4) == pytest.approx(4800.0)
    with pytest.raises(ValueError):
        stats.rate_per_s(1, 0.0, 1)


def test_batch_fill():
    assert stats.batch_fill(12, 2, 8) == pytest.approx(0.75)
    assert stats.batch_fill(0, 0, 8) is None


def test_poisson_arrivals_are_seeded_and_at_rate():
    a = traffic.arrivals(np.random.default_rng(5),
                         {"process": "poisson", "rate_per_s": 2000.0}, 10.0)
    b = traffic.arrivals(np.random.default_rng(5),
                         {"process": "poisson", "rate_per_s": 2000.0}, 10.0)
    assert np.array_equal(a, b)
    assert np.all(np.diff(a) >= 0) and a[-1] < 10.0
    assert abs(len(a) - 20000) < 5 * math.sqrt(20000)


def test_state_vector_is_one_hot_then_loads():
    X, w = traffic.placement_states(np.random.default_rng(0), 3, 100, 10,
                                    10, 0.25)
    s = traffic.state_vector(X, w, 10)
    assert s.shape == (3, 1010)
    onehot = s[:, :1000].reshape(3, 100, 10)
    assert np.all(onehot.sum(-1) == 1) and np.all(onehot.argmax(-1) == X)
    assert np.array_equal(s[:, 1000:], w)


# -- trace reduction -------------------------------------------------------------
def synthetic_trace():
    dev = reduce_trace.Device("/device:TPU:0")
    dev.ops = [(10, 20, "%a"), (15, 30, "%b"), (50, 60, "%a")]
    dev.modules = [(10, 30, "jit_step(1)"), (50, 60, "jit_step(1)")]
    host = [(0, 100, reduce_trace.WINDOW_SPAN), (30, 50, "bench.step"),
            (32, 48, "$control.py:260 step"), (60, 100, "bench.idle")]
    return reduce_trace.Trace(devices=[dev], host=host)


def test_busy_union_idle_share_and_gaps():
    t = synthetic_trace()
    assert t.window_s() == pytest.approx(100e-9)
    assert t.busy_s(t.devices[0]) == pytest.approx(30e-9)
    assert t.idle_share() == pytest.approx(0.7)
    assert t.idle_gaps() == [(0, 10), (30, 50), (60, 100)]
    assert t.module_runs("jit_step(") == [(10, 30), (50, 60)]
    assert t.top_ops(2) == [["%a", pytest.approx(20e-9)],
                            ["%b", pytest.approx(15e-9)]]
    by_host = dict((k, v) for k, v in t.gaps_by_host())
    assert by_host["$control.py:260 step"] == pytest.approx(20e-9)
    assert by_host["bench.idle"] == pytest.approx(40e-9)


def test_reduction_of_a_recorded_tpu_trace():
    t = reduce_trace.load_bytes(gzip.decompress(TRACE.read_bytes()))
    assert [d.name for d in t.devices] == ["/device:TPU:0"]
    assert 0.0 < t.window_s() < 5.0
    busy = t.mean_busy_s()
    assert 0.0 < busy < t.window_s()
    assert 0.0 < t.idle_share() < 1.0
    runs = t.module_runs("jit_fn(")
    assert runs and all(e > s for s, e in runs)
    ops = t.top_ops(10)
    assert len(ops) == 10 and ops[0][1] >= ops[-1][1] > 0
    gaps = t.gaps_by_host(10)
    idle = sum(hi - lo for lo, hi in t.idle_gaps()) / 1e9
    assert sum(v for _, v in t.gaps_by_host(10 ** 6)) == pytest.approx(idle)
    assert all(isinstance(k, str) and v > 0 for k, v in gaps)


# -- no chip, no result ------------------------------------------------------------
def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "cq_large.train_dqn",
         "--seed", "2147483649", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# -- the reference against the program (CPU, small) ----------------------------------
@pytest.fixture(scope="module")
def jax_cpu():
    import jax
    return jax


def _cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["cq_large", "word_count"])
def test_reference_latency_matches_the_simulator(jax_cpu, name):
    import jax
    import jax.numpy as jnp
    import reference
    from repro.dsdps import SchedulingEnv, apps
    cfg = _cfg(name)
    dep = reference.Deployment(cfg)
    topo = apps.ALL_APPS[cfg["app"]]()
    env = SchedulingEnv(topo, apps.default_workload(topo))
    for i in range(3):
        X = jax.nn.one_hot(jax.random.randint(jax.random.PRNGKey(i),
                                              (env.N,), 0, env.M), env.M)
        w = env.default_params().base_rates * (1.0 + 0.2 * i)
        got = float(env.evaluate(X, w))
        ref = float(reference.latency_ms(
            dep, X, w, jnp.asarray(dep.service, jnp.float32),
            jnp.asarray(dep.speed, jnp.float32)))
        assert got == pytest.approx(ref, rel=1e-5)


def _small(cell, **kw):
    cell = json.loads((BENCH / "workloads" / f"{cell}.json").read_text())
    cell.update(kw)
    return cell


def test_serving_job_is_correct_on_the_cpu(jax_cpu):
    import plants
    Job, cfg, _, _ = plants.load_job("word_count.serve")
    cell = _small("word_count.serve", clusters=4,
                  arrivals={"process": "poisson", "rate_per_s": 200.0})
    cell["check"]["sample"] = 128
    job = Job(cfg, cell, 2 ** 31 + 11, 1, 1.0, False, lambda *a: None)
    job.setup()
    out = job.window()
    assert out["failed"] == 0 and out["attempted"] > 100
    assert out["metrics"]["decision_p99_ms"] > 0
    job.release()
    checks = {n: (v, lim) for n, v, lim in job.check()}
    assert all(v <= lim for v, lim in checks.values()), checks
    # the control (nearest assignment, critic skipped) fails its limit
    ctl = plants.serve_control_readings(job)
    assert ctl["q_gap_mean"] > checks["q_gap_mean"][1]


def _train(cell="cq_large.train_dqn", seed=2 ** 31 + 17, plant=None):
    import plants
    Job, _, _, _ = plants.load_job("cq_large.train_dqn")
    cfg = _cfg("cq_large")
    small = _small(cell, lanes=8, epochs=300)
    job = Job(cfg, small, seed, 1, 0.0, False, lambda *a: None)
    import contextlib
    with plant() if plant else contextlib.nullcontext():
        job.setup()
        job.window()
    job.release()
    return {n: (v, lim) for n, v, lim in job.check()}


def test_dqn_training_job_is_correct_on_the_cpu(jax_cpu):
    checks = _train()
    # the chip's limits hold with room on the CPU
    assert all(v <= lim for v, lim in checks.values()), checks
    assert checks["lat_gap"][0] < 1e-5 and checks["update_gap"][0] < 1e-4


def test_ddpg_update_matches_the_program(jax_cpu):
    """One ddpg update of the reference against the program's
    ``update_step`` from the same state: the online nets agree."""
    import jax
    import jax.numpy as jnp
    import reference
    from repro.core import ddpg, make_agent
    from repro.dsdps import SchedulingEnv, apps
    cfg = _cfg("cq_large")
    a = cfg["agents"]["ddpg"]
    dep = reference.Deployment(cfg)
    topo = apps.ALL_APPS["cq_large"]()
    env = SchedulingEnv(topo, apps.default_workload(topo))
    agent = make_agent("ddpg", env, k_nn=a["k_nn"])
    key = jax.random.PRNGKey(3)
    prog = ddpg.init_state(key, agent.cfg)
    ref = reference.init_ddpg(key, dep, a)
    rng = np.random.default_rng(0)
    for _ in range(40):
        X, w = traffic.placement_states(rng, 2, dep.N, dep.M, dep.S, 0.25)
        s, s2 = (jnp.asarray(v) for v in traffic.state_vector(X, w, dep.M))
        act = s2[:dep.N * dep.M]
        lat = float(rng.uniform(2.0, 4.0))
        prog = ddpg.store(prog, s, act, -lat, s2, a["reward_scale"])
        ref = reference.store(ref, s, act, s2, lat, a["reward_scale"])
    k = jax.random.PRNGKey(9)
    prog, _ = ddpg.update_step(k, prog, agent.cfg)
    ref, _ = reference.update_ddpg(k, ref, a, dep.N, dep.M)
    got = jax.tree.leaves((prog.actor, prog.critic))
    for g, r in zip(got, reference.online_leaves(ref)):
        # Adam turns the rounding of near-zero gradients into steps of up
        # to the learning rate; a few such entries differ by 1e-6..1e-5
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-5)


# -- planted faults ----------------------------------------------------------------
@pytest.mark.parametrize("fault", ["answer_altered", "half_batch"])
def test_serving_fault_is_caught(jax_cpu, fault):
    import plants
    Job, cfg, _, _ = plants.load_job("word_count.serve")
    cell = _small("word_count.serve", clusters=4,
                  arrivals={"process": "poisson", "rate_per_s": 200.0})
    cell["check"]["sample"] = 128
    job = Job(cfg, cell, 2 ** 31 + 13, 1, 1.0, False, lambda *a: None)
    with plants.SERVE_FAULTS[fault]():
        job.setup()
        job.window()
    job.release()
    assert any(v > lim for _, v, lim in job.check())


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_training_fault_is_caught(jax_cpu, fault):
    import plants
    checks = _train(plant=plants.TRAIN_FAULTS[fault])
    assert any(v > lim for v, lim in checks.values()), checks
