"""DDPG (Algorithm 1), DQN baseline, model-based baseline — learning
machinery correctness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DDPGConfig, DQNConfig, ModelBasedScheduler,
                        ddpg_init, dqn_init, round_robin)
from repro.core import ddpg, dqn
from repro.core.replay import pick, replay_add, replay_init, replay_sample
from repro.dsdps import SchedulingEnv, apps
from repro.dsdps.apps import default_workload


@pytest.fixture(scope="module")
def small_env():
    topo = apps.continuous_queries("small")
    return SchedulingEnv(topo, default_workload(topo))


def test_replay_ring_buffer_semantics():
    buf = replay_init(4, 3, 2)
    for i in range(6):
        buf = replay_add(buf, jnp.full(3, i), jnp.full(2, i),
                         jnp.float32(i), jnp.full(3, i + 1))
    assert int(buf.size) == 4
    assert int(buf.ptr) == 2
    # oldest entries (0, 1) were overwritten by (4, 5)
    stored = set(float(r) for r in buf.rewards)
    assert stored == {2.0, 3.0, 4.0, 5.0}
    s, a, r, sn = replay_sample(jax.random.PRNGKey(0), buf, 16)
    assert s.shape == (16, 3) and r.shape == (16,)


@pytest.mark.parametrize("batch,moves", [
    (32, 1000),     # the DQN's H = 32 over 1000 moves
    (4, 1),         # one move: the sum has a single term
])
def test_q_of_taken_move_pick_matches_take_along_axis(batch, moves):
    """Q(s, a) as the DQN update reads it: value and VJP equal
    ``take_along_axis``'s bit for bit."""
    kq, ka, kg = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (batch, moves))
    a = jax.random.randint(ka, (batch,), 0, moves)
    g = jax.random.normal(kg, (batch,))

    def gather(q):
        return jnp.take_along_axis(q, a[:, None], axis=-1)[:, 0]

    got, got_vjp = jax.vjp(lambda q: pick(q, a), q)
    want, want_vjp = jax.vjp(gather, q)

    def bits(x):
        return np.asarray(x).view(np.uint32)
    np.testing.assert_array_equal(bits(got), bits(want))
    np.testing.assert_array_equal(bits(got_vjp(g)[0]), bits(want_vjp(g)[0]))


def test_ddpg_select_action_feasible(small_env):
    env = small_env
    cfg = DDPGConfig(n_executors=env.N, n_machines=env.M,
                     state_dim=env.state_dim, k_nn=4)
    state = ddpg_init(jax.random.PRNGKey(0), cfg)
    s = env.reset(jax.random.PRNGKey(1))
    a = ddpg.select_action(jax.random.PRNGKey(2), state, cfg,
                           env.state_vector(s), explore=False,
                           exact_host_knn=True)
    from repro.core.spaces import is_feasible
    assert bool(is_feasible(a))
    a2 = ddpg.select_action_jit(jax.random.PRNGKey(2), state, cfg,
                                env.state_vector(s), explore=False)
    assert bool(is_feasible(a2))


def test_ddpg_update_reduces_critic_loss(small_env):
    env = small_env
    cfg = DDPGConfig(n_executors=env.N, n_machines=env.M,
                     state_dim=env.state_dim, k_nn=4, lr_critic=3e-3)
    key = jax.random.PRNGKey(0)
    state = ddpg_init(key, cfg)
    # fill replay with synthetic transitions having a learnable value fn
    for i in range(80):
        k = jax.random.fold_in(key, i)
        s = jax.random.uniform(k, (cfg.state_dim,))
        a = jax.random.uniform(k, (cfg.action_dim,))
        r = -s.mean()
        state = ddpg.store(state, s, a, r, s)
    losses = []
    for i in range(60):
        state, aux = ddpg.update_step(jax.random.fold_in(key, 1000 + i),
                                      state, cfg)
        losses.append(float(aux["critic_loss"]))
    assert np.mean(losses[-10:]) < np.mean(losses[:10])


def test_ddpg_target_network_soft_update(small_env):
    env = small_env
    cfg = DDPGConfig(n_executors=env.N, n_machines=env.M,
                     state_dim=env.state_dim, k_nn=2)
    state = ddpg_init(jax.random.PRNGKey(0), cfg)
    for i in range(3):
        k = jax.random.fold_in(jax.random.PRNGKey(1), i)
        s = jax.random.uniform(k, (cfg.state_dim,))
        state = ddpg.store(state, s, jax.random.uniform(k, (cfg.action_dim,)),
                           jnp.float32(-1.0), s)
    w_before = state.target_critic.weights[0]
    state2, _ = ddpg.update_step(jax.random.PRNGKey(2), state, cfg)
    w_after = state2.target_critic.weights[0]
    online = state2.critic.weights[0]
    expected = (1 - cfg.tau) * w_before + cfg.tau * online
    np.testing.assert_allclose(np.asarray(w_after), np.asarray(expected),
                               rtol=1e-5, atol=1e-6)


def test_ddpg_reward_statistics_survive_the_update(small_env):
    """store → update_step → store: the running reward statistics carry
    over the update, so the second reward is standardized against the
    first and is not stored as (r − r) / 1e-4 = 0."""
    env = small_env
    cfg = DDPGConfig(n_executors=env.N, n_machines=env.M,
                     state_dim=env.state_dim, k_nn=2)
    state = ddpg_init(jax.random.PRNGKey(0), cfg)
    s = jnp.zeros((cfg.state_dim,))
    a = jnp.zeros((cfg.action_dim,))
    state = ddpg.store(state, s, a, jnp.float32(-3.0), s)
    mean1, var1 = float(state.r_mean), float(state.r_var)
    state, _ = ddpg.update_step(jax.random.PRNGKey(1), state, cfg)
    assert int(state.r_count) == 1
    assert (float(state.r_mean), float(state.r_var)) == (mean1, var1)
    state = ddpg.store(state, s, a, jnp.float32(-5.0), s)
    assert int(state.r_count) == 2
    assert float(state.r_mean) != mean1 and float(state.r_var) != var1
    assert float(state.replay.rewards[1]) != 0.0


def test_ddpg_offline_pretrain_keeps_its_reward_statistics(small_env):
    """The statistics offline_pretrain sets over its samples survive its
    update scan: r_count is the number of offline samples."""
    env = small_env
    cfg = DDPGConfig(n_executors=env.N, n_machines=env.M,
                     state_dim=env.state_dim, k_nn=2, buffer=16)
    state = ddpg_init(jax.random.PRNGKey(0), cfg)
    state = ddpg.offline_pretrain(jax.random.PRNGKey(1), state, cfg, env,
                                  n_samples=24, n_updates=3)
    assert int(state.r_count) == 24
    assert float(state.r_mean) != 0.0 and float(state.r_var) != 1.0


def test_dqn_move_semantics():
    X = jax.nn.one_hot(jnp.array([0, 1, 2]), 4)
    X2 = dqn.apply_move(X, jnp.asarray(1 * 4 + 3), 4)  # executor 1 -> machine 3
    assert int(X2[1].argmax()) == 3
    assert int(X2[0].argmax()) == 0 and int(X2[2].argmax()) == 2


def test_dqn_update_runs(small_env):
    env = small_env
    cfg = DQNConfig(n_executors=env.N, n_machines=env.M,
                    state_dim=env.state_dim)
    key = jax.random.PRNGKey(0)
    state = dqn_init(key, cfg)
    for i in range(40):
        k = jax.random.fold_in(key, i)
        s = jax.random.uniform(k, (cfg.state_dim,))
        state = dqn.store(state, s, i % cfg.num_actions, jnp.float32(-2.0), s)
    state, aux = dqn.update_step(jax.random.PRNGKey(1), state, cfg)
    assert np.isfinite(float(aux["loss"]))


def test_model_based_predictor_correlates(small_env):
    env = small_env
    sched = ModelBasedScheduler(env).fit(jax.random.PRNGKey(0), n_samples=250)
    w = env.workload.init()
    preds, trues = [], []
    for i in range(40):
        X = env.random_assignment(jax.random.PRNGKey(1000 + i))
        preds.append(float(sched.predict(X, w)))
        trues.append(float(env.evaluate(X, w)))
    r = np.corrcoef(preds, trues)[0, 1]
    assert r > 0.6, f"model-based predictor correlation too low: {r:.3f}"


def test_model_based_schedule_beats_round_robin(small_env):
    env = small_env
    sched = ModelBasedScheduler(env).fit(jax.random.PRNGKey(0), n_samples=250)
    w = env.workload.init()
    X = sched.schedule(w, sweeps=2)
    rr = float(env.evaluate(env.round_robin_assignment(), w))
    mb = float(env.evaluate(X, w))
    assert mb < rr * 1.02   # at least matches RR (usually clearly better)


def test_model_based_no_retrace_across_calls():
    """Regression: ``fit`` used to build a fresh ``jax.jit`` wrapper per
    call and ``schedule`` re-defined + re-jitted its move search per call —
    every invocation retraced.  Both now go through module-level jitted
    programs; the diagnostics jit-cache-miss sentinel must see exactly one
    compilation each on first use and ZERO across repeat calls with the
    same static args."""
    from repro.core import model_based as mb
    from repro.diagnostics import CompileCounter
    # fresh env instance => fresh static jit key => compilation is observable
    topo = apps.continuous_queries("small")
    env = SchedulingEnv(topo, default_workload(topo))
    w = env.workload.init()
    with CompileCounter(mb._fit_theta_jit, label="fit") as cc_fit, \
            CompileCounter(mb.sweep_schedule, label="schedule") as cc_sched:
        sched = ModelBasedScheduler(env).fit(jax.random.PRNGKey(0),
                                             n_samples=50)
        X1 = sched.schedule(w, sweeps=2)
    cc_fit.assert_compiles(1)
    cc_sched.assert_compiles(1)
    # same static args (env, n_samples, sweeps), new traced values: the
    # cached executables run without re-tracing
    with CompileCounter(mb._fit_theta_jit, mb.sweep_schedule,
                        label="repeat") as cc:
        sched.fit(jax.random.PRNGKey(1), n_samples=50)
        X2 = sched.schedule(w * 1.1, sweeps=2)
        X3 = sched.schedule(w, X0=X1, sweeps=2)
    cc.assert_compiles(0)
    assert X2.shape == X1.shape == X3.shape


def test_ddpg_select_pallas_knn_matches_default(small_env,
                                                interpreted_knn_kernel):
    """The Pallas-backed K-NN projection is a drop-in for the XLA row
    top-2 inside the DDPG select path (interpret mode on CPU)."""
    env = small_env
    kw = dict(n_executors=env.N, n_machines=env.M,
              state_dim=env.state_dim, k_nn=4)
    cfg = DDPGConfig(**kw)
    cfg_pl = DDPGConfig(**kw, use_pallas_knn=True)
    state = ddpg_init(jax.random.PRNGKey(0), cfg)
    s = env.reset(jax.random.PRNGKey(1))
    a = ddpg.select_action(jax.random.PRNGKey(2), state, cfg,
                           env.state_vector(s), explore=False)
    a_pl = ddpg.select_action(jax.random.PRNGKey(2), state, cfg_pl,
                              env.state_vector(s), explore=False)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(a_pl))


def test_round_robin_skips_dead_machines():
    X = round_robin(10, 4, alive=np.array([True, False, True, True]))
    used = set(np.asarray(X).argmax(-1).tolist())
    assert 1 not in used
    assert np.allclose(np.asarray(X).sum(-1), 1.0)
