"""Algorithm 1 — the actor-critic-based method for scheduling (paper §3.2.1).

Faithful hyper-parameters: 2×(64,32,tanh) nets, τ=0.01, γ=0.99, |B|=1000,
H=32, ε-decayed uniform exploration noise, 10k random offline samples before
online learning.  The MIQP-NN optimizer is replaced by the exact k-best
projection (core/knn_projection.py, DESIGN.md §2)."""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import api
from repro.core import networks as nets
from repro.core.exploration import EpsilonSchedule, perturb_proto
from repro.core.knn_projection import knn_actions_exact, knn_actions_jax
from repro.core.replay import Replay, replay_add, replay_init, replay_sample
from repro.diagnostics.spans import CRITIC_TARGET
from repro.train.optimizer import adam, apply_updates


@dataclasses.dataclass(frozen=True)
class DDPGConfig:
    n_executors: int
    n_machines: int
    state_dim: int
    gamma: float = 0.99          # paper
    tau: float = 0.01            # paper
    k_nn: int = 12               # K nearest feasible actions
    batch: int = 32              # paper H
    buffer: int = 1000           # paper |B|
    # actor lr < critic lr: the deterministic-policy-gradient actor drifts
    # into critic-extrapolation regions over long online runs otherwise
    lr_actor: float = 2e-4
    lr_critic: float = 1e-3
    # rewards are negative milliseconds; an affine rescale (no change to the
    # optimal policy) keeps critic targets O(1) for stable training
    reward_scale: float = 0.25
    eps: EpsilonSchedule = EpsilonSchedule()
    # route the K-NN projection's top-2/regret reduction through the Pallas
    # kernel (kernels/knn_topk) — flips the select/target hot path onto the
    # kernels layer.  The kernel compiles for TPU only.
    use_pallas_knn: bool = False

    @property
    def action_dim(self) -> int:
        return self.n_executors * self.n_machines


class DDPGState(NamedTuple):
    actor: nets.MLPParams
    critic: nets.MLPParams
    target_actor: nets.MLPParams
    target_critic: nets.MLPParams
    opt_actor: object
    opt_critic: object
    replay: Replay
    epoch: jnp.ndarray
    # running reward statistics: rewards are stored STANDARDIZED
    # ((r−mean)/std).  Latency differences between schedules are a few
    # percent of the mean, so raw centered rewards are ~1e-2 — far too
    # small a regression target for the (paper-faithful, 64/32) critic.
    # An affine reward transform never changes the optimal policy.
    r_mean: jnp.ndarray = jnp.zeros(())
    r_var: jnp.ndarray = jnp.ones(())
    r_count: jnp.ndarray = jnp.zeros((), jnp.int32)


def init_state(key: jax.Array, cfg: DDPGConfig) -> DDPGState:
    ka, kc = jax.random.split(key)
    actor = nets.init_actor(ka, cfg.state_dim, cfg.action_dim)
    critic = nets.init_critic(kc, cfg.state_dim, cfg.action_dim)
    opt_a = adam(cfg.lr_actor)
    opt_c = adam(cfg.lr_critic)
    return DDPGState(
        actor=actor,
        critic=critic,
        target_actor=actor,
        target_critic=critic,
        opt_actor=opt_a.init(actor),
        opt_critic=opt_c.init(critic),
        replay=replay_init(cfg.buffer, cfg.state_dim, cfg.action_dim),
        epoch=jnp.zeros((), jnp.int32),
    )


# --------------------------------------------------------------------------
# Action selection (lines 8-11): proto -> explore -> K-NN -> critic argmax
# --------------------------------------------------------------------------
def select_action(
    key: jax.Array,
    state: DDPGState,
    cfg: DDPGConfig,
    s_vec: jnp.ndarray,
    explore: bool = True,
    exact_host_knn: bool = False,
    k_override: int | None = None,
) -> jnp.ndarray:
    """Returns a one-hot assignment [N, M].

    ``k_override`` widens the K-NN set (deploy time uses a much larger K
    than the per-epoch loop — the exact k-best enumeration makes K=256
    essentially free, unlike the paper's per-neighbour Gurobi solve)."""
    k = k_override or cfg.k_nn
    proto = nets.apply_actor(state.actor, s_vec).reshape(
        cfg.n_executors, cfg.n_machines)
    if explore:
        eps = cfg.eps(state.epoch)
        proto = perturb_proto(key, proto, eps)
    if exact_host_knn:
        cands = jnp.asarray(knn_actions_exact(np.asarray(proto), k))
    else:
        cands = knn_actions_jax(proto, k, use_pallas=cfg.use_pallas_knn)
    q = jax.vmap(
        lambda a: nets.apply_critic(state.critic, s_vec, a.reshape(-1))
    )(cands)
    return cands[jnp.argmax(q)]


@partial(jax.jit, static_argnames=("cfg", "explore"))
def select_action_jit(key, state: DDPGState, cfg: DDPGConfig, s_vec, explore: bool = True):
    return select_action(key, state, cfg, s_vec, explore=explore,
                         exact_host_knn=False)


# --------------------------------------------------------------------------
# One learning update (lines 13-18)
# --------------------------------------------------------------------------
def _target_values(state: DDPGState, cfg: DDPGConfig, r, s_next):
    """y_i = r_i + γ max_{a∈A_K(f'(s'))} Q'(s', a)   (line 15), under the
    ``critic_target`` scope (diagnostics.SUBSCOPES)."""
    def per_sample(sv):
        proto = nets.apply_actor(state.target_actor, sv).reshape(
            cfg.n_executors, cfg.n_machines)
        cands = knn_actions_jax(proto, cfg.k_nn,
                                use_pallas=cfg.use_pallas_knn)
        q = jax.vmap(
            lambda a: nets.apply_critic(state.target_critic, sv, a.reshape(-1))
        )(cands)
        return q.max()
    with jax.named_scope(CRITIC_TARGET):
        q_next = jax.vmap(per_sample)(s_next)
        return r + cfg.gamma * q_next


@partial(jax.jit, static_argnames=("cfg",))
def update_step(key: jax.Array, state: DDPGState, cfg: DDPGConfig) -> tuple:
    s, a, r, s_next = replay_sample(key, state.replay, cfg.batch)
    y = _target_values(state, cfg, r, s_next)

    def critic_loss(cp):
        q = jax.vmap(lambda sv, av: nets.apply_critic(cp, sv, av))(s, a)
        return jnp.mean(jnp.square(y - q))

    c_loss, c_grads = jax.value_and_grad(critic_loss)(state.critic)
    opt_c = adam(cfg.lr_critic)
    c_upd, opt_c_state = opt_c.update(c_grads, state.opt_critic, state.critic)
    critic = apply_updates(state.critic, c_upd)

    def actor_loss(ap):
        # deterministic policy gradient (line 17): ascend Q(s, f(s))
        protos = jax.vmap(lambda sv: nets.apply_actor(ap, sv))(s)
        q = jax.vmap(lambda sv, pv: nets.apply_critic(critic, sv, pv))(s, protos)
        return -jnp.mean(q)

    a_loss, a_grads = jax.value_and_grad(actor_loss)(state.actor)
    opt_a = adam(cfg.lr_actor)
    a_upd, opt_a_state = opt_a.update(a_grads, state.opt_actor, state.actor)
    actor = apply_updates(state.actor, a_upd)

    # _replace: the replay, the epoch and the reward statistics carry over
    new_state = state._replace(
        actor=actor,
        critic=critic,
        target_actor=nets.soft_update(state.target_actor, actor, cfg.tau),
        target_critic=nets.soft_update(state.target_critic, critic, cfg.tau),
        opt_actor=opt_a_state,
        opt_critic=opt_c_state,
    )
    return new_state, {"critic_loss": c_loss, "actor_loss": a_loss}


def store(state: DDPGState, s, a, r, s_next,
          reward_scale: float = 1.0) -> DDPGState:
    r = r * reward_scale
    cnt = state.r_count + 1
    alpha = jnp.maximum(0.02, 1.0 / cnt.astype(jnp.float32))
    mean = state.r_mean + alpha * (r - state.r_mean)
    var = (1 - alpha) * state.r_var + alpha * jnp.square(r - mean)
    r_std = (r - mean) / jnp.maximum(jnp.sqrt(var), 1e-4)
    return state._replace(
        replay=replay_add(state.replay, s, a, jnp.clip(r_std, -10, 10),
                          s_next),
        r_mean=mean, r_var=var, r_count=cnt)


def tick(state: DDPGState) -> DDPGState:
    return state._replace(epoch=state.epoch + 1)


# --------------------------------------------------------------------------
# The Agent-interface adapter (functional core API v1).  The fused online
# epoch — select → env.step → store → update×U → tick as ONE scan body —
# now lives in the generic api.make_epoch_step; these module-level pure
# functions implement its per-agent hooks.  The running reward-
# standardization statistics (r_mean/r_var/r_count) live in DDPGState and
# therefore ride the scan carry automatically.
# --------------------------------------------------------------------------
def _agent_init(key, cfg: DDPGConfig, env_params=None):
    return init_state(key, cfg)


def _agent_select(key, cfg: DDPGConfig, state, s_vec, env_state, env_params,
                  explore):
    a = select_action(key, state, cfg, s_vec, explore=explore,
                      exact_host_knn=False)
    return a, a.reshape(-1)


def _agent_observe(cfg: DDPGConfig, state, s_vec, aux, reward, s_next):
    return store(state, s_vec, aux, reward, s_next,
                 reward_scale=cfg.reward_scale)


def _agent_update(key, cfg: DDPGConfig, state):
    state, _ = update_step(key, state, cfg)
    return state


def _agent_tick(cfg: DDPGConfig, state):
    return tick(state)


def as_agent(cfg: DDPGConfig) -> api.Agent:
    """The actor-critic method as a pluggable Agent bundle."""
    return api.Agent(name="ddpg", cfg=cfg, init_fn=_agent_init,
                     select_fn=_agent_select, observe_fn=_agent_observe,
                     update_fn=_agent_update, tick_fn=_agent_tick)


def agent_factory(env, **overrides) -> api.Agent:
    """Registry hook: size a DDPGConfig for ``env`` (or pass ``cfg=``)."""
    cfg = overrides.pop("cfg", None)
    if cfg is None:
        cfg = DDPGConfig(n_executors=env.N, n_machines=env.M,
                         state_dim=env.state_dim, **overrides)
    return as_agent(cfg)


api.register_agent("ddpg", agent_factory)


def make_epoch_step(env, cfg: DDPGConfig, updates_per_epoch: int = 1,
                    explore: bool = True, env_params=None):
    """Scan body over decision epochs (compat wrapper over the generic
    api.make_epoch_step; key discipline matches run_online_ddpg_python)."""
    return api.make_epoch_step(env, as_agent(cfg), env_params=env_params,
                               updates_per_epoch=updates_per_epoch,
                               explore=explore)


def init_fleet(key: jax.Array, cfg: DDPGConfig, fleet: int) -> DDPGState:
    """Independently-initialized per-lane states, stacked on a leading
    [fleet] axis (the shape run_online_fleet expects)."""
    return jax.vmap(lambda k: init_state(k, cfg))(jax.random.split(key, fleet))


def offline_pretrain_fleet(
    keys: jax.Array,
    states: DDPGState,
    cfg: DDPGConfig,
    env,
    n_samples: int = 10_000,
    n_updates: int = 2_000,
    env_params=None,
) -> DDPGState:
    """vmap of offline_pretrain over stacked lanes: every lane collects its
    own random-action transitions and pretrains its own nets, all in one
    XLA program.  ``env_params`` may be a single EnvParams or a stacked
    scenario fleet (each lane then pretrains under its own scenario;
    per-leaf broadcast stacks ride with in_axes=None on shared leaves)."""
    if env_params is not None:
        from repro.dsdps.simulator import params_in_axes
        axes = params_in_axes(env_params, env.default_params())
        if axes is not None:
            return jax.vmap(
                lambda k, s, p: offline_pretrain(k, s, cfg, env,
                                                 n_samples=n_samples,
                                                 n_updates=n_updates,
                                                 env_params=p),
                in_axes=(0, 0, axes)
            )(keys, states, env_params)
    return jax.vmap(
        lambda k, s: offline_pretrain(k, s, cfg, env,
                                      n_samples=n_samples,
                                      n_updates=n_updates,
                                      env_params=env_params)
    )(keys, states)


# --------------------------------------------------------------------------
# Offline training (line 4): fill buffer with random-action transitions,
# then run gradient updates — paper: 10,000 samples per setup.
# --------------------------------------------------------------------------
def offline_pretrain(
    key: jax.Array,
    state: DDPGState,
    cfg: DDPGConfig,
    env,
    n_samples: int = 10_000,
    n_updates: int = 2_000,
    env_params=None,
) -> DDPGState:
    params = env.default_params() if env_params is None else env_params
    k_env, k_upd = jax.random.split(key)

    # scan bodies: lax.scan traces these inline — a per-call @jax.jit here
    # would only rebuild a never-reused wrapper every pretrain call
    def collect(carry, k):
        env_state = carry
        k_a, k_step = jax.random.split(k)
        action = env.random_assignment(k_a)
        out = env.step(k_step, env_state, action, params)
        s_vec = env.state_vector(env_state, params)
        s_next_vec = env.state_vector(out.state, params)
        return out.state, (s_vec, action.reshape(-1),
                           out.reward * cfg.reward_scale, s_next_vec)

    env_state = env.reset(k_env, params)
    keys = jax.random.split(k_env, n_samples)
    env_state, (S, A, R, SN) = jax.lax.scan(collect, env_state, keys)

    # keep the newest `capacity` samples (ring buffer semantics),
    # standardized over the offline distribution
    cap = state.replay.states.shape[0]
    take = min(n_samples, cap)
    r_mean = R.mean()
    r_std = jnp.maximum(R.std(), 1e-4)

    def fill(replay, xs):
        s, a, r, sn = xs
        return replay_add(replay, s, a,
                          jnp.clip((r - r_mean) / r_std, -10, 10), sn), None

    replay, _ = jax.lax.scan(
        fill, state.replay, (S[-take:], A[-take:], R[-take:], SN[-take:])
    )
    state = state._replace(replay=replay, r_mean=r_mean,
                           r_var=jnp.square(r_std),
                           r_count=jnp.asarray(n_samples, jnp.int32))

    def train(st, k):
        st, aux = update_step(k, st, cfg)
        return st, aux["critic_loss"]

    state, _ = jax.lax.scan(train, state, jax.random.split(k_upd, n_updates))
    return state
