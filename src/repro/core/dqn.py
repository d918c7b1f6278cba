"""DQN-based DRL baseline (paper §3.2, shown to underperform at scale).

The action space is restricted to single-executor moves: action (i, j)
re-assigns executor i to machine j, giving |A| = N·M.  Q(s, ·) is a single
MLP head over all moves; ε-greedy exploration; replay + target network as
in Mnih et al."""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import api
from repro.core import networks as nets
from repro.core.exploration import EpsilonSchedule, epsilon_greedy
from repro.core.replay import (Replay, pick, replay_add, replay_init,
                               replay_sample)
from repro.train.optimizer import adam, apply_updates


@dataclasses.dataclass(frozen=True)
class DQNConfig:
    n_executors: int
    n_machines: int
    state_dim: int
    gamma: float = 0.99
    tau: float = 0.01
    batch: int = 32
    buffer: int = 1000
    lr: float = 1e-3
    reward_scale: float = 0.25
    eps: EpsilonSchedule = EpsilonSchedule()

    @property
    def num_actions(self) -> int:
        return self.n_executors * self.n_machines


class DQNState(NamedTuple):
    qnet: nets.MLPParams
    target: nets.MLPParams
    opt: object
    replay: Replay
    epoch: jnp.ndarray
    r_mean: jnp.ndarray = jnp.zeros(())
    r_var: jnp.ndarray = jnp.ones(())
    r_count: jnp.ndarray = jnp.zeros((), jnp.int32)


def init_state(key: jax.Array, cfg: DQNConfig) -> DQNState:
    q = nets.init_qnet(key, cfg.state_dim, cfg.num_actions)
    return DQNState(
        qnet=q,
        target=q,
        opt=adam(cfg.lr).init(q),
        replay=replay_init(cfg.buffer, cfg.state_dim, 1),
        epoch=jnp.zeros((), jnp.int32),
    )


def apply_move(X: jnp.ndarray, move: jnp.ndarray, n_machines: int) -> jnp.ndarray:
    """Move `move // M`-th executor to machine `move % M`."""
    i = move // n_machines
    j = move % n_machines
    return X.at[i].set(jax.nn.one_hot(j, n_machines, dtype=X.dtype))


@partial(jax.jit, static_argnames=("cfg", "explore"))
def select_move(key, state: DQNState, cfg: DQNConfig, s_vec, explore: bool = True):
    q = nets.apply_qnet(state.qnet, s_vec)
    eps = cfg.eps(state.epoch) if explore else jnp.zeros(())
    return epsilon_greedy(key, q, eps)


@partial(jax.jit, static_argnames=("cfg",))
def update_step(key, state: DQNState, cfg: DQNConfig):
    s, a, r, s_next = replay_sample(key, state.replay, cfg.batch)
    a = a[:, 0].astype(jnp.int32)
    q_next = jax.vmap(lambda sv: nets.apply_qnet(state.target, sv))(s_next)
    y = r + cfg.gamma * q_next.max(-1)

    def loss(qp):
        q = jax.vmap(lambda sv: nets.apply_qnet(qp, sv))(s)
        q_sa = pick(q, a)
        return jnp.mean(jnp.square(y - q_sa))

    l, grads = jax.value_and_grad(loss)(state.qnet)
    opt = adam(cfg.lr)
    upd, opt_state = opt.update(grads, state.opt, state.qnet)
    qnet = apply_updates(state.qnet, upd)
    return state._replace(
        qnet=qnet,
        target=nets.soft_update(state.target, qnet, cfg.tau),
        opt=opt_state,
    ), {"loss": l}


def store(state: DQNState, s, move, r, s_next,
          reward_scale: float = 1.0) -> DQNState:
    r = r * reward_scale
    cnt = state.r_count + 1
    alpha = jnp.maximum(0.02, 1.0 / cnt.astype(jnp.float32))
    mean = state.r_mean + alpha * (r - state.r_mean)
    var = (1 - alpha) * state.r_var + alpha * jnp.square(r - mean)
    r_std = jnp.clip((r - mean) / jnp.maximum(jnp.sqrt(var), 1e-4), -10, 10)
    return state._replace(
        replay=replay_add(state.replay, s,
                          jnp.asarray([move], jnp.float32),
                          r_std, s_next),
        r_mean=mean, r_var=var, r_count=cnt)


def tick(state: DQNState) -> DQNState:
    return state._replace(epoch=state.epoch + 1)


# --------------------------------------------------------------------------
# Agent-interface adapter (mirrors ddpg's) — the DQN hooks of the generic
# fused epoch body in api.make_epoch_step.
# --------------------------------------------------------------------------
def _agent_init(key, cfg: DQNConfig, env_params=None):
    return init_state(key, cfg)


def _agent_select(key, cfg: DQNConfig, state, s_vec, env_state, env_params,
                  explore):
    move = select_move(key, state, cfg, s_vec, explore=explore)
    return apply_move(env_state.X, move, cfg.n_machines), move


def _agent_observe(cfg: DQNConfig, state, s_vec, aux, reward, s_next):
    return store(state, s_vec, aux, reward, s_next,
                 reward_scale=cfg.reward_scale)


def _agent_update(key, cfg: DQNConfig, state):
    state, _ = update_step(key, state, cfg)
    return state


def _agent_tick(cfg: DQNConfig, state):
    return tick(state)


def as_agent(cfg: DQNConfig) -> api.Agent:
    """The DQN baseline as a pluggable Agent bundle."""
    return api.Agent(name="dqn", cfg=cfg, init_fn=_agent_init,
                     select_fn=_agent_select, observe_fn=_agent_observe,
                     update_fn=_agent_update, tick_fn=_agent_tick)


def agent_factory(env, **overrides) -> api.Agent:
    """Registry hook: size a DQNConfig for ``env`` (or pass ``cfg=``)."""
    cfg = overrides.pop("cfg", None)
    if cfg is None:
        cfg = DQNConfig(n_executors=env.N, n_machines=env.M,
                        state_dim=env.state_dim, **overrides)
    return as_agent(cfg)


api.register_agent("dqn", agent_factory)


def make_epoch_step(env, cfg: DQNConfig, updates_per_epoch: int = 1,
                    explore: bool = True, env_params=None):
    """carry = (DQNState, EnvState, key); emits (reward, latency_ms, moved).
    Compat wrapper over api.make_epoch_step — key-splitting matches
    agent.run_online_dqn_python exactly."""
    return api.make_epoch_step(env, as_agent(cfg), env_params=env_params,
                               updates_per_epoch=updates_per_epoch,
                               explore=explore)


def init_fleet(key: jax.Array, cfg: DQNConfig, fleet: int) -> DQNState:
    """Independently-initialized per-lane states stacked on [fleet]."""
    return jax.vmap(lambda k: init_state(k, cfg))(jax.random.split(key, fleet))
