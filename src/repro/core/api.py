"""Functional core API v1 — the pluggable `Agent` interface + registry.

The paper's framework is explicitly pluggable: one DRL control loop driven
against arbitrary applications and control policies.  An :class:`Agent` is
an optax-style bundle of pure functions over a hashable config:

    init     (key, cfg, env_params)                       -> agent_state
    select   (key, cfg, state, s_vec, env_state,
              env_params, explore)                        -> (action, aux)
    observe  (cfg, state, s_vec, aux, reward, s_next)     -> agent_state
    update   (key, cfg, state)                            -> agent_state
    tick     (cfg, state)                                 -> agent_state

``aux`` is whatever the agent wants replayed (DDPG: the flat action; DQN:
the move index; non-learning baselines: a dummy scalar).  ``env_params``
is the scenario the agent is actually controlling (an EnvParams /
PlacementParams pytree, or None for the env's defaults): learning agents
may ignore it, but model-grounded baselines MUST consult it — a
model-based lane in a heterogeneous straggler fleet has to profile and
search ITS cluster, not the nominal one.  Because the bundle holds
module-level functions plus a hashable config, two agents built from
equal configs compare equal — an Agent is a valid jit STATIC argument,
and jit's own cache (keyed on the static env spec + agent) replaces the
old id(env)-keyed runner cache.

:func:`make_epoch_step` fuses select → env.step → observe → update×U →
tick into one scan body for ANY agent, against the functional env surface
``reset(key, params) / step(key, state, action, params) /
state_vector(state, params)``.  The fleet runner (core/agent.py) vmaps
that scan over stacked agent states AND stacked EnvParams, so baselines
and learners run through the same one-XLA-program fleet path.

:func:`make_agent` is the registry entry point:

    agent = make_agent("ddpg", env, k_nn=16)
    states = agent.init_fleet(key, fleet=8)
    states, hist = run_online_fleet(keys, env, agent, states, T=300)

Built-in names: ``ddpg``, ``dqn``, ``stream_q``, ``stream_ac``,
``graph_policy``, ``round_robin``, ``model_based`` (plus the
serving-only ``rate_control`` and ``auto_tune`` action-space policies).
The runners take Agent bundles ONLY — the PR-2 window during which bare
DDPG/DQN configs were coerced has closed; wrap a ready config with
``make_agent(name, env, cfg=cfg)``.  The full interface contract is
documented in docs/core_api.md.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.diagnostics.spans import AGENT_SELECT, AGENT_UPDATE, ENV_STEP


class Agent(NamedTuple):
    """Optax-style bundle of pure control-policy functions.

    Fields hold module-level functions taking the config explicitly (so
    equality/hashing works for jit static args); the ``init/select/...``
    methods are the ergonomic curried surface.  Signatures (the PR-3
    params-aware contract):

        init_fn(key, cfg, env_params)                       -> agent_state
        select_fn(key, cfg, state, s_vec, env_state,
                  env_params, explore)                      -> (action, aux)
        observe_fn(cfg, state, s_vec, aux, reward, s_next)  -> agent_state
        update_fn(key, cfg, state)                          -> agent_state
        tick_fn(cfg, state)                                 -> agent_state
    """

    name: str
    cfg: Any
    init_fn: Callable[[jax.Array, Any, Any], Any]
    select_fn: Callable[..., tuple[jnp.ndarray, Any]]
    observe_fn: Callable[..., Any]
    update_fn: Callable[[jax.Array, Any, Any], Any]
    tick_fn: Callable[[Any, Any], Any]

    # -- curried convenience surface ---------------------------------------
    def init(self, key: jax.Array, env_params=None):
        return self.init_fn(key, self.cfg, env_params)

    def init_fleet(self, key: jax.Array, fleet: int, env_params=None,
                   env=None):
        """Independently-initialized per-lane states stacked on [fleet].

        ``env_params`` may be None, a single scenario shared by every lane,
        or a STACKED scenario fleet ([F] leading axis, possibly with
        broadcast-invariant leaves) — each lane then initializes under its
        own scenario (e.g. the model-based baseline profiles and fits ITS
        cluster, so a straggler lane learns a straggler model).  ``env`` is
        required alongside ``env_params``: its ``default_params()``
        supplies the single-scenario leaf ranks, without which a stacked
        fleet is indistinguishable from a single scenario (and would be
        fed whole to every lane)."""
        keys = jax.random.split(key, fleet)
        if env_params is not None:
            if env is None:
                raise ValueError(
                    "init_fleet(env_params=...) needs env= as well — the "
                    "env's default_params() is the reference that tells a "
                    "stacked scenario fleet apart from a single scenario")
            from repro.dsdps.simulator import params_in_axes
            axes = params_in_axes(env_params, env.default_params())
            if axes is not None:
                return jax.vmap(
                    lambda k, p: self.init_fn(k, self.cfg, p),
                    in_axes=(0, axes))(keys, env_params)
        return jax.vmap(lambda k: self.init_fn(k, self.cfg, env_params))(keys)

    def select(self, key, state, s_vec, env_state, env_params=None,
               explore: bool = True):
        return self.select_fn(key, self.cfg, state, s_vec, env_state,
                              env_params, explore)

    def observe(self, state, s_vec, aux, reward, s_next):
        return self.observe_fn(self.cfg, state, s_vec, aux, reward, s_next)

    def update(self, key, state):
        return self.update_fn(key, self.cfg, state)

    def tick(self, state):
        return self.tick_fn(self.cfg, state)

    def make_epoch_step(self, env, env_params=None, updates_per_epoch: int = 1,
                        explore: bool = True):
        return make_epoch_step(env, self, env_params=env_params,
                               updates_per_epoch=updates_per_epoch,
                               explore=explore)


def make_epoch_step(env, agent: Agent, env_params=None,
                    updates_per_epoch: int = 1, explore: bool = True):
    """Fused online decision epoch as a scan body, for any Agent.

    carry = (agent_state, env_state, key); per-epoch output is
    (reward, latency_ms, moved).  The key-splitting discipline matches the
    legacy per-agent Python loops (core.agent.run_online_*_python) exactly,
    so scan runners reproduce their traces.  ``env_params`` may be a traced
    pytree (the fleet runner passes one lane of a stacked scenario fleet);
    None freezes the env's defaults into the program as constants.

    The env step, the select and the update (observe, the update scan,
    tick) each run under their ``jax.named_scope`` of
    ``diagnostics.spans.LAYERS``; a profile maps the compiled ops back to
    them (``diagnostics.scope_tables``).  The key split stays unscoped."""
    params = env.default_params() if env_params is None else env_params

    def epoch_step(carry, _):
        state, env_state, key = carry
        key, k_act, k_step, k_upd = jax.random.split(key, 4)
        with jax.named_scope(ENV_STEP):
            s_vec = env.state_vector(env_state, params)
        with jax.named_scope(AGENT_SELECT):
            action, aux = agent.select_fn(k_act, agent.cfg, state, s_vec,
                                          env_state, params, explore)
        with jax.named_scope(ENV_STEP):
            out = env.step(k_step, env_state, action, params)
            s_next = env.state_vector(out.state, params)
        with jax.named_scope(AGENT_UPDATE):
            state = agent.observe_fn(agent.cfg, state, s_vec, aux,
                                     out.reward, s_next)

            def upd(st, k):
                return agent.update_fn(k, agent.cfg, st), None

            state, _ = jax.lax.scan(
                upd, state, jax.random.split(k_upd, updates_per_epoch))
            state = agent.tick_fn(agent.cfg, state)
        return (state, out.state, key), (out.reward, out.latency_ms, out.moved)

    return epoch_step


def params_are_stacked(env, env_params) -> bool:
    """True when ``env_params`` carries a leading fleet axis (one more
    dimension than the env's single-scenario defaults)."""
    from repro.dsdps.simulator import params_stacked
    return params_stacked(env_params, env.default_params())


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------
_REGISTRY: dict[str, Callable[..., Agent]] = {}
_FAMILIES: dict[str, tuple[str, ...]] = {}

# the two env families sharing the functional surface (reset/step/
# state_vector/default_params + N/M/state_dim): the DSDPS SchedulingEnv
# and the TPU ExpertPlacementEnv instantiation
ENV_FAMILIES = ("scheduling", "placement")


def register_agent(name: str, factory: Callable[..., Agent],
                   families: tuple[str, ...] = ENV_FAMILIES) -> None:
    """Register ``factory(env, **overrides) -> Agent`` under ``name``.

    ``families`` declares which env families the agent's actions are valid
    for (subset of :data:`ENV_FAMILIES`; empty for serving-only policies
    whose action spaces never reach ``env.step``) — the registry
    completeness test drives every registered agent through one fused
    epoch step on each family it declares."""
    unknown = set(families) - set(ENV_FAMILIES)
    if unknown:
        raise ValueError(f"unknown env families {sorted(unknown)}; "
                         f"known: {ENV_FAMILIES}")
    _REGISTRY[name] = factory
    _FAMILIES[name] = tuple(families)


def _load_builtins() -> None:
    # Built-in agents self-register at import time; imported lazily to keep
    # this module dependency-free (ddpg/dqn/... all import it).
    import repro.core.control_policies  # noqa: F401
    import repro.core.ddpg        # noqa: F401
    import repro.core.dqn         # noqa: F401
    import repro.core.graph_policy  # noqa: F401
    import repro.core.model_based  # noqa: F401
    import repro.core.round_robin  # noqa: F401
    import repro.core.stream_ac   # noqa: F401
    import repro.core.stream_q    # noqa: F401


def agent_names() -> tuple[str, ...]:
    """Registered agent names (builtin + user-registered)."""
    _load_builtins()
    return tuple(sorted(_REGISTRY))


def agent_families(name: str) -> tuple[str, ...]:
    """Env families ``name`` declared at registration (see
    :func:`register_agent`); empty tuple = serving-only."""
    _load_builtins()
    try:
        return _FAMILIES[name]
    except KeyError:
        raise KeyError(f"unknown agent {name!r}; "
                       f"known: {sorted(_REGISTRY)}") from None


def make_agent(name: str, env, **overrides) -> Agent:
    """Construct a registered agent sized for ``env``.

    ``overrides`` are forwarded to the agent's config constructor (e.g.
    ``make_agent("ddpg", env, k_nn=16, eps=EpsilonSchedule(...))``), or
    pass a ready config as ``cfg=``."""
    _load_builtins()
    try:
        factory = _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown agent {name!r}; "
                       f"known: {sorted(_REGISTRY)}") from None
    return factory(env, **overrides)
