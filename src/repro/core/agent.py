"""Unified online-learning control loops (the paper's decision-epoch loop).

These drive any environment exposing the functional core surface
(``reset(key, params)`` / ``step(key, state, action, params)`` /
``state_vector(state, params)`` / ``default_params()``) — the DSDPS
simulator or the TPU expert-placement environment — with any
:class:`repro.core.api.Agent` (actor-critic Algorithm 1, the DQN baseline,
or the non-learning round-robin / model-based baselines), producing the
reward traces of Figs 7/9/11.

Three execution paths:

  * ``run_online_agent`` — ONE online run of any registry agent, executed
    as a single jitted ``jax.lax.scan`` over decision epochs;

  * ``run_online_fleet`` — MANY independent runs executed as one XLA
    program: ``jax.vmap`` over a fleet axis of the same scan.  Lanes may
    differ by seed, by initial EnvState, AND by scenario: pass stacked
    :class:`~repro.dsdps.simulator.EnvParams` (repro.dsdps.scenarios) and
    heterogeneous workload rates × service-time jitter × noise levels ×
    stragglers train in ONE program.  This is what makes Decima-style
    train-over-a-distribution-of-workloads affordable here.

  * ``run_online_fleet(..., mesh=...)`` — the same fleet partitioned over
    a device mesh: the fleet axis of every carry (keys, agent states, env
    states, stacked EnvParams leaves) shards over the mesh's data axes
    via ``shard_map`` (repro/sharding/fleet.py), so fleet capacity is the
    whole mesh's memory, not one accelerator's.  On real accelerators the
    carries are donated (the epoch scan runs in-place); on the 1-device
    host mesh the path is bit-comparable to the plain vmap runner.
    Passing ``checkpoint=`` (a
    :class:`repro.checkpoint.fleet.FleetCheckpoint`) chunks the epoch
    scan every ``checkpoint.every`` epochs and atomically snapshots the
    carries in the background — the device→host transfer itself runs off
    the caller thread, so the mesh keeps scanning while the previous
    chunk serializes — and long heterogeneous-scenario runs survive
    restarts and device-count changes (docs/sharded_fleets.md).  Passing
    ``lifecycle=`` (a :class:`repro.fleet.lifecycle.StopRule`) makes the
    fleet ELASTIC: lanes whose smoothed reward plateaus stop early and
    the surviving lanes are compacted into a smaller fleet between
    chunks, so converged scenarios stop paying compute
    (docs/elastic_fleets.md).

Executable caching is jit's own: the env spec and the Agent bundle are
hashable static arguments of module-level jitted programs, and EnvParams
are traced, so re-running with new scenario parameters never recompiles.
(The pre-v1 ``id(env)``-keyed ``_RUNNER_CACHE`` is gone, and the PR-2
``run_online_ddpg`` / ``run_online_dqn`` bare-config wrappers were
removed when their deprecation window closed — build an Agent with
``make_agent(name, env, cfg=...)`` instead.)

The legacy per-epoch Python loops are kept as ``run_online_*_python`` —
they are the bit-exactness reference for the scan runners
(tests/test_fleet_runner.py) and the baseline of benchmarks/fleet_bench.py."""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ddpg, dqn
from repro.core.api import Agent, make_epoch_step
from repro.diagnostics import maybe_check_finite, note_compile, span
from repro.diagnostics.guards import _cache_size
from repro.core.ddpg import DDPGConfig, DDPGState
from repro.core.dqn import DQNConfig, DQNState
from repro.sharding.fleet import fleet_host, fleet_spec, shard_fleet


@dataclasses.dataclass
class History:
    """Reward / latency / movement traces of one run ([T]) or of a fleet of
    runs ([fleet, T]); final_assignment is [N, M] or [fleet, N, M]."""

    rewards: np.ndarray
    latencies: np.ndarray
    moved: np.ndarray
    final_assignment: np.ndarray

    @property
    def fleet(self) -> int | None:
        """Fleet size, or None for a single-run history."""
        return self.rewards.shape[0] if self.rewards.ndim == 2 else None

    def lane(self, i: int) -> "History":
        """The i-th run of a fleet history as a single-run History."""
        if self.fleet is None:
            raise ValueError("lane() on a single-run History")
        return History(rewards=self.rewards[i], latencies=self.latencies[i],
                       moved=self.moved[i],
                       final_assignment=self.final_assignment[i])

    def normalized_rewards(self) -> np.ndarray:
        """(r - r_min)/(r_max - r_min), the paper's normalization —
        per-lane (along the epoch axis) for fleet histories."""
        r = self.rewards
        lo = r.min(axis=-1, keepdims=True)
        hi = r.max(axis=-1, keepdims=True)
        return (r - lo) / np.maximum(hi - lo, 1e-12)

    def smoothed_rewards(self, cutoff: float = 0.05) -> np.ndarray:
        """Forward-backward (zero-phase) low-pass filter, as in the paper
        ([20] Gustafsson filtfilt).  Falls back to a numpy forward-backward
        moving average when scipy is unavailable."""
        r = self.normalized_rewards()
        if r.shape[-1] < 15:
            return r
        try:
            from scipy.signal import butter, filtfilt
        except ImportError:
            return _smooth_moving_average(r, cutoff)
        b, a = butter(2, cutoff)
        return filtfilt(b, a, r, axis=-1)

    def seed_band(self, cutoff: float = 0.05) -> tuple[np.ndarray, np.ndarray]:
        """(mean, std) across the fleet axis of the smoothed normalized
        reward curves — the seed-averaged curve + variance band plotted by
        the paper_fig benchmarks."""
        r = np.atleast_2d(self.smoothed_rewards(cutoff))
        return r.mean(axis=0), r.std(axis=0)


def _smooth_moving_average(r: np.ndarray, cutoff: float) -> np.ndarray:
    """Scipy-free zero-phase smoother: an edge-padded moving average of
    width ~1/cutoff applied forward then backward (symmetric kernel, so the
    result is zero-phase like filtfilt; slightly softer roll-off)."""
    win = max(3, int(round(1.0 / max(cutoff, 1e-3))))
    win = min(win, r.shape[-1])
    kernel = np.ones(win) / win
    pad = (win // 2, win - 1 - win // 2)

    def one_pass(x: np.ndarray) -> np.ndarray:
        return np.convolve(np.pad(x, pad, mode="edge"), kernel, mode="valid")

    sm = np.apply_along_axis(one_pass, -1, r)
    sm = np.apply_along_axis(lambda x: one_pass(x[::-1])[::-1], -1, sm)
    return sm


def _require_agent(agent) -> Agent:
    """The runners take api.Agent bundles only.  (The PR-2 deprecation
    window during which bare DDPG/DQN configs were coerced has closed.)"""
    if not isinstance(agent, Agent):
        raise TypeError(
            f"expected an api.Agent, got {type(agent).__name__}; build one "
            f"with make_agent(name, env, cfg=...) or ddpg/dqn.as_agent(cfg) "
            f"(the pre-v1 bare-config call style was removed)")
    return agent


# --------------------------------------------------------------------------
# The jitted programs.  env + agent are hashable static arguments — jit's
# cache replaces the old id(env)-keyed runner cache — and EnvParams ride
# as traced pytrees, so scenario changes never recompile.  Executables
# (and the env specs they key on) live for the process: far fewer entries
# than the old per-env-instance cache since params changes reuse programs,
# but a sweep over many (env, agent, T) combos can call jax.clear_caches()
# between apps if memory matters.
# --------------------------------------------------------------------------
@partial(jax.jit,
         static_argnames=("env", "agent", "T", "updates_per_epoch", "explore"))
def _single_program(key, state, env_state, env_params, *, env, agent: Agent,
                    T: int, updates_per_epoch: int, explore: bool):
    epoch = make_epoch_step(env, agent, env_params=env_params,
                            updates_per_epoch=updates_per_epoch,
                            explore=explore)
    (state, env_state, _), (rewards, lats, moved) = jax.lax.scan(
        epoch, (state, env_state, key), None, length=T)
    return state, rewards, lats, moved, env_state.X


def _fleet_fn(keys, states, env_states, env_params, *, env, agent: Agent,
              T: int, updates_per_epoch: int, explore: bool, params_axes):
    """The fleet body: vmap of the fused epoch scan over the lane axis.

    ``params_axes`` is the per-leaf vmap axis spec for ``env_params``
    (simulator.params_in_axes): an EnvParams-shaped pytree of 0/None —
    scenario-invariant leaves broadcast with None instead of being stacked
    F× — or plain None when every lane shares one scenario.  It is a
    hashable NamedTuple of ints/None, so it rides jit as a static arg.

    Returns the FULL evolved carries ``(states, env_states, keys)`` plus
    the ``(rewards, lats, moved)`` traces — the carries are what fleet
    checkpointing snapshots and what chunked runs thread from one scan
    call into the next."""
    def lane(key, state, env_state, lane_params):
        epoch = make_epoch_step(env, agent, env_params=lane_params,
                                updates_per_epoch=updates_per_epoch,
                                explore=explore)
        (state, env_state, key), (rewards, lats, moved) = jax.lax.scan(
            epoch, (state, env_state, key), None, length=T)
        return state, env_state, key, rewards, lats, moved

    in_axes = (0, 0, 0, params_axes)
    return jax.vmap(lane, in_axes=in_axes)(keys, states, env_states,
                                           env_params)


_FLEET_STATICS = ("env", "agent", "T", "updates_per_epoch", "explore",
                  "params_axes")
_fleet_program = jax.jit(_fleet_fn, static_argnames=_FLEET_STATICS)


def _sharded_fleet_fn(keys, states, env_states, env_params, *, env,
                      agent: Agent, T: int, updates_per_epoch: int,
                      explore: bool, params_axes, mesh, params_specs):
    """The fleet body wrapped in ``shard_map``: every carry partitions its
    leading fleet axis over the mesh's data axes; ``params_specs``
    (sharding.fleet.params_partition_specs) replicates broadcast-invariant
    EnvParams leaves instead of sharding them.  Lanes are independent, so
    the body needs no collectives — each device runs the vmapped scan over
    its local lanes (check_vma stays off: no replicated outputs to
    certify, and the scan body trips no replication rules)."""
    spec = fleet_spec(mesh)
    body = partial(_fleet_fn, env=env, agent=agent, T=T,
                   updates_per_epoch=updates_per_epoch, explore=explore,
                   params_axes=params_axes)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(spec, spec, spec, params_specs),
                       out_specs=(spec, spec, spec, spec, spec, spec),
                       check_vma=False)
    return fn(keys, states, env_states, env_params)


_SHARDED_STATICS = _FLEET_STATICS + ("mesh", "params_specs")
_fleet_program_sharded = jax.jit(_sharded_fleet_fn,
                                 static_argnames=_SHARDED_STATICS)
# Donated variant for real accelerator meshes: the carries (keys, agent
# states, env states) are consumed in place, halving fleet memory across
# chunked checkpointed runs.  CPU meshes use the non-donated program (jax
# cannot donate on cpu and would warn on every call).
_fleet_program_sharded_donated = jax.jit(_sharded_fleet_fn,
                                         static_argnames=_SHARDED_STATICS,
                                         donate_argnums=(0, 1, 2))


def run_fleet_chunk(keys, states, env_states, env_params, *, env,
                    agent: Agent, T: int, updates_per_epoch: int,
                    explore: bool, params_axes, mesh=None, params_specs=None):
    """One chunk of the fleet epoch scan: the shared execution primitive
    behind ``run_online_fleet``'s checkpoint chunking and the elastic lane
    lifecycle's stop-check boundaries (repro/fleet/lifecycle.py).

    The inputs must already be placed (``sharding.fleet.shard_fleet``) when
    ``mesh`` is given; ``params_specs`` is the hashable PartitionSpec tree
    that placement returned.  On accelerator meshes the carries are DONATED
    — slice anything you still need out of them (e.g. a stopped lane's
    final state) before calling again.  Returns the evolved carries plus
    the ``[fleet, T]`` traces: ``(states, env_states, keys, rewards,
    latencies, moved)``.

    The call runs inside the ``fleet.dispatch`` span, and a call that
    compiled is recorded for ``diagnostics.scope_tables``."""
    common = dict(env=env, agent=agent, T=int(T),
                  updates_per_epoch=int(updates_per_epoch),
                  explore=bool(explore), params_axes=params_axes)
    if mesh is not None:
        donate = mesh.devices.flat[0].platform != "cpu"
        program = (_fleet_program_sharded_donated if donate
                   else _fleet_program_sharded)
        common.update(mesh=mesh, params_specs=params_specs)
    else:
        program = _fleet_program
    args = (keys, states, env_states, env_params)
    with span("fleet.dispatch"):
        cached = _cache_size(program)
        out = program(*args, **common)
        note_compile(program, cached, args, common)
    return out


def chunk_schedule(T: int, every: int | None) -> list[int]:
    """Chunk lengths for a ``T``-epoch scan cut every ``every`` epochs
    (trailing partial chunk included); ``[T]`` when ``every`` is falsy."""
    if not every:
        return [T]
    chunks = [every] * (T // every)
    if T % every:
        chunks.append(T % every)
    return chunks


def prepare_fleet(keys, env, states, env_states, env_params, mesh):
    """The fleet runners' shared setup preamble: default-params /
    ``params_axes`` resolution, the env-reset key split, and mesh
    placement.  The elastic runner's loss-free bit-match contract depends
    on this staying IDENTICAL between the fixed-grid and elastic entry
    points, which is why it is one function.

    Returns ``(keys, states, env_states, env_params, ref, params_axes,
    params_specs)``.  Runs inside the ``fleet.prepare`` span."""
    # setup preamble exemption: placing hosts arrays on devices is this
    # function's JOB, so the diagnostics transfer guard (which polices the
    # steady-state chunk loop) is lifted for its dynamic extent
    with span("fleet.prepare"), jax.transfer_guard("allow"):
        keys = jnp.asarray(keys)
        ref = env.default_params()
        if env_params is None:
            env_params = ref
            params_axes = None
        else:
            from repro.dsdps.simulator import params_in_axes
            params_axes = params_in_axes(env_params, ref)
        if env_states is None:
            pairs = jax.vmap(jax.random.split)(keys)      # [F, 2] keys
            k_env, keys = pairs[:, 0], pairs[:, 1]
            env_states = reset_fleet_states(k_env, env, env_params)
        params_specs = None
        if mesh is not None:
            keys, states, env_states, env_params, params_specs = shard_fleet(
                mesh, keys, states, env_states, env_params, ref)
        return keys, states, env_states, env_params, ref, params_axes, \
            params_specs


def _run_single(key, env, agent, state, T, updates_per_epoch, explore,
                env_params=None):
    agent = _require_agent(agent)
    params = env.default_params() if env_params is None else env_params
    k_env, key = jax.random.split(key)
    env_state = env.reset(k_env, params)
    state, rewards, lats, moved, X = _single_program(
        key, state, env_state, params, env=env, agent=agent, T=int(T),
        updates_per_epoch=int(updates_per_epoch), explore=bool(explore))
    return state, History(rewards=np.asarray(rewards),
                          latencies=np.asarray(lats),
                          moved=np.asarray(moved),
                          final_assignment=np.asarray(X))


def run_online_agent(
    key: jax.Array,
    env,
    agent: Agent,
    state,
    T: int,
    updates_per_epoch: int = 1,
    explore: bool = True,
    env_params=None,
):
    """One online run of any registry agent as a single jitted scan over
    ``T`` decision epochs.

    ``key`` is split once for the env reset, then carried through the
    fused epoch scan with the same key discipline as the legacy Python
    oracles (``run_online_*_python``), so the scan reproduces their
    traces.  ``env_params`` is a single scenario pytree (defaults to
    ``env.default_params()``).  Returns ``(agent_state, History)`` with
    ``[T]`` traces."""
    return _run_single(key, env, agent, state, T, updates_per_epoch, explore,
                       env_params=env_params)


def reset_fleet_states(keys: jax.Array, env, env_params=None):
    """Stacked per-lane initial EnvStates: vmapped ``env.reset`` over a
    ``[fleet]`` key array, with per-leaf broadcast handling when
    ``env_params`` is a (possibly broadcast-invariant) stacked scenario
    fleet.  Works for ANY functional env (SchedulingEnv's ``reset_fleet``
    adds DSDPS-specific extras like legacy speed_factors on top of this).

    This is also the structure template
    :meth:`repro.checkpoint.fleet.FleetCheckpoint.restore` needs for the
    ``env_states`` tree when resuming a run (values are ignored — only
    shapes/dtypes/structure matter)."""
    if env_params is None:
        env_params = env.default_params()
        params_axes = None
    else:
        from repro.dsdps.simulator import params_in_axes
        params_axes = params_in_axes(env_params, env.default_params())
    if params_axes is not None:
        return jax.vmap(env.reset, in_axes=(0, params_axes))(keys, env_params)
    return jax.vmap(lambda k: env.reset(k, env_params))(keys)


def run_online_fleet(
    keys: jax.Array,
    env,
    agent: Agent,
    states,
    T: int,
    updates_per_epoch: int = 1,
    explore: bool = True,
    env_states=None,
    env_params=None,
    mesh=None,
    checkpoint=None,
    start_epoch: int = 0,
    lifecycle=None,
):
    """Fleet-batched online learning: one XLA program for [fleet] runs.

    ``keys``   — stacked per-lane PRNG keys ([fleet] key array);
    ``agent``  — an api.Agent (make_agent(...));
    ``states`` — per-lane agent states stacked on a leading [fleet] axis
                 (agent.init_fleet / ddpg.init_fleet / dqn.init_fleet,
                 optionally pretrained with ddpg.offline_pretrain_fleet);
    ``env_params`` — a single EnvParams (shared by every lane) or a STACKED
                 EnvParams scenario fleet ([F] leading axis, e.g. from
                 repro.dsdps.scenarios): heterogeneous workload rates,
                 service-time jitter, noise levels, and stragglers then run
                 as one vmapped program.  Defaults to env.default_params().
                 Stacks built with ``stack_env_params(...,
                 broadcast_invariant=True)`` keep scenario-invariant leaves
                 (routing / flow_solve / tuple_bytes) as ONE copy; those
                 leaves ride the vmap with per-leaf ``in_axes=None`` —
                 numerically identical to the fully-stacked run, minus the
                 duplicated memory and batched-matmul FLOPs.
    ``env_states`` — optional stacked EnvState (SchedulingEnv.reset_fleet)
                 for heterogeneous *initial state* lanes: per-lane straggler
                 speed factors, initial assignments, warm workload states.
                 When omitted, every lane resets the env exactly as the
                 single-run API does (so fleet lane i bit-matches a
                 run_online_agent call with the same key, initial state,
                 and params lane).
    ``mesh``   — optional ``jax.sharding.Mesh``: the fleet axis of every
                 carry shards over the mesh's data axes (every axis except
                 "model") via shard_map, so the fleet's memory footprint
                 spreads over the whole mesh instead of one device.  The
                 fleet size must be a multiple of the data-axis device
                 count.  On accelerator meshes the carries are DONATED —
                 don't reuse ``states``/``env_states``/``keys`` buffers
                 after the call; on CPU meshes (launch.mesh.make_host_mesh)
                 nothing is donated and lane i stays bit-comparable to the
                 un-sharded vmap run (modulo the documented broadcast-
                 matmul ulp caveat).
    ``checkpoint`` — optional repro.checkpoint.fleet.FleetCheckpoint: the
                 epoch scan is chunked every ``checkpoint.every`` epochs
                 and the full carries (agent states, env states, keys) are
                 snapshotted asynchronously and atomically after each
                 chunk, tagged with the absolute epoch number.  A chunked
                 run threads the scan carry between chunks, so a run
                 restored from epoch k continues bit-identically to an
                 uninterrupted run with the same cadence.
    ``start_epoch`` — absolute epoch this call starts at (resume offset):
                 only affects checkpoint numbering.  ``T`` is always the
                 number of epochs executed BY THIS CALL.
    ``lifecycle`` — optional :class:`repro.fleet.lifecycle.StopRule`: lanes
                 whose smoothed reward plateaus stop early and the fleet is
                 COMPACTED between chunks so finished lanes stop paying
                 compute (docs/elastic_fleets.md).  Stopped lanes' trace
                 tails are padded with their final value; use
                 :func:`repro.fleet.lifecycle.run_online_fleet_elastic`
                 directly for the per-lane stop epochs and the
                 executed-lane-epoch accounting.

    Returns (stacked agent states, History with [fleet, T] traces).  The
    call runs inside one ``fleet.job`` span, the parent of its
    ``fleet.prepare``, ``fleet.dispatch`` and ``fleet.pull`` (the pulls of
    its traces and final assignment) spans."""
    with span("fleet.job"):
        agent = _require_agent(agent)
        T = int(T)
        if T < 1:
            raise ValueError(f"T must be >= 1, got {T}")
        if lifecycle is not None:
            from repro.fleet.lifecycle import run_online_fleet_elastic
            result = run_online_fleet_elastic(
                keys, env, agent, states, T, rule=lifecycle,
                updates_per_epoch=updates_per_epoch, explore=explore,
                env_states=env_states, env_params=env_params, mesh=mesh,
                checkpoint=checkpoint, start_epoch=start_epoch)
            return result.states, result.history
        keys, states, env_states, env_params, _, params_axes, params_specs = \
            prepare_fleet(keys, env, states, env_states, env_params, mesh)

        every = getattr(checkpoint, "every", None) if checkpoint is not None \
            else None
        epoch = int(start_epoch)
        r_parts, l_parts, m_parts = [], [], []
        for n in chunk_schedule(T, every):
            states, env_states, keys, rewards, lats, moved = run_fleet_chunk(
                keys, states, env_states, env_params, env=env, agent=agent,
                T=n, updates_per_epoch=updates_per_epoch, explore=explore,
                params_axes=params_axes, mesh=mesh, params_specs=params_specs)
            # fleet_host == np.asarray off a spanning mesh; on one it
            # allgathers the trace shards so every process sees the full
            # [fleet, T] history (multi-host runs return identical Histories
            # on every process)
            with span("fleet.pull"):
                r_parts.append(fleet_host(rewards))
                l_parts.append(fleet_host(lats))
                m_parts.append(fleet_host(moved))
            epoch += n
            maybe_check_finite((states, rewards), f"run_online_fleet epoch {epoch}")
            if checkpoint is not None:
                checkpoint.save(epoch, states, env_states, keys)
        with span("fleet.pull"):
            final_assignment = fleet_host(env_states.X)
        return states, History(rewards=np.concatenate(r_parts, axis=-1),
                               latencies=np.concatenate(l_parts, axis=-1),
                               moved=np.concatenate(m_parts, axis=-1),
                               final_assignment=final_assignment)


# --------------------------------------------------------------------------
# Legacy per-epoch Python loops — the reference semantics.  Kept unchanged
# as (a) the regression oracle for the scan runners and (b) the sequential
# baseline the fleet microbenchmark measures its speedup against.
# --------------------------------------------------------------------------
def run_online_ddpg_python(
    key: jax.Array,
    env,
    cfg: DDPGConfig,
    state: DDPGState,
    T: int,
    updates_per_epoch: int = 1,
    explore: bool = True,
) -> tuple[DDPGState, History]:
    k_env, key = jax.random.split(key)
    env_state = env.reset(k_env)
    rewards, lats, moved = [], [], []

    for t in range(T):
        key, k_act, k_step, k_upd = jax.random.split(key, 4)
        s_vec = env.state_vector(env_state)
        action = ddpg.select_action_jit(k_act, state, cfg, s_vec, explore=explore)
        out = env.step(k_step, env_state, action)
        s_next = env.state_vector(out.state)
        state = ddpg.store(state, s_vec, action.reshape(-1), out.reward, s_next,
                           reward_scale=cfg.reward_scale)
        for k in jax.random.split(k_upd, updates_per_epoch):
            state, _ = ddpg.update_step(k, state, cfg)
        state = ddpg.tick(state)
        env_state = out.state
        rewards.append(float(out.reward))
        lats.append(float(out.latency_ms))
        moved.append(int(out.moved))

    return state, History(
        rewards=np.asarray(rewards),
        latencies=np.asarray(lats),
        moved=np.asarray(moved),
        final_assignment=np.asarray(env_state.X),
    )


def run_online_dqn_python(
    key: jax.Array,
    env,
    cfg: DQNConfig,
    state: DQNState,
    T: int,
    updates_per_epoch: int = 1,
    explore: bool = True,
) -> tuple[DQNState, History]:
    k_env, key = jax.random.split(key)
    env_state = env.reset(k_env)
    rewards, lats, moved = [], [], []

    for t in range(T):
        key, k_act, k_step, k_upd = jax.random.split(key, 4)
        s_vec = env.state_vector(env_state)
        move = dqn.select_move(k_act, state, cfg, s_vec, explore=explore)
        action = dqn.apply_move(env_state.X, move, cfg.n_machines)
        out = env.step(k_step, env_state, action)
        s_next = env.state_vector(out.state)
        state = dqn.store(state, s_vec, move, out.reward, s_next,
                          reward_scale=cfg.reward_scale)
        for k in jax.random.split(k_upd, updates_per_epoch):
            state, _ = dqn.update_step(k, state, cfg)
        state = dqn.tick(state)
        env_state = out.state
        rewards.append(float(out.reward))
        lats.append(float(out.latency_ms))
        moved.append(int(out.moved))

    return state, History(
        rewards=np.asarray(rewards),
        latencies=np.asarray(lats),
        moved=np.asarray(moved),
        final_assignment=np.asarray(env_state.X),
    )


def greedy_assignment_ddpg(key, env, cfg: DDPGConfig, state: DDPGState,
                           env_state) -> jnp.ndarray:
    """Deploy-time action of a trained agent (no exploration)."""
    s_vec = env.state_vector(env_state)
    return ddpg.select_action(key, state, cfg, s_vec, explore=False,
                              exact_host_knn=True)
