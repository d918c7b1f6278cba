"""Elastic lane lifecycle: early-stop, compact, and search scenario fleets.

The fixed-grid fleet runner (``core/agent.run_online_fleet``) spends
identical compute on every lane, converged or not.  The paper's claim is
that model-free control *quickly* reaches a good schedule during online
learning — so for most scenario lanes most epochs of a fixed grid are
wasted.  This module converts fleet compute from fixed-grid to
budget-aware:

* **Per-lane early stopping** — :class:`StopRule` is a jit-compatible
  plateau test on the smoothed reward trace (:func:`plateau_converged`).
  The elastic runner reuses the ``checkpoint=`` chunking machinery: the
  epoch scan is cut every ``rule.check_every`` epochs (or the checkpoint
  cadence when one is attached) and the rule runs at each boundary.

* **Lane compaction** — lanes the rule marks done stop paying compute:
  between chunks :func:`compact_lanes` gathers the survivors into a
  smaller fleet (agent states, env states, PRNG keys, and the STACKED
  leaves of an EnvParams scenario fleet — broadcast-invariant leaves pass
  through single-copy) and, on a mesh, re-places them with
  ``sharding/fleet.py``.  ``shard_map`` partitions evenly, so meshed
  fleets compact to multiples of the data-axis device count
  (``sharding.fleet.compaction_size``); the gap rides as already-stopped
  "passenger" lanes whose extra epochs are discarded.  Compaction is
  loss-free: a surviving lane's trajectory bit-matches the uncompacted
  run on the host mesh (lanes are independent; pinned in
  tests/test_lifecycle.py).

* **Successive-halving scenario search** — :func:`search_scenarios`
  launches a wide fleet of perturbed scenarios
  (``dsdps/scenarios.build_for`` + ``sample_perturbed``), prunes the
  bottom half at each rung by eval reward, refills freed lanes with fresh
  perturbations, and returns a ranked :class:`Leaderboard`.  This is the
  Decima-style adaptively-curated workload set, and the Vaquero &
  Cuadrado online budget reallocation, on top of our fleet runner.

Entry points: ``run_online_fleet(..., lifecycle=StopRule(...))`` for the
drop-in path, :func:`run_online_fleet_elastic` for the full
:class:`ElasticResult` accounting, ``drl_control --scenario-search`` and
``fleet_bench --lifecycle`` from the command line.  The narrative
walkthrough lives in docs/elastic_fleets.md."""
from __future__ import annotations

import dataclasses
import json
import pathlib
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.agent import (History, chunk_schedule, prepare_fleet,
                              reset_fleet_states, run_fleet_chunk)
from repro.core.api import Agent
from repro.diagnostics import maybe_check_finite, span
from repro.dsdps.simulator import lane_params, params_in_axes, stack_env_params
from repro.sharding.fleet import (compaction_size, fleet_host,
                                  fleet_host_tree, is_spanning, shard_fleet)


class StopRule(NamedTuple):
    """Jit-compatible plateau test on the smoothed per-lane reward.

    A lane is converged when the mean reward of its last ``window`` epochs
    improves on the mean of the ``window`` before that by no more than
    ``rel_tol`` (relative to the reward magnitude) — window means ARE the
    smoother, so single noisy epochs cannot stop a lane.  ``min_epochs``
    lower-bounds how early any lane may stop; ``check_every`` is the chunk
    cadence at which the rule runs when no checkpoint cadence drives the
    chunking.  A NamedTuple of numbers → hashable → rides jit as a static
    argument."""

    window: int = 8
    rel_tol: float = 0.01
    min_epochs: int = 16
    check_every: int = 8

    @property
    def warmup(self) -> int:
        """Epochs of history the rule needs before it can fire."""
        return max(self.min_epochs, 2 * self.window)


@partial(jax.jit, static_argnames=("rule",))
def plateau_converged(recent: jnp.ndarray, rule: StopRule) -> jnp.ndarray:
    """Per-lane plateau verdict over the last ``2 * rule.window`` epochs.

    ``recent`` is ``[..., 2*window]`` reward history (the elastic runner
    slices it from the accumulating trace at each chunk boundary).  Fixed
    input shape → one compile per (shape, rule); usable INSIDE a jitted
    scan as well as between chunks."""
    W = rule.window
    prev = recent[..., :W].mean(axis=-1)
    last = recent[..., W:].mean(axis=-1)
    scale = jnp.maximum(jnp.maximum(jnp.abs(prev), jnp.abs(last)), 1e-9)
    return (last - prev) <= rule.rel_tol * scale


def compact_lanes(idx, keys, states, env_states, env_params, ref):
    """Gather lanes ``idx`` of the fleet carries into a smaller fleet.

    ``keys`` / ``states`` / ``env_states`` gather their leading fleet
    axis; ``env_params`` gathers only its STACKED leaves (one more leading
    axis than the single-scenario reference ``ref``) — broadcast-invariant
    leaves pass through as the single copy they are, so a
    ``stack_env_params(..., broadcast_invariant=True)`` fleet stays
    broadcast-invariant after compaction and the ``params_in_axes`` spec
    is unchanged.  Returns ``(keys, states, env_states, env_params)``."""
    idx = jnp.asarray(idx)
    take = lambda tree: jax.tree.map(lambda x: jnp.take(x, idx, axis=0), tree)
    keys = jnp.take(keys, idx, axis=0)
    states = take(states)
    env_states = take(env_states)
    if env_params is not None:
        flat, treedef = jax.tree_util.tree_flatten(env_params)
        ref_flat = jax.tree_util.tree_leaves(ref)
        picked = [jnp.take(p, idx, axis=0) if jnp.ndim(p) == jnp.ndim(r) + 1
                  else p for p, r in zip(flat, ref_flat)]
        env_params = jax.tree_util.tree_unflatten(treedef, picked)
    return keys, states, env_states, env_params


@dataclasses.dataclass
class ElasticResult:
    """Outcome of an elastic fleet run, in ORIGINAL lane order.

    ``history`` carries full ``[F, T]`` traces: a lane stopped at epoch e
    repeats its epoch-(e-1) reward/latency from e on (moved pads with 0),
    so downstream seed-band plotting keeps working; ``epochs_run[i]``
    says where lane i's real trace ends.  ``executed_lane_epochs`` counts
    every lane-epoch actually executed — passengers included — which is
    what ``fleet_bench --lifecycle`` compares against the fixed grid.
    ``lane_ids[i]`` names row i's lane in the RUN THAT STARTED the
    lifecycle — a fresh run numbers 0..F-1; a run resumed from a
    compacted snapshot (:func:`restore_elastic`) keeps the original
    numbering of the surviving lanes."""

    states: Any                     # [F] stacked agent states
    history: History                # [F, T] padded traces
    epochs_run: np.ndarray          # [F] epochs each lane really executed
    executed_lane_epochs: int
    fixed_grid_lane_epochs: int
    lane_ids: np.ndarray = None     # [F] original lane names

    @property
    def savings(self) -> float:
        """Fraction of the fixed grid's lane-epochs NOT executed."""
        return 1.0 - self.executed_lane_epochs / max(
            self.fixed_grid_lane_epochs, 1)


def run_online_fleet_elastic(
    keys: jax.Array,
    env,
    agent: Agent,
    states,
    T: int,
    rule: StopRule | None = None,
    updates_per_epoch: int = 1,
    explore: bool = True,
    env_states=None,
    env_params=None,
    mesh=None,
    checkpoint=None,
    start_epoch: int = 0,
    stop_fn: Callable[[np.ndarray, int], np.ndarray] | None = None,
    lane_ids: np.ndarray | None = None,
) -> ElasticResult:
    """``run_online_fleet`` with the elastic lane lifecycle.

    Identical call surface and per-epoch semantics as the fixed-grid
    runner (same chunked scan, same key discipline — a lane's trajectory
    up to its stop epoch bit-matches the fixed-grid run on the host mesh),
    plus: at every chunk boundary the :class:`StopRule` marks plateaued
    lanes done, their final carries are captured, and the surviving lanes
    are compacted into a smaller fleet (re-placed against ``mesh`` when
    sharded, padded with passenger lanes to keep the fleet divisible).

    ``checkpoint`` snapshots the COMPACTED carries with a ``lane_map``
    naming the original lanes (passenger rows are marked ``-1`` — their
    states continued past their stop epoch and are not authoritative);
    restore with ``FleetCheckpoint.restore(..., with_lane_map=True)``.

    ``stop_fn(rewards_so_far, t) -> done[n_live]`` overrides the plateau
    test (rows are the live lanes' full ``[n_live, t]`` reward history) —
    the hook custom convergence criteria and the bit-match tests use.

    ``lane_ids`` names the lanes in the ORIGINAL run's numbering — pass
    the ids :func:`restore_elastic` returns when resuming a compacted
    snapshot, so checkpoint lane maps and the result's lane accounting
    keep referring to the original lanes across kill/resume cycles."""
    from repro.core.agent import _require_agent
    agent = _require_agent(agent)
    rule = rule if rule is not None else StopRule()
    T = int(T)
    if T < 1:
        raise ValueError(f"T must be >= 1, got {T}")
    F = int(jnp.asarray(keys).shape[0])
    keys, states, env_states, env_params, ref, params_axes, params_specs = \
        prepare_fleet(keys, env, states, env_states, env_params, mesh)

    every = getattr(checkpoint, "every", None) if checkpoint is not None \
        else None
    every = every or rule.check_every

    # -- per-original-lane output slots -------------------------------------
    rewards_buf = np.zeros((F, T), np.float32)
    lats_buf = np.zeros((F, T), np.float32)
    moved_buf = np.zeros((F, T), np.float32)
    epochs_run = np.full(F, T, np.int64)
    final_states: list[Any] = [None] * F
    final_X: list[Any] = [None] * F

    # -- compact-fleet bookkeeping ------------------------------------------
    orig = np.arange(F)              # compact position -> row in this run
    ids = (np.arange(F) if lane_ids is None
           else np.asarray(lane_ids, np.int64))  # row -> ORIGINAL lane name
    if ids.shape != (F,):
        raise ValueError(f"lane_ids must be [{F}], got {ids.shape}")
    live = np.ones(F, bool)          # False = passenger (already captured)
    executed = 0
    t = 0

    def host_carries(states_now, env_states_now):
        # chunk-boundary bookkeeping crosses host<->device on purpose, so
        # the diagnostics transfer guard is lifted here (as in the
        # stop-test/compaction block below); the guarded steady state is
        # the chunk scan itself.  On a process-spanning mesh fleet_host is
        # a cross-process allgather every process runs identically, so the
        # host-side lane bookkeeping below stays deterministic and in
        # lockstep across processes.
        with jax.transfer_guard("allow"):
            return fleet_host_tree(states_now), fleet_host_tree(env_states_now)

    def capture(pos: int, host_states, host_env_states) -> None:
        o = int(orig[pos])
        final_states[o] = jax.tree.map(lambda x: np.asarray(x[pos]),
                                       host_states)
        final_X[o] = np.asarray(host_env_states.X[pos])

    for n in chunk_schedule(T, every):
        states, env_states, keys, rewards, lats, moved = run_fleet_chunk(
            keys, states, env_states, env_params, env=env, agent=agent,
            T=n, updates_per_epoch=updates_per_epoch, explore=explore,
            params_axes=params_axes, mesh=mesh, params_specs=params_specs)
        executed += len(orig) * n
        maybe_check_finite((states, rewards),
                           f"run_online_fleet_elastic epoch {start_epoch + t + n}")
        with span("fleet.pull"):
            r, l, m = fleet_host(rewards), fleet_host(lats), fleet_host(moved)
        rows = orig[live]
        rewards_buf[rows, t:t + n] = r[live]
        lats_buf[rows, t:t + n] = l[live]
        moved_buf[rows, t:t + n] = m[live]
        t += n
        if checkpoint is not None:
            lane_map = np.where(live, ids[orig], -1).astype(np.int32)
            checkpoint.save(start_epoch + t, states, env_states, keys,
                            lane_map=lane_map)
        if t >= T:
            break

        # -- stop test at the chunk boundary (boundary work: guard lifted) --
        with jax.transfer_guard("allow"):
            if stop_fn is not None:
                done_rows = np.asarray(stop_fn(rewards_buf[rows, :t], t),
                                       bool)
            elif t >= rule.warmup:
                recent = jnp.asarray(rewards_buf[rows, t - 2 * rule.window:t])
                done_rows = np.asarray(plateau_converged(recent, rule))
            else:
                continue
            if not done_rows.any():
                continue
            h_states, h_env = host_carries(states, env_states)
            live_pos = np.flatnonzero(live)
            for pos in live_pos[done_rows]:
                capture(int(pos), h_states, h_env)
                o = int(orig[pos])
                epochs_run[o] = t
                rewards_buf[o, t:] = rewards_buf[o, t - 1]
                lats_buf[o, t:] = lats_buf[o, t - 1]
                moved_buf[o, t:] = 0.0
            live[live_pos[done_rows]] = False

            # -- compaction -------------------------------------------------
            n_live = int(live.sum())
            if n_live == 0:
                break
            target = compaction_size(n_live, mesh)
            if target < len(orig):
                keep = np.flatnonzero(live)
                if target > n_live:      # pad with most recent passengers
                    passengers = np.flatnonzero(~live)[::-1][:target - n_live]
                    keep = np.sort(np.concatenate([keep, passengers]))
                if mesh is not None and is_spanning(mesh):
                    # spanning arrays can't be gathered with plain
                    # jnp.take on-device (arbitrary cross-process
                    # gathers); bring the carries home — identically on
                    # every process — compact on host, and let
                    # shard_fleet below re-place against the global mesh
                    keys = fleet_host(keys)
                    states = fleet_host_tree(states)
                    env_states = fleet_host_tree(env_states)
                    if env_params is not None:
                        env_params = fleet_host_tree(env_params)
                keys, states, env_states, env_params = compact_lanes(
                    keep, keys, states, env_states, env_params, ref)
                orig, live = orig[keep], live[keep]
                if mesh is not None:
                    keys, states, env_states, env_params, params_specs = \
                        shard_fleet(mesh, keys, states, env_states,
                                    env_params, ref)

    # lanes still running at the horizon (or passengers never re-captured)
    if np.any(live):
        h_states, h_env = host_carries(states, env_states)
        for pos in np.flatnonzero(live):
            capture(int(pos), h_states, h_env)

    with jax.transfer_guard("allow"):
        states_out = jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)),
                                  *final_states)
    history = History(rewards=rewards_buf, latencies=lats_buf,
                      moved=moved_buf, final_assignment=np.stack(final_X))
    return ElasticResult(states=states_out, history=history,
                         epochs_run=epochs_run,
                         executed_lane_epochs=executed,
                         fixed_grid_lane_epochs=F * T,
                         lane_ids=ids)


def restore_elastic(checkpoint, states_like, env_states_like, keys_like,
                    env_params=None, ref=None, epoch: int | None = None,
                    mesh=None):
    """Restore a COMPACTED elastic-lifecycle snapshot for resumption.

    Elastic runs checkpoint their compacted carries with a ``lane_map``
    naming each row's original lane (``-1`` = passenger: a lane that
    already stopped and whose row continued past its stop epoch as
    divisibility padding — its checkpointed state is NOT authoritative).
    This helper restores the snapshot via ``FleetCheckpoint.restore(...,
    with_lane_map=True)``, DROPS the passenger rows, and — given the
    original run's stacked ``env_params`` scenario fleet plus its
    single-scenario ``ref`` — gathers the surviving lanes' scenario rows
    (broadcast-invariant leaves pass through single-copy).

    The ``*_like`` templates only supply tree STRUCTURE (the generic
    checkpointer takes shapes from the manifest), so templates built for
    the original full-size fleet restore any compacted snapshot.

    Returns ``(epoch, keys, states, env_states, env_params, lane_ids)``;
    feed everything straight back into :func:`run_online_fleet_elastic`
    with ``start_epoch=epoch`` and ``lane_ids=lane_ids``."""
    # on a process-spanning target mesh restore to HOST arrays: the
    # passenger-dropping row gather below can't run on spanning shards,
    # and run_online_fleet_elastic's prepare_fleet re-places the compacted
    # carries against the mesh anyway
    restore_mesh = None if (mesh is not None and is_spanning(mesh)) else mesh
    epoch, states, env_states, keys, lane_map = checkpoint.restore(
        states_like, env_states_like, keys_like, epoch=epoch,
        mesh=restore_mesh, with_lane_map=True)
    lane_map = np.asarray(lane_map)
    rows = np.flatnonzero(lane_map >= 0)
    ids = lane_map[rows].astype(np.int64)
    with jax.transfer_guard("allow"):
        take = jnp.asarray(rows)
        gather = lambda t: jax.tree.map(
            lambda x: jnp.take(jnp.asarray(x), take, axis=0), t)
        states, env_states = gather(states), gather(env_states)
        keys = jnp.take(jnp.asarray(keys), take, axis=0)
        if env_params is not None:
            if ref is None:
                raise ValueError("restoring with env_params= needs ref= "
                                 "(the env's default_params()) to tell "
                                 "stacked leaves from invariant ones")
            flat, treedef = jax.tree_util.tree_flatten(env_params)
            ref_flat = jax.tree_util.tree_leaves(ref)
            pick = jnp.asarray(ids)
            picked = [jnp.take(p, pick, axis=0)
                      if jnp.ndim(p) == jnp.ndim(r) + 1 else p
                      for p, r in zip(flat, ref_flat)]
            env_params = jax.tree_util.tree_unflatten(treedef, picked)
    return epoch, keys, states, env_states, env_params, ids


# --------------------------------------------------------------------------
# Successive-halving scenario search
# --------------------------------------------------------------------------
@dataclasses.dataclass
class ScenarioEntry:
    """One candidate scenario's search record."""

    cand: int            # candidate id (launch order)
    rung: int            # rungs completed (1-based)
    epochs: int          # cumulative training epochs this candidate got
    score: float         # eval reward: mean of its last eval_window epochs
    survived: bool       # still in the fleet after its last cut


@dataclasses.dataclass
class Leaderboard:
    """Ranked outcome of :func:`search_scenarios` (best score first).

    ``params[cand]`` holds each candidate's single-scenario EnvParams —
    re-stack the top entries with ``stack_env_params`` to train a full
    fleet on the curated set (the Decima discipline)."""

    entries: list[ScenarioEntry]
    rungs: tuple[int, ...]
    fleet: int
    total_lane_epochs: int
    params: dict[int, Any]

    def to_json(self) -> dict:
        return {
            "rungs": list(self.rungs),
            "fleet": self.fleet,
            "total_lane_epochs": self.total_lane_epochs,
            "leaderboard": [dataclasses.asdict(e) for e in self.entries],
        }

    def save(self, path: str | pathlib.Path) -> pathlib.Path:
        path = pathlib.Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(self.to_json(), indent=2))
        return path


def _tree_concat(a, b):
    return jax.tree.map(lambda x, y: jnp.concatenate([x, y], axis=0), a, b)


def search_scenarios(
    env,
    agent: Agent,
    scenario: str = "mixed",
    perturb: Callable[[jax.Array], Any] | None = None,
    fleet: int = 8,
    rungs: tuple[int, ...] = (16, 16, 32),
    eval_window: int = 8,
    updates_per_epoch: int = 1,
    explore: bool = True,
    refill: bool = True,
    seed: int = 0,
) -> Leaderboard:
    """Successive-halving search over perturbed scenarios.

    A ``fleet``-wide candidate set seeded from the named scenario builder
    (``dsdps/scenarios.build_for(env, scenario, fleet)``) trains through
    the rungs: after each rung every lane is scored by eval reward (mean
    training reward over its last ``eval_window`` epochs — higher is
    better, i.e. lower stabilized latency), the bottom half is pruned via
    :func:`compact_lanes`, and — with ``refill=True`` — the freed lanes
    are refilled with fresh perturbations (``perturb(key) -> params``,
    default ``dsdps.scenarios.perturb_sampler(env)``), so the fleet stays
    wide while compute concentrates on promising scenarios.  Survivors
    carry their agent state, env state, and PRNG key across rungs;
    refills start fresh (their ``epochs`` field says how long each
    candidate actually trained).

    Returns a :class:`Leaderboard` ranked by score, holding every
    candidate ever launched plus its EnvParams for curriculum reuse.
    Wired into ``drl_control --scenario-search`` and ``fleet_bench
    --lifecycle``."""
    from repro.core.agent import _require_agent
    from repro.dsdps import scenarios as scen
    agent = _require_agent(agent)
    if fleet < 2:
        raise ValueError(f"search needs fleet >= 2, got {fleet}")
    ref = env.default_params()
    if perturb is None:
        perturb = scen.perturb_sampler(env)
    key = jax.random.PRNGKey(seed)

    stacked = scen.build_for(env, scenario, fleet)
    cand_params = {i: lane_params(stacked, ref, i) for i in range(fleet)}
    current = list(range(fleet))
    next_id = fleet
    key, k_init, k_lane, k_env = jax.random.split(key, 4)
    states = agent.init_fleet(k_init, fleet, env_params=stacked, env=env)
    keys = jax.random.split(k_lane, fleet)
    env_states = reset_fleet_states(
        jax.random.split(k_env, fleet), env, stacked)

    entries: dict[int, ScenarioEntry] = {}
    epochs_done = {c: 0 for c in current}
    total = 0
    for r, n in enumerate(rungs):
        stacked = stack_env_params([cand_params[c] for c in current])
        states, env_states, keys, rewards, _, _ = run_fleet_chunk(
            keys, states, env_states, stacked, env=env, agent=agent,
            T=int(n), updates_per_epoch=updates_per_epoch, explore=explore,
            params_axes=params_in_axes(stacked, ref))
        total += len(current) * int(n)
        scores = np.asarray(rewards)[:, -min(eval_window, int(n)):].mean(
            axis=1)
        for i, c in enumerate(current):
            epochs_done[c] += int(n)
            entries[c] = ScenarioEntry(cand=c, rung=r + 1,
                                       epochs=epochs_done[c],
                                       score=float(scores[i]), survived=True)
        if r == len(rungs) - 1:
            break

        # -- the halving cut ------------------------------------------------
        n_keep = max(1, len(current) // 2)
        keep = np.sort(np.argsort(-scores)[:n_keep])
        for i, c in enumerate(current):
            if i not in set(keep.tolist()):
                entries[c] = dataclasses.replace(entries[c], survived=False)
        keys, states, env_states, _ = compact_lanes(
            keep, keys, states, env_states, stacked, ref)
        current = [current[i] for i in keep]

        if refill:
            new_ids = []
            for _ in range(fleet - len(current)):
                key, k_p = jax.random.split(key)
                cand_params[next_id] = perturb(k_p)
                new_ids.append(next_id)
                next_id += 1
            new_stacked = stack_env_params([cand_params[c] for c in new_ids])
            key, k_i, k_l, k_e = jax.random.split(key, 4)
            new_states = agent.init_fleet(k_i, len(new_ids),
                                          env_params=new_stacked, env=env)
            new_keys = jax.random.split(k_l, len(new_ids))
            new_env = reset_fleet_states(
                jax.random.split(k_e, len(new_ids)), env, new_stacked)
            states = _tree_concat(states, new_states)
            env_states = _tree_concat(env_states, new_env)
            keys = jnp.concatenate([keys, new_keys], axis=0)
            current += new_ids
            epochs_done.update({c: 0 for c in new_ids})

    ranked = sorted(entries.values(), key=lambda e: -e.score)
    return Leaderboard(entries=ranked, rungs=tuple(int(n) for n in rungs),
                       fleet=fleet, total_lane_epochs=total,
                       params=cand_params)
