"""Elastic re-meshing: when workers die, pick the best surviving mesh and
resume from the latest checkpoint (restore is device-count-independent —
checkpoint/checkpointer.py stores full arrays and re-places them).

Two restore families share the mesh-planning policy here:

* **LM training** (``train/trainer.py``): keep the model axis intact if
  possible (TP groups span a pod's fast ICI; losing a chip inside a TP
  group forces the whole host group out), shrink the data axis to the
  largest value that fits the survivors — FSDP width shrinks, per-step
  global batch shrinks with it, and training resumes.  Plan with
  :func:`plan_mesh` and restore through the trainer's sharding policy.

* **Fleet control runs** (``core/agent.run_online_fleet``):
  :func:`resume_after_failure` plans a data-only mesh over the survivors
  and restores the fleet carries — agent states built by
  ``make_agent(...).init_fleet``, env states, and evolved PRNG keys —
  through :meth:`repro.checkpoint.fleet.FleetCheckpoint.restore`, which
  re-places every lane against the NEW mesh (replication fallback when
  the fleet no longer divides the device count).  Elastic-lifecycle runs
  (repro/fleet/lifecycle.py) checkpoint a COMPACTED fleet with a lane
  map; pass ``with_lane_map=True`` to recover which original lanes the
  surviving rows are.  The walkthrough lives in docs/elastic_fleets.md.
"""
from __future__ import annotations

import dataclasses

import jax

from repro.launch.mesh import auto_mesh


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    shape: tuple[int, ...]
    axes: tuple[str, ...]

    @property
    def device_count(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


def plan_mesh(alive_devices: int, model_parallel: int = 16,
              multi_pod: bool = False) -> MeshPlan:
    """Largest (data, model) grid that fits the survivors.

    ``model_parallel=1`` plans the data-only ``(n, 1)`` grid fleet
    control runs use (``launch.mesh.make_fleet_mesh``); the multi-host
    driver (``repro.launch.multihost``) calls it that way to size the
    reduced mesh after a worker process dies."""
    alive_devices = int(alive_devices)
    if alive_devices < 1:
        raise ValueError(
            f"cannot plan a mesh over {alive_devices} alive device(s)")
    if alive_devices < model_parallel:
        # degrade TP too (rare: an entire pod's worth of failures)
        mp = 1
        while mp * 2 <= alive_devices:
            mp *= 2
        model_parallel = mp
    data = alive_devices // model_parallel
    if multi_pod and data % 2 == 0 and data >= 2:
        return MeshPlan((2, data // 2, model_parallel),
                        ("pod", "data", "model"))
    return MeshPlan((data, model_parallel), ("data", "model"))


def make_mesh(plan: MeshPlan):
    return auto_mesh(plan.shape, plan.axes)


def resume_after_failure(checkpoint, env, agent, keys, states,
                         env_states=None, env_params=None,
                         alive_devices: int | None = None,
                         with_lane_map: bool = False):
    """Full elastic-restart path for a fleet control run: plan a data-only
    mesh over the survivors, restore the fleet carries re-placed against
    it, and hand back everything ``run_online_fleet`` needs to continue.

    ``checkpoint`` — a :class:`repro.checkpoint.fleet.FleetCheckpoint`
    over the dead run's directory; ``agent`` — the same
    ``make_agent(...)`` bundle the run trained (its ``init_fleet`` builds
    the agent-state structure template via ``states``); ``keys`` /
    ``states`` / ``env_states`` — structure templates for the carries
    (freshly-initialized values; shapes/dtypes/structure are what
    matters, see ``reset_fleet_states``); ``env_params`` — the run's
    scenario fleet, needed to rebuild the env-state template when
    ``env_states`` is None; ``alive_devices`` — surviving device count
    (default: every device jax still sees).  ``with_lane_map=True`` reads
    an elastic-lifecycle snapshot and appends the original-lane index
    array to the return.

    Returns ``(mesh, epoch, states, env_states, keys[, lane_map])`` —
    feed them to ``run_online_fleet(..., mesh=mesh, start_epoch=epoch,
    T=remaining)`` (the launcher's ``--resume`` flag is this function as
    a CLI)."""
    from repro.core.agent import reset_fleet_states
    from repro.core.api import Agent
    from repro.launch.mesh import make_fleet_mesh
    if not isinstance(agent, Agent):
        raise TypeError(
            f"expected an api.Agent (make_agent(...)), got "
            f"{type(agent).__name__} — the pre-v1 policy_cls/cfg call "
            f"style was removed with the PR-2 deprecation window")
    n = len(jax.devices()) if alive_devices is None else int(alive_devices)
    mesh = make_fleet_mesh(n)
    if env_states is None:
        env_states = reset_fleet_states(keys, env, env_params)
    out = checkpoint.restore(states, env_states, keys, mesh=mesh,
                             with_lane_map=with_lane_map)
    return (mesh, *out)
