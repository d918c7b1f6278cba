"""Mesh construction + multi-process (multi-host) initialization.

Everything here is a FUNCTION, not a module-level constant: importing
this module never touches jax device state.

Three mesh families:

* :func:`make_production_mesh` — the accelerator training mesh, derived
  from ``jax.device_count()`` (documented pod shapes — 16×16 single pod,
  2×16×16 multi-pod — when enough chips are visible, the largest
  (data, model) grid that fits otherwise);
* :func:`make_host_mesh` — the degenerate 1×1 CPU mesh that anchors the
  bit-comparability tests;
* :func:`make_fleet_mesh` — the data-only ``(n, 1)`` mesh the fleet
  runner shards scenario lanes over.  With ``spanning=True`` the mesh
  spans EVERY process of a ``jax.distributed`` job — the multi-host
  mega-fleet axis (docs/sharded_fleets.md#multi-host-fleets).

:func:`init_distributed` is the process-spanning entry point: call it
first thing in every worker process (before any other jax API touches
the backend), then build spanning meshes.  Single-process calls are a
no-op, so the same launcher script runs unmodified on one host."""
from __future__ import annotations

import os

import jax
import numpy as np

# env vars the localhost driver (repro.launch.multihost) sets for its
# workers; real clusters can export the same three variables
COORDINATOR_ENV = "REPRO_COORDINATOR"
NUM_PROCESSES_ENV = "REPRO_NUM_PROCESSES"
PROCESS_ID_ENV = "REPRO_PROCESS_ID"

_DISTRIBUTED = {"initialized": False}


def init_distributed(coordinator_address: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None) -> tuple[int, int]:
    """Join (or skip) a multi-process jax job; returns (process_id, n).

    Arguments default to the ``REPRO_COORDINATOR`` /
    ``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID`` environment variables
    (what ``repro.launch.multihost`` exports for its localhost workers).
    With no coordinator configured — or ``num_processes <= 1`` — this is
    a NO-OP returning ``(0, 1)``: the same worker script runs
    single-process without edits, which is also what keeps the
    CI-executed docs snippet runnable.

    Must be called BEFORE anything else initializes the jax backend.  On
    the CPU backend the cross-process collectives implementation is
    switched to gloo (the default, ``"none"``, refuses multi-process
    computations outright).  Idempotent: a second call returns the
    current (process_index, process_count) without re-initializing."""
    if _DISTRIBUTED["initialized"]:
        return jax.process_index(), jax.process_count()
    env = os.environ
    if coordinator_address is None:
        coordinator_address = env.get(COORDINATOR_ENV)
    if num_processes is None and env.get(NUM_PROCESSES_ENV):
        num_processes = int(env[NUM_PROCESSES_ENV])
    if process_id is None and env.get(PROCESS_ID_ENV):
        process_id = int(env[PROCESS_ID_ENV])
    if coordinator_address is None or (num_processes or 1) <= 1:
        return 0, 1
    # CPU cross-process computations need a real collectives backend
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=int(num_processes),
                               process_id=int(process_id))
    _DISTRIBUTED["initialized"] = True
    return jax.process_index(), jax.process_count()


def auto_mesh(shape, axes, devices=None):
    """A mesh whose axes are all ``AxisType.Auto`` — the one place meshes
    are built.  ``jax.make_mesh`` defaults to Explicit axes, which put the
    mesh axis into every placed array's type; a carry restored onto such a
    mesh then refuses the fleet runner's plain vmap ("should be sharded
    the same").  Auto axes leave sharding to the compiler, as the fleet
    and training paths expect.  ``devices`` defaults to jax's own pick."""
    axis_types = (jax.sharding.AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types)
    return jax.sharding.Mesh(np.asarray(devices).reshape(tuple(shape)),
                             tuple(axes), axis_types=axis_types)


def make_production_mesh(*, multi_pod: bool = False):
    """The accelerator training mesh, sized to the visible devices.

    With a full pod (256+ chips) this is the documented v5e shape —
    16×16 = 256 over ``("data", "model")``, or 2×16×16 = 512 with a
    leading "pod" axis when ``multi_pod`` — and on anything smaller it
    degrades to the largest (data, model) grid that fits
    (``fault.elastic.plan_mesh``: model-parallel width halves until it
    divides, data takes the rest), so a laptop or CI host gets a 1×1
    mesh instead of a crash."""
    from repro.fault.elastic import plan_mesh
    plan = plan_mesh(jax.device_count(), model_parallel=16,
                     multi_pod=multi_pod)
    return auto_mesh(plan.shape, plan.axes)


def make_host_mesh():
    """Degenerate 1×1 mesh over the single real CPU device — used by smoke
    tests and examples so the same pjit code paths run un-sharded.  For
    the fleet runner this is the bit-comparability anchor: a
    ``run_online_fleet(..., mesh=make_host_mesh())`` run shards nothing,
    so its lanes match the plain vmap path."""
    return auto_mesh((1, 1), ("data", "model"))


def make_fleet_mesh(n_devices: int | None = None, *, spanning: bool = False):
    """Data-only mesh for fleet sharding: shape ``(n, 1)`` over
    ``("data", "model")``, so the fleet axis of a ``run_online_fleet(...,
    mesh=...)`` call partitions over all ``n`` devices while the "model"
    axis stays degenerate (control-policy nets are tiny; lanes, not
    layers, are what need the memory).

    ``spanning=False`` (default) uses this PROCESS's devices — on a
    single-process job that is every visible device, identical to the
    pre-multi-host behavior.  ``spanning=True`` builds the mesh over the
    GLOBAL device list of a ``jax.distributed`` job
    (:func:`init_distributed`): an ``(n_hosts * devices_per_host, 1)``
    data mesh every process participates in — each process then feeds
    and reads only its addressable shard of the fleet carries
    (``sharding/fleet.py`` handles the global placement).  In a
    single-process job ``spanning=True`` degenerates to the local mesh,
    so the same code path runs everywhere."""
    devices = list(jax.devices()) if spanning else list(jax.local_devices())
    n = len(devices) if n_devices is None else int(n_devices)
    return auto_mesh((n, 1), ("data", "model"), devices=devices[:n])
