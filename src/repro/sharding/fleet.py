"""Fleet-axis sharding: partition scenario-fleet carries over a mesh.

The fleet runner (``core/agent.run_online_fleet``) vmaps one online run
over a leading ``[fleet]`` axis; everything here is about placing that
axis over hardware.  A mesh's *data* axes (every axis except ``"model"``,
matching :class:`repro.sharding.policy.MeshAxes`) carry the fleet: lane
arrays — stacked PRNG keys, agent states, env states, and the stacked
leaves of a scenario ``EnvParams`` fleet — shard their leading axis over
those devices, while broadcast-invariant params leaves (kept single-copy
by ``stack_env_params(..., broadcast_invariant=True)``) replicate.

Two entry points:

* :func:`fleet_shardings` — a matching pytree of ``NamedSharding`` for
  any fleet-stacked carry tree (used by elastic checkpoint restore to
  re-place loaded lanes against the *current* mesh);
* :func:`shard_fleet` — ``device_put`` the runner's four input trees onto
  the mesh and return the hashable params PartitionSpec tree the sharded
  program needs.

Meshes may SPAN processes (``launch.mesh.make_fleet_mesh(spanning=True)``
under ``jax.distributed`` — the multi-host mega-fleet axis):
:func:`put_global` then assembles global arrays from each process's
addressable shards instead of ``device_put``, and :func:`fleet_host`
brings fleet arrays home with a cross-process allgather so every process
sees identical full traces (docs/sharded_fleets.md#multi-host-fleets).

On :func:`repro.launch.mesh.make_host_mesh` (one CPU device) every spec
degenerates to a single shard, so the sharded code path stays
bit-comparable to the plain vmap path — that is what the CPU equivalence
tests pin."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def is_spanning(mesh: Mesh) -> bool:
    """True when ``mesh`` spans devices of more than one process — the
    multi-host fleet case (``launch.mesh.make_fleet_mesh(spanning=True)``
    under ``jax.distributed``).  Spanning meshes change how arrays are
    placed (each process feeds only its addressable shard:
    :func:`put_global`) and how results come home
    (:func:`fleet_host`)."""
    return any(d.process_index != jax.process_index()
               for d in mesh.devices.flat)


def put_global(x, sharding: NamedSharding):
    """Place a host (or process-local) value onto ``sharding``.

    For fully-addressable shardings this is plain ``jax.device_put``.
    For process-spanning shardings ``device_put`` of a host array is
    illegal, so the global array is assembled with
    ``jax.make_array_from_callback``: every process holds the SAME full
    host value (fleet carries are built deterministically from shared
    seeds, or read back from a checkpoint every process can see) and
    contributes only the slices its own devices own."""
    if sharding.is_fully_addressable:
        return jax.device_put(x, sharding)
    host = np.asarray(x)
    return jax.make_array_from_callback(host.shape, sharding,
                                        lambda idx: host[idx])


def fleet_host(x) -> np.ndarray:
    """Full host value of a fleet array on EVERY process.

    ``np.asarray`` for ordinary (fully-addressable) arrays; for arrays
    sharded over a process-spanning mesh the fleet-axis shards are
    re-assembled with a cross-process allgather
    (``multihost_utils.process_allgather``), and fully-replicated
    spanning arrays just read their local copy.  Deterministic and
    identical across processes — which is what lets every process run
    the same host-side trace accounting / elastic lane bookkeeping
    without diverging."""
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        if x.sharding.is_fully_replicated:
            return np.asarray(x.addressable_shards[0].data)
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    return np.asarray(x)


def fleet_host_tree(tree):
    """:func:`fleet_host` over every leaf of a pytree."""
    return jax.tree.map(fleet_host, tree)


def fleet_axes(mesh: Mesh) -> tuple[str, ...]:
    """The mesh axes that carry the fleet: every axis except ``"model"``
    (the same data/FSDP grouping as ``sharding.policy.MeshAxes``)."""
    return tuple(n for n in mesh.axis_names if n != "model")


def fleet_size(mesh: Mesh) -> int:
    """Number of devices the fleet axis is partitioned over."""
    return int(np.prod([mesh.shape[a] for a in fleet_axes(mesh)]))


def fleet_spec(mesh: Mesh) -> P:
    """PartitionSpec sharding an array's leading (fleet) axis over the
    mesh's data axes; trailing dims stay unsharded."""
    return P(fleet_axes(mesh))


def fleet_shardings(mesh: Mesh, tree):
    """Matching pytree of ``NamedSharding`` placing every leaf's leading
    ``[fleet]`` axis over the mesh's data axes.

    Leaves that cannot shard — scalars, or a leading dim not divisible by
    the data-axis size — fall back to replication instead of erroring, so
    a checkpoint written for fleet=8 restores on a 3-device mesh (lanes
    replicated) rather than crashing: the elastic-restore contract."""
    axes = fleet_axes(mesh)
    n = fleet_size(mesh)

    def leaf_sharding(x):
        shape = np.shape(x)
        if len(shape) >= 1 and n > 0 and shape[0] % n == 0:
            return NamedSharding(mesh, P(axes))
        return NamedSharding(mesh, P())

    return jax.tree.map(leaf_sharding, tree)


def params_partition_specs(params, ref, mesh: Mesh):
    """Per-leaf PartitionSpec tree for a (possibly broadcast-invariant)
    stacked params fleet: stacked leaves shard their leading ``[F]`` axis
    over the mesh's data axes, broadcast-invariant leaves replicate.  A
    single-scenario ``params`` (nothing stacked vs ``ref``) replicates
    everywhere.  The result has the params' own container structure
    (a NamedTuple of PartitionSpecs → hashable → valid jit static arg)."""
    axes = fleet_axes(mesh)
    flat, treedef = jax.tree_util.tree_flatten(params)
    ref_flat = jax.tree_util.tree_leaves(ref)
    if len(flat) != len(ref_flat):
        raise ValueError("params and reference pytrees differ in structure")
    specs = [P(axes) if np.ndim(p) == np.ndim(r) + 1 else P()
             for p, r in zip(flat, ref_flat)]
    return jax.tree_util.tree_unflatten(treedef, specs)


def compaction_size(n_live: int, mesh: Mesh | None) -> int:
    """Smallest lane count ≥ ``n_live`` a compacted fleet may shrink to.

    ``shard_map`` partitions the fleet axis evenly, so on a mesh the
    elastic lane lifecycle (repro/fleet/lifecycle.py) can only compact to
    multiples of the data-axis device count — the gap is padded with
    already-stopped "passenger" lanes whose extra epochs are discarded.
    Without a mesh (plain vmap) any size works and this is ``n_live``."""
    if mesh is None:
        return int(n_live)
    n = fleet_size(mesh)
    return int(-(-int(n_live) // n) * n)          # ceil to a multiple of n


def _buffer_ids(x) -> set:
    return {(s.device.id, s.data.unsafe_buffer_pointer())
            for s in x.addressable_shards}


def own_buffers(read, carries):
    """``carries`` with every leaf on device buffers of its own.

    A donating call refuses a buffer that backs two of its arguments, and
    fleet arrays often share one: an eager ``init_fleet`` returns its
    online nets as their targets too, and an env reset passes its params'
    arrays through.  Each carry leaf whose buffers also back a leaf of
    ``read`` (passed to the same call, not donated) or an earlier carry
    leaf is copied."""
    seen = set().union(*map(_buffer_ids, jax.tree.leaves(read)))

    def own(x):
        ids = _buffer_ids(x)
        if ids & seen:
            x = jnp.copy(x)
            ids = _buffer_ids(x)
        seen.update(ids)
        return x

    return jax.tree.map(own, carries)


def shard_fleet(mesh: Mesh, keys, states, env_states, env_params, ref):
    """Place the fleet runner's carries on ``mesh``.

    ``keys``/``states``/``env_states`` shard their leading fleet axis over
    the mesh's data axes; ``env_params`` shards only its stacked leaves
    (``ref`` — the env's single-scenario ``default_params()`` — tells the
    two apart), replicating broadcast-invariant ones.  The fleet size must
    divide the data-axis device count (``shard_map`` partitions evenly).

    Returns ``(keys, states, env_states, env_params, params_specs)`` with
    every array committed to its ``NamedSharding``, every carry leaf on
    buffers of its own (:func:`own_buffers`: the sharded program donates
    the carries on accelerators) and ``params_specs`` the hashable
    PartitionSpec tree for the sharded program."""
    n = fleet_size(mesh)
    F = int(np.shape(keys)[0])
    if F % n != 0:
        raise ValueError(
            f"fleet size {F} does not divide over the mesh's {n} data-axis "
            f"devices; pick a fleet that is a multiple of {n} (or run the "
            f"un-sharded vmap path with mesh=None)")
    spec = fleet_spec(mesh)
    shard = NamedSharding(mesh, spec)
    put = lambda tree: jax.tree.map(lambda x: put_global(x, shard), tree)
    keys = put_global(keys, shard)
    states = put(states)
    env_states = put(env_states)
    params_specs = params_partition_specs(env_params, ref, mesh)
    env_params = jax.tree.map(
        lambda x, s: put_global(x, NamedSharding(mesh, s)),
        env_params, params_specs)
    keys, states, env_states = own_buffers(env_params,
                                           (keys, states, env_states))
    return keys, states, env_states, env_params, params_specs
