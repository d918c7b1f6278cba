"""Device-sharded scenario fleets (run_online_fleet(..., mesh=...)).

The contract under test: (a) on the host mesh (launch.mesh.make_host_mesh,
the one CPU device) the sharded path is bit-comparable to the plain vmap runner,
(b) params partition specs mirror params_in_axes (stacked leaves shard,
broadcast-invariant leaves replicate) and stay hashable, (c) indivisible
fleets fail loudly, and (d) on a REAL 2-device mesh (subprocess with
--xla_force_host_platform_device_count=2) lane i still matches the
un-sharded run and a checkpoint written under the 2-device mesh restores
against a different device count (elastic re-placement)."""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import ddpg, make_agent
from repro.core.agent import reset_fleet_states, run_online_fleet
from repro.core.ddpg import DDPGConfig
from repro.dsdps import SchedulingEnv, apps, scenarios
from repro.dsdps.apps import default_workload
from repro.launch.mesh import make_host_mesh
from repro.sharding.fleet import (fleet_axes, fleet_shardings, fleet_size,
                                  fleet_spec, params_partition_specs,
                                  shard_fleet)


@pytest.fixture(scope="module")
def small_env():
    topo = apps.continuous_queries("small")
    return SchedulingEnv(topo, default_workload(topo))


@pytest.fixture(scope="module")
def ddpg_agent(small_env):
    cfg = DDPGConfig(n_executors=small_env.N, n_machines=small_env.M,
                     state_dim=small_env.state_dim, k_nn=4)
    return make_agent("ddpg", small_env, cfg=cfg)


def test_fleet_axes_and_spec():
    mesh = make_host_mesh()
    assert fleet_axes(mesh) == ("data",)
    assert fleet_size(mesh) == 1
    assert fleet_spec(mesh) == P(("data",))


def test_params_partition_specs(small_env):
    env = small_env
    p = env.default_params()
    mesh = make_host_mesh()
    bc = scenarios.build("one_slow_machine", env, 3, broadcast_invariant=True)
    specs = params_partition_specs(bc, p, mesh)
    # stacked leaves shard the fleet axis, invariant leaves replicate
    assert specs.speed == P(("data",))
    assert specs.routing == P() and specs.flow_solve == P()
    # single-scenario params replicate everywhere
    single = params_partition_specs(p, p, mesh)
    assert all(s == P() for s in single)
    # hashable: the sharded program takes the spec tree as a static arg
    assert hash(specs) == hash(params_partition_specs(
        scenarios.build("one_slow_machine", env, 3, broadcast_invariant=True),
        p, mesh))


def test_fleet_shardings_shapes(small_env):
    mesh = make_host_mesh()
    tree = {"stacked": np.zeros((4, 3)), "vector": np.zeros(4),
            "scalar": np.float32(1.0)}
    sh = fleet_shardings(mesh, tree)
    assert isinstance(sh["stacked"], NamedSharding)
    assert sh["stacked"].spec == P(("data",))
    assert sh["vector"].spec == P(("data",))
    assert sh["scalar"].spec == P()          # scalars replicate


def test_shard_fleet_gives_every_carry_leaf_its_own_buffer(small_env,
                                                           ddpg_agent):
    """The sharded program donates the carries on accelerators, and a
    donating call refuses a buffer passed twice: init_fleet returns the
    online nets as their targets, and reset passes params arrays through."""
    env, F = small_env, 2
    states = ddpg_agent.init_fleet(jax.random.PRNGKey(0), F)
    keys = jax.random.split(jax.random.PRNGKey(1), F)
    env_states = reset_fleet_states(keys, env)
    ref = env.default_params()

    def buffers(tree):
        return [(s.device.id, s.data.unsafe_buffer_pointer())
                for x in jax.tree.leaves(tree) for s in x.addressable_shards]

    assert len(set(buffers(states))) < len(buffers(states))   # aliased in
    keys, states, env_states, params, _ = shard_fleet(
        make_host_mesh(), keys, states, env_states, ref, ref)
    carried = buffers((keys, states, env_states))
    assert len(set(carried)) == len(carried)
    assert not set(carried) & set(buffers(params))


def test_host_mesh_lane_equivalence(small_env, ddpg_agent):
    """The ISSUE-4 acceptance gate: lane i of a mesh-sharded
    run_online_fleet bit-matches lane i of the single-device vmap run on
    the host mesh (the broadcast-matmul ulp caveat does not bite here —
    both paths lower the same program on one device)."""
    env, agent = small_env, ddpg_agent
    F, T = 4, 8
    params = scenarios.build("mixed", env, F, broadcast_invariant=True)
    states = agent.init_fleet(jax.random.PRNGKey(0), F, env_params=params,
                              env=env)
    keys = jax.random.split(jax.random.PRNGKey(1), F)
    s_v, h_v = run_online_fleet(keys, env, agent, states, T=T,
                                env_params=params)
    s_m, h_m = run_online_fleet(keys, env, agent, states, T=T,
                                env_params=params, mesh=make_host_mesh())
    np.testing.assert_array_equal(h_m.rewards, h_v.rewards)
    np.testing.assert_array_equal(h_m.latencies, h_v.latencies)
    np.testing.assert_array_equal(h_m.moved, h_v.moved)
    np.testing.assert_array_equal(h_m.final_assignment, h_v.final_assignment)
    for a, b in zip(jax.tree.leaves(s_v), jax.tree.leaves(s_m)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bad_agent_still_raises_before_sharding(small_env):
    """mesh= does not loosen the Agent requirement."""
    env = small_env
    cfg = DDPGConfig(n_executors=env.N, n_machines=env.M,
                     state_dim=env.state_dim, k_nn=4)
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    states = ddpg.init_fleet(jax.random.PRNGKey(1), cfg, 2)
    with pytest.raises(TypeError, match="make_agent"):
        run_online_fleet(keys, env, cfg, states, T=2, mesh=make_host_mesh())


_TWO_DEVICE_SCRIPT = textwrap.dedent("""
    import jax, numpy as np, tempfile
    assert len(jax.devices()) == 2, jax.devices()
    from repro.core import make_agent, reset_fleet_states, run_online_fleet
    from repro.checkpoint.fleet import FleetCheckpoint
    from repro.dsdps import SchedulingEnv, apps, scenarios
    from repro.dsdps.apps import default_workload
    from repro.launch.mesh import make_fleet_mesh, make_host_mesh

    topo = apps.continuous_queries("small")
    env = SchedulingEnv(topo, default_workload(topo))
    agent = make_agent("ddpg", env, k_nn=4)
    F, T = 2, 4
    params = scenarios.build("one_slow_machine", env, F,
                             broadcast_invariant=True)
    states = agent.init_fleet(jax.random.PRNGKey(0), F, env_params=params,
                              env=env)
    keys = jax.random.split(jax.random.PRNGKey(1), F)
    _, h_v = run_online_fleet(keys, env, agent, states, T=T,
                              env_params=params)
    mesh = make_fleet_mesh()
    assert mesh.devices.size == 2
    with tempfile.TemporaryDirectory() as d:
        ck = FleetCheckpoint(d, every=2)
        _, h_m = run_online_fleet(keys, env, agent, states, T=T,
                                  env_params=params, mesh=mesh,
                                  checkpoint=ck)
        ck.wait()
        # lane equivalence under real 2-way sharding
        np.testing.assert_array_equal(h_m.moved, h_v.moved)
        np.testing.assert_array_equal(h_m.final_assignment,
                                      h_v.final_assignment)
        np.testing.assert_allclose(h_m.rewards, h_v.rewards,
                                   rtol=1e-5, atol=1e-5)
        # elastic restore: checkpoint written under the 2-device mesh
        # re-places against the 1-device host mesh
        like_env = reset_fleet_states(keys, env, params)
        ep, st, es, ks = ck.restore(states, like_env, keys,
                                    mesh=make_host_mesh())
        assert ep == T
        run_online_fleet(ks, env, agent, st, T=2, env_params=params,
                         env_states=es, mesh=make_host_mesh())
        ck.close()
    # a fleet that does not divide the data axis fails loudly
    keys3 = jax.random.split(jax.random.PRNGKey(2), 3)
    states3 = agent.init_fleet(jax.random.PRNGKey(3), 3)
    try:
        run_online_fleet(keys3, env, agent, states3, T=2, mesh=mesh)
        raise SystemExit("expected ValueError for indivisible fleet")
    except ValueError as e:
        assert "does not divide" in str(e)
    print("TWO_DEVICE_OK")
""")


def test_two_device_sharding_subprocess(small_env):
    """Real multi-device coverage on CPU: force 2 host devices in a
    subprocess, shard a fleet over them, and pin lane equivalence plus the
    cross-device-count elastic restore."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=2")
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _TWO_DEVICE_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "TWO_DEVICE_OK" in out.stdout
