"""Compile-only checks against a described (not attached) TPU v5e.

The TPU compiler ships with jax, so the main path's kernel and the fleet
epoch program are compiled here for the chip at real widths: what Mosaic
or XLA:TPU would refuse (tiling, VMEM, device memory) fails these tests
without a chip.  Nothing runs.  The topology is described inside a fixture,
never at import: only one process may load the TPU library at a time."""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.knn_projection import knn_actions_jax
from repro.kernels.knn_topk import row_top2_regret


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # the TPU library logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a chip's executable cannot be read back without the chip: keep these
    # compiles out of any persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shaped(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        jax.eval_shape(lambda: tree))


@pytest.mark.parametrize("shape,vmaps", [
    ((100, 10), 0),           # cq_large: N=100 executors, M=10 machines
    ((512, 10), 0),           # several lane blocks
    ((128, 100, 10), 1),      # vmapped over a 128-lane fleet
])
def test_row_top2_regret_compiles_for_v5e(one_chip, shape, vmaps):
    f = row_top2_regret
    for _ in range(vmaps):
        f = jax.vmap(f)
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    text = jax.jit(f).lower(x).compile().as_text()
    assert "tpu_custom_call" in text


def test_knn_beam_compiles_without_sorts_or_scatters_for_v5e(one_chip):
    """The K-NN beam at the DDPG target's width (128 lanes × 32 samples,
    N=100, M=10, K=12) picks its rows and candidates by comparison ranks:
    the chip's program holds no sort (a TPU top-k sorts every element) and
    no scatter."""
    f = jax.vmap(jax.vmap(lambda p: knn_actions_jax(p, 12)))
    x = jax.ShapeDtypeStruct((128, 32, 100, 10), jnp.float32,
                             sharding=one_chip)
    text = jax.jit(f).lower(x).compile().as_text()
    assert " sort(" not in text
    assert " scatter(" not in text


def _compile_cq_large_fleet_epoch(agent_name, sharding):
    """The fused epoch program (select → env.step → store → update) of a
    128-lane cq_large fleet, compiled for ``sharding``'s device."""
    from repro.core import make_agent
    from repro.core.agent import _fleet_program, reset_fleet_states
    from repro.launch.drl_control import build_env
    env = build_env("cq_large")
    agent = make_agent(agent_name, env)
    fleet = 128
    key = jax.random.PRNGKey(0)
    keys = jax.random.split(key, fleet)
    states = jax.eval_shape(lambda: agent.init_fleet(key, fleet))
    env_states = jax.eval_shape(lambda: reset_fleet_states(keys, env))
    args = [_shaped(t, sharding)
            for t in (keys, states, env_states, env.default_params())]
    return _fleet_program.lower(
        *args, env=env, agent=agent, T=1, updates_per_epoch=1,
        explore=True, params_axes=None).compile()


def test_cq_large_ddpg_fleet_epoch_compiles_for_one_v5e(one_chip):
    """The fused epoch program of a 128-lane cq_large ddpg fleet fits one
    chip, and sorts nothing: its K-NN beams rank by comparisons."""
    compiled = _compile_cq_large_fleet_epoch("ddpg", one_chip)
    assert " sort(" not in compiled.as_text()
    mem = compiled.memory_analysis()
    print(f"cq_large ddpg fleet F=128 epoch on one v5e: {mem}")
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 0 < total < 16e9


def test_cq_large_dqn_update_gathers_only_state_rows_on_v5e(one_chip):
    """The DQN update of a 128-lane cq_large fleet (minibatch 32, 1010-wide
    states, 1000 moves) gathers only the two state-row blocks of its
    minibatch: the rewards, the moves and Q(s, a), one value per sample,
    are select-reduces, since the chip prices a gather per index."""
    text = _compile_cq_large_fleet_epoch("dqn", one_chip).as_text()
    shapes = []
    for line in text.splitlines():
        op = re.search(r'op_name="([^"]*)"', line)
        out = re.search(r"= \w+\[([\d,]*)\]\S* gather\(", line)
        if op and out and "agent_update" in op.group(1):
            shapes.append(tuple(int(d) for d in out.group(1).split(",")))
    assert len(shapes) == 2, shapes
    for shape in shapes:
        assert shape in ((4096, 1010), (128, 32, 1010)), shapes
