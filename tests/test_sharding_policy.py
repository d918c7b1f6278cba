"""Sharding policy: every param/cache spec must be divisibility-valid for
every architecture on the production mesh shapes (no 512 host devices
needed — PartitionSpec construction is pure)."""
import numpy as np
import pytest

import jax
from repro.configs import ARCH_IDS, get_config
from repro.models import lm
from repro.models.config import ModelConfig


class FakeMesh:
    """Duck-typed mesh: .axis_names / .shape only (policy never touches
    devices when building PartitionSpecs)."""
    def __init__(self, shape, names):
        self.axis_names = names
        self.shape = dict(zip(names, shape))


def _axis_size(mesh, axes):
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


@pytest.mark.parametrize("arch_id", ARCH_IDS)
@pytest.mark.parametrize("mesh_shape,names", [
    ((16, 16), ("data", "model")),
    ((2, 16, 16), ("pod", "data", "model")),
])
def test_param_specs_divisible(arch_id, mesh_shape, names):
    from repro.sharding.policy import ShardingPolicy
    cfg = get_config(arch_id)
    mesh = FakeMesh(mesh_shape, names)
    policy = ShardingPolicy.__new__(ShardingPolicy)
    policy.mesh = mesh
    policy.cfg = cfg
    policy.fsdp = True
    from repro.sharding.policy import MeshAxes
    policy.axes = MeshAxes(dp=tuple(n for n in names if n != "model"))
    policy.dp_size = _axis_size(mesh, policy.axes.dp)
    policy.tp_size = _axis_size(mesh, "model")

    abstract = lm.abstract_params(cfg)
    specs = policy.params_tree(abstract)

    flat_p = jax.tree_util.tree_flatten_with_path(abstract)[0]
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: hasattr(x, "_normalized_spec")
                             or x.__class__.__name__ == "PartitionSpec")
    assert len(flat_p) == len(flat_s)
    n_sharded = 0
    for (kp, leaf), spec in zip(flat_p, flat_s):
        for dim, ax in zip(leaf.shape, tuple(spec)):
            if ax is None:
                continue
            n_sharded += 1
            size = _axis_size(mesh, ax)
            path = jax.tree_util.keystr(kp)
            assert dim % size == 0, \
                f"{arch_id} {path}: dim {dim} not divisible by {ax}={size}"
    # the policy must actually shard the bulk of the model
    assert n_sharded > 10, f"{arch_id}: almost nothing sharded"


@pytest.mark.parametrize("arch_id", ["llama3-8b", "jamba-1.5-large-398b",
                                     "rwkv6-7b", "seamless-m4t-medium"])
def test_cache_specs_divisible(arch_id):
    from repro.sharding.policy import MeshAxes, ShardingPolicy
    cfg = get_config(arch_id)
    mesh = FakeMesh((16, 16), ("data", "model"))
    policy = ShardingPolicy.__new__(ShardingPolicy)
    policy.mesh, policy.cfg = mesh, cfg
    policy.axes = MeshAxes(dp=("data",))
    policy.dp_size, policy.tp_size = 16, 16

    cache = jax.eval_shape(lambda: lm.init_cache(
        cfg, batch=128, max_seq=4096,
        enc_len=1024 if cfg.family == "encdec" else 0))
    flat = jax.tree_util.tree_flatten_with_path(cache)[0]
    for kp, leaf in flat:
        path = jax.tree_util.keystr(kp, simple=True, separator="/")
        spec = policy.cache_spec(path, leaf.shape)
        for dim, ax in zip(leaf.shape, tuple(spec)):
            if ax is None:
                continue
            assert dim % _axis_size(mesh, ax) == 0, (arch_id, path, dim, ax)


def test_fallbacks_kick_in():
    """granite: 40 experts unsplittable by 16 -> expert d_ff TP'd instead
    of EP; jamba: 16 experts -> true EP; yi: 56 kv/q heads unsplittable ->
    the *cache* falls back to sequence sharding and the activation
    constraint leaves the head axis unsharded (params still shard the
    flattened head dim, which is 16-divisible)."""
    from repro.sharding.policy import MeshAxes, ShardingPolicy
    mesh = FakeMesh((16, 16), ("data", "model"))

    def mk(cfg):
        p = ShardingPolicy.__new__(ShardingPolicy)
        p.mesh, p.cfg = mesh, cfg
        p.fsdp = True
        p.axes = MeshAxes(dp=("data",))
        p.dp_size, p.tp_size = 16, 16
        return p

    gr = mk(get_config("granite-moe-3b-a800m"))
    spec = gr.param_spec("layers/pos0/ffn/gate", (32, 40, 1536, 512))
    assert tuple(spec)[1] is None                      # experts NOT sharded
    assert "model" in tuple(spec)                      # ...but d_ff TP'd

    ja = mk(get_config("jamba-1.5-large-398b"))
    spec = ja.param_spec("layers/pos1/ffn/gate", (9, 16, 8192, 24576))
    assert tuple(spec)[1] == "model"                   # true EP: 16 experts

    yi = mk(get_config("yi-34b"))
    spec = yi.param_spec("layers/pos0/mixer/wq/w", (60, 7168, 7168))
    assert "model" in tuple(spec)                      # params still TP'd
    # kv heads (8) unsplittable by 16 -> cache sequence-sharded instead
    cspec = yi.cache_spec("pos0/k", (60, 128, 32768, 8, 128))
    assert tuple(cspec)[2] == "model" and tuple(cspec)[3] is None
