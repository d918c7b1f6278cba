"""Checkpointing (sync/async/atomic/integrity/elastic), data-pipeline
determinism, heartbeat + straggler + elastic-mesh planning."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import AsyncCheckpointer, Checkpointer
from repro.data.pipeline import DataConfig, PrefetchIterator, batch_at, \
    pack_sequences
from repro.fault.elastic import plan_mesh
from repro.fault.heartbeat import HeartbeatMonitor
from repro.fault.straggler import StragglerDetector


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"params": {"w": jax.random.normal(k, (8, 8)),
                       "b": jnp.zeros(8, jnp.bfloat16)},
            "step": jnp.asarray(7, jnp.int32)}


def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(tmp_path)
    st = _state()
    ck.save(7, st)
    restored = ck.restore(st)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_keeps_latest_k(tmp_path):
    ck = Checkpointer(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        ck.save(s, _state(s))
    assert ck.all_steps() == [3, 4]
    assert ck.latest_step() == 4


def test_checkpoint_detects_corruption(tmp_path):
    ck = Checkpointer(tmp_path)
    path = ck.save(1, _state())
    leaf = next(path.glob("leaf_*.npy"))
    raw = bytearray(leaf.read_bytes())
    raw[-1] ^= 0xFF
    leaf.write_bytes(bytes(raw))
    with pytest.raises(IOError, match="corruption"):
        ck.restore(_state())


def test_async_checkpointer(tmp_path):
    ck = AsyncCheckpointer(tmp_path)
    st = _state()
    ck.save_async(5, st)
    ck.save_async(10, st)
    ck.wait()
    assert ck.all_steps() == [5, 10]
    restored = ck.restore(st, step=10)
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.asarray(st["params"]["w"]))
    ck.close()


def test_checkpoint_atomicity(tmp_path):
    """A stale .tmp dir from a crashed writer must not shadow real ckpts."""
    ck = Checkpointer(tmp_path)
    (tmp_path / ".tmp_step_00000009").mkdir()
    ck.save(3, _state())
    assert ck.latest_step() == 3


def test_elastic_restore_to_new_topology(tmp_path):
    """Restore places leaves with explicit shardings (single device here,
    but exercises the code path used after re-meshing)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import auto_mesh
    mesh = auto_mesh((1,), ("data",))
    ck = Checkpointer(tmp_path)
    st = _state()
    ck.save(1, st)
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), st)
    restored = ck.restore(st, shardings=sh)
    assert restored["params"]["w"].sharding == NamedSharding(mesh, P())


# -- data pipeline ---------------------------------------------------------------
def test_data_deterministic_replay():
    cfg = DataConfig(vocab_size=1000, seq_len=16, global_batch=4)
    b1 = batch_at(cfg, 12)
    b2 = batch_at(cfg, 12)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    b3 = batch_at(cfg, 13)
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b3["tokens"]))


def test_data_host_sharding_disjoint():
    a = batch_at(DataConfig(1000, 16, 8, num_hosts=2, host_id=0), 5)
    b = batch_at(DataConfig(1000, 16, 8, num_hosts=2, host_id=1), 5)
    assert a["tokens"].shape == (4, 16)
    assert not np.array_equal(np.asarray(a["tokens"]),
                              np.asarray(b["tokens"]))


def test_prefetch_iterator_matches_direct():
    cfg = DataConfig(vocab_size=100, seq_len=8, global_batch=2)
    it = PrefetchIterator(cfg, start_step=3)
    got = [next(it) for _ in range(3)]
    it.close()
    for i, b in enumerate(got):
        np.testing.assert_array_equal(np.asarray(b["tokens"]),
                                      np.asarray(batch_at(cfg, 3 + i)["tokens"]))


def test_pack_sequences():
    docs = [np.arange(1, 6, dtype=np.int32), np.arange(10, 13, dtype=np.int32)]
    out = pack_sequences(docs, seq_len=4)
    assert out.shape == (2, 4)
    np.testing.assert_array_equal(out[0], [1, 2, 3, 4])
    np.testing.assert_array_equal(out[1], [5, 10, 11, 12])


# -- fault tolerance ---------------------------------------------------------------
def test_heartbeat_detection():
    t = [0.0]
    mon = HeartbeatMonitor(4, timeout_s=5.0, clock=lambda: t[0])
    t[0] = 3.0
    for w in (0, 1, 2):
        mon.beat(w)
    t[0] = 7.0
    assert mon.dead_workers() == {3}
    assert mon.newly_dead() == {3}
    assert mon.newly_dead() == set()          # reported once
    assert mon.alive == [0, 1, 2]
    mon.beat(3)
    assert mon.dead_workers() == set()


def test_straggler_detector():
    det = StragglerDetector(4)
    for step in range(10):
        for w in range(4):
            det.observe(w, 1.0 if w != 2 else 2.5)
    assert det.stragglers() == [2]
    f = det.speed_factors()
    assert f[2] < 0.6 and abs(f[0] - 1.0) < 0.1


def test_elastic_mesh_planning():
    assert plan_mesh(512, 16, multi_pod=True).shape == (2, 16, 16)
    assert plan_mesh(496, 16).shape == (31, 16)     # lost a host: dp shrinks
    assert plan_mesh(256, 16).shape == (16, 16)
    p = plan_mesh(8, 16)                            # fewer chips than TP
    assert p.device_count <= 8
