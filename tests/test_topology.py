"""Topology/observation-layer invariants (the graph_policy substrate).

The routing matrix R is the ground truth every layer above trusts — the
flow solver, the latency model, and now the graph observation that
``graph_policy`` message-passes over.  Its invariants are pinned as
properties over randomly-generated component DAGs (via the
``hypothesis_compat`` shim — clean per-test skips when the ``test``
extra isn't installed):

  * row mass: R's row for an executor of component ``c`` sums to
    selectivity(c) x (sum over outgoing edges of the fan-out mass: 1 for
    shuffle/fields/global, P_dst for all-grouping replication);
  * fields grouping: the skewed key split is a valid distribution over
    the downstream executors, identical for every upstream executor;
  * global grouping: everything lands on executor 0 of the downstream
    component;
  * executor expansion: executor ids partition by component exactly at
    the declared parallelisms.

Malformed topologies (cycles, unknown component/grouping names,
duplicate components) must be rejected at construction, and
``to_graph_obs`` must pad without ever touching real entries — the
real-node/edge prefix is bit-identical at every envelope, and a
too-small envelope raises instead of truncating.
"""
import numpy as np
import pytest

from hypothesis_compat import given, settings, st

from repro.dsdps.topology import (ALL, FIELDS, GLOBAL, SHUFFLE, Component,
                                  Edge, Topology)

GROUPINGS = (SHUFFLE, FIELDS, GLOBAL, ALL)


def _chain(par, groups, skews, sels, tag="chain"):
    """spout -> b1 -> ... chain: one component per level, one edge per
    hop — every generated instance is a DAG by construction and each
    (src, dst) pair carries exactly ONE edge, so per-edge invariants can
    be read straight off R's rows."""
    comps = [Component("c0", par[0], cpu_ms_per_tuple=0.1,
                       selectivity=sels[0], is_spout=True)]
    edges = []
    for i in range(1, len(par)):
        comps.append(Component(f"c{i}", par[i], cpu_ms_per_tuple=0.1,
                               selectivity=sels[i]))
        edges.append(Edge(f"c{i-1}", f"c{i}", GROUPINGS[groups[i - 1]],
                          skew=skews[i - 1]))
    return Topology(name=tag, components=comps, edges=edges)


chain_args = dict(
    par=st.lists(st.integers(min_value=1, max_value=5), min_size=2,
                 max_size=5),
    seed=st.integers(min_value=0, max_value=10),
    data=st.data(),
)


def _draw_chain(par, seed, data):
    k = len(par) - 1
    groups = data.draw(st.lists(st.integers(min_value=0, max_value=3),
                                min_size=k, max_size=k))
    skews = data.draw(st.lists(
        st.floats(min_value=0.0, max_value=2.5, allow_nan=False),
        min_size=k, max_size=k))
    sels = data.draw(st.lists(
        st.floats(min_value=0.05, max_value=4.0, allow_nan=False),
        min_size=len(par), max_size=len(par)))
    return _chain(par, groups, skews, sels), groups, skews, sels, seed


@settings(max_examples=40, deadline=None)
@given(**chain_args)
def test_row_mass_is_selectivity_times_fanout(par, seed, data):
    topo, groups, _, sels, seed = _draw_chain(par, seed, data)
    R = topo.routing_matrix(seed)
    for ci in range(len(par)):
        out_edges = [e for e in topo.edges if e.src == f"c{ci}"]
        mass = sum(
            (topo.component(e.dst).parallelism if e.grouping == ALL else 1.0)
            for e in out_edges)
        for i in topo.executor_slice(f"c{ci}"):
            np.testing.assert_allclose(R[i].sum(), sels[ci] * mass,
                                       rtol=1e-12)


@settings(max_examples=40, deadline=None)
@given(**chain_args)
def test_fields_split_is_a_distribution_shared_by_all_senders(
        par, seed, data):
    topo, groups, _, sels, seed = _draw_chain(par, seed, data)
    R = topo.routing_matrix(seed)
    for e in topo.edges:
        dst_ids = list(topo.executor_slice(e.dst))
        src_ids = list(topo.executor_slice(e.src))
        sel = topo.component(e.src).selectivity
        fracs = np.asarray([R[i, dst_ids] / sel for i in src_ids])
        if e.grouping in (SHUFFLE, FIELDS):
            assert (fracs >= 0.0).all()
            np.testing.assert_allclose(fracs.sum(axis=1), 1.0, rtol=1e-12)
            # the key-hash split is a property of the EDGE: every
            # upstream executor sees the identical (skewed) distribution
            for row in fracs[1:]:
                np.testing.assert_allclose(row, fracs[0], rtol=1e-12)
        if e.grouping == SHUFFLE:
            np.testing.assert_allclose(fracs, 1.0 / len(dst_ids), rtol=1e-12)
        if e.grouping == GLOBAL:
            expect = np.zeros(len(dst_ids))
            expect[0] = 1.0
            np.testing.assert_allclose(
                fracs, np.broadcast_to(expect, fracs.shape), atol=1e-15)
        if e.grouping == ALL:
            np.testing.assert_allclose(fracs, 1.0, rtol=1e-12)


@settings(max_examples=40, deadline=None)
@given(**chain_args)
def test_executor_expansion_matches_parallelism(par, seed, data):
    topo, *_ = _draw_chain(par, seed, data)
    assert topo.num_executors == sum(par)
    comp_of = topo.executor_component
    for ci, p in enumerate(par):
        sl = topo.executor_slice(f"c{ci}")
        assert len(sl) == p
        assert (comp_of[list(sl)] == ci).all()
    # slices partition [0, N): every executor belongs to exactly one comp
    seen = sorted(i for ci in range(len(par))
                  for i in topo.executor_slice(f"c{ci}"))
    assert seen == list(range(topo.num_executors))


@settings(max_examples=25, deadline=None)
@given(**chain_args)
def test_routing_matrix_deterministic_per_seed(par, seed, data):
    topo, *_ = _draw_chain(par, seed, data)
    np.testing.assert_array_equal(topo.routing_matrix(seed),
                                  topo.routing_matrix(seed))


# -- malformed topologies are rejected at construction ----------------------
def _two(edges):
    return Topology(name="bad", components=[
        Component("a", 2, cpu_ms_per_tuple=0.1, is_spout=True),
        Component("b", 2, cpu_ms_per_tuple=0.1),
    ], edges=edges)


def test_cycle_rejected():
    with pytest.raises(ValueError, match="cycle"):
        _two([Edge("a", "b"), Edge("b", "a")])


def test_self_loop_rejected():
    with pytest.raises(ValueError, match="cycle"):
        _two([Edge("a", "b"), Edge("b", "b")])


def test_unknown_component_rejected():
    with pytest.raises(ValueError, match="unknown component"):
        _two([Edge("a", "nope")])


def test_unknown_grouping_rejected():
    with pytest.raises(ValueError, match="unknown grouping"):
        _two([Edge("a", "b", grouping="broadcast")])


def test_duplicate_component_names_rejected():
    with pytest.raises(ValueError, match="duplicate component names"):
        Topology(name="bad", components=[
            Component("a", 2, cpu_ms_per_tuple=0.1, is_spout=True),
            Component("a", 3, cpu_ms_per_tuple=0.1),
        ], edges=[])


# -- to_graph_obs: padding is inert, truncation is an error -----------------
def _diamondish():
    return Topology(name="obs", components=[
        Component("s", 2, cpu_ms_per_tuple=0.05, selectivity=1.0,
                  tuple_bytes=128, is_spout=True),
        Component("f", 3, cpu_ms_per_tuple=0.3, selectivity=2.0,
                  tuple_bytes=256),
        Component("g", 2, cpu_ms_per_tuple=0.2, selectivity=0.0,
                  tuple_bytes=64),
    ], edges=[Edge("s", "f", SHUFFLE), Edge("f", "g", FIELDS, skew=0.7)])


def test_graph_obs_real_prefix_identical_across_envelopes():
    topo = _diamondish()
    n = topo.num_executors
    R = topo.routing_matrix(0)
    e = int(np.count_nonzero(R))
    tight = topo.to_graph_obs(n, e)
    padded = topo.to_graph_obs(n + 9, e + 17)
    assert tight.num_executors == padded.num_executors == n
    assert tight.num_edges == padded.num_edges == e
    for leaf in ("service_ms", "tuple_bytes", "is_spout", "out_mass",
                 "in_mass", "node_mask"):
        np.testing.assert_array_equal(getattr(tight, leaf)[:n],
                                      getattr(padded, leaf)[:n])
        assert (getattr(padded, leaf)[n:] == 0.0).all()
    for leaf in ("edge_src", "edge_dst", "edge_w", "edge_mask"):
        np.testing.assert_array_equal(getattr(tight, leaf)[:e],
                                      getattr(padded, leaf)[:e])
    # padded edges point at the sacrificial segment with zero weight
    assert (padded.edge_src[e:] == n + 9).all()
    assert (padded.edge_dst[e:] == n + 9).all()
    assert (padded.edge_w[e:] == 0.0).all()
    assert (padded.edge_mask[e:] == 0.0).all()


def test_graph_obs_matches_routing_matrix():
    topo = _diamondish()
    R = topo.routing_matrix(0)
    obs = topo.to_graph_obs(topo.num_executors + 3,
                            int(np.count_nonzero(R)) + 5)
    e = obs.num_edges
    np.testing.assert_allclose(
        obs.edge_w[:e],
        R[obs.edge_src[:e], obs.edge_dst[:e]].astype(np.float32))
    dense = np.zeros_like(R)
    dense[obs.edge_src[:e], obs.edge_dst[:e]] = obs.edge_w[:e]
    np.testing.assert_allclose(dense, R, rtol=1e-6)
    np.testing.assert_allclose(obs.out_mass[: topo.num_executors],
                               R.sum(axis=1).astype(np.float32))
    np.testing.assert_allclose(obs.in_mass[: topo.num_executors],
                               R.sum(axis=0).astype(np.float32))


def test_graph_obs_envelope_overflow_raises():
    topo = _diamondish()
    with pytest.raises(ValueError, match="exceeds graph envelope"):
        topo.to_graph_obs(topo.num_executors - 1, 999)
    with pytest.raises(ValueError, match="exceeds graph envelope"):
        topo.to_graph_obs(topo.num_executors, 2)
