"""The MIQP-NN replacement (core/knn_projection.py) — exactness and
feasibility (DESIGN.md §2)."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core.knn_projection import (distance_to, knn_actions_exact,
                                       knn_actions_jax,
                                       knn_assignments_exact,
                                       nearest_assignment)
from repro.core.spaces import is_feasible


def brute_force_knn(proto: np.ndarray, k: int) -> np.ndarray:
    """Enumerate all M^N assignments (tiny instances only)."""
    n, m = proto.shape
    dists = []
    for cols in itertools.product(range(m), repeat=n):
        a = np.eye(m)[list(cols)]
        dists.append((np.sum((a - proto) ** 2), cols))
    dists.sort(key=lambda t: t[0])
    return np.array([d for d, _ in dists[:k]])


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10_000), st.integers(2, 5), st.integers(2, 4),
       st.integers(1, 8))
def test_exact_knn_matches_brute_force(seed, n, m, k):
    rng = np.random.default_rng(seed)
    proto = rng.uniform(size=(n, m))
    cols = knn_assignments_exact(proto, k)
    actions = np.eye(m)[cols]
    got = np.sort(((actions - proto) ** 2).sum((1, 2)))
    want = brute_force_knn(proto, min(k, m ** n))[: len(got)]
    np.testing.assert_allclose(np.sort(got)[: len(want)], want, rtol=1e-9)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000), st.integers(2, 30), st.integers(2, 10),
       st.integers(1, 12))
def test_exact_knn_ordered_and_feasible(seed, n, m, k):
    rng = np.random.default_rng(seed)
    proto = rng.uniform(size=(n, m))
    acts = knn_actions_exact(proto, k)
    d = ((acts - proto[None]) ** 2).sum((1, 2))
    assert np.all(np.diff(d) >= -1e-9), "neighbours must be distance-ordered"
    for a in acts:
        assert bool(is_feasible(jnp.asarray(a)))


def test_jax_beam_matches_exact_on_random_instances():
    mismatches = 0
    for seed in range(20):
        key = jax.random.PRNGKey(seed)
        proto = jax.random.uniform(key, (40, 10))
        k = 8
        exact = knn_actions_exact(np.asarray(proto), k)
        beam = np.asarray(knn_actions_jax(proto, k))
        d_exact = np.sort(((exact - np.asarray(proto)) ** 2).sum((1, 2)))
        d_beam = np.sort(((beam - np.asarray(proto)) ** 2).sum((1, 2)))
        if not np.allclose(d_exact, d_beam, rtol=1e-5):
            mismatches += 1
    # the beam is exact w.h.p. on continuous data; allow a rare tie case
    assert mismatches <= 1, f"{mismatches}/20 beam≠exact"


def test_jax_beam_contains_exact_1nn():
    for seed in range(10):
        key = jax.random.PRNGKey(100 + seed)
        proto = jax.random.uniform(key, (25, 6))
        beam = np.asarray(knn_actions_jax(proto, 6))
        one = np.asarray(nearest_assignment(proto))
        assert any(np.array_equal(b, one) for b in beam)


def test_pallas_beam_matches_xla_beam_exactly():
    """knn_actions_jax(use_pallas=True) routes the top-2/regret reduction
    through the kernels/knn_topk Pallas kernel (interpret mode, asked for
    explicitly on CPU) and must match the XLA beam bit for bit."""
    for seed, (n, m, k) in [(0, (40, 10, 8)), (1, (25, 6, 6)),
                            (2, (7, 3, 4)), (3, (100, 10, 16))]:
        proto = jax.random.uniform(jax.random.PRNGKey(seed), (n, m))
        beam = np.asarray(knn_actions_jax(proto, k))
        pallas = np.asarray(knn_actions_jax(proto, k, use_pallas=True,
                                            interpret=True))
        np.testing.assert_array_equal(pallas, beam)


def topk_beam(proto, k, pair_pool=8, triple_pool=4):
    """The beam as three ``lax.top_k`` calls and a row scatter: the plain
    formulation ``knn_actions_jax`` must reproduce bit for bit."""
    n, m = proto.shape
    top2_vals, top2_idx = jax.lax.top_k(proto, 2)
    best_col, second_col = top2_idx[:, 0], top2_idx[:, 1]
    flip_regret = 2.0 * (top2_vals[:, 0] - top2_vals[:, 1])
    pool = min(max(pair_pool, triple_pool, k), n)
    cheap_cost, cheap_rows = jax.lax.top_k(-flip_regret, pool)
    cheap_cost = -cheap_cost
    sets = [()] + [(i,) for i in range(pool)]
    p, t = min(pair_pool, pool), min(triple_pool, pool)
    sets += [(i, j) for i in range(p) for j in range(i + 1, p)]
    sets += [(i, j, l) for i in range(t) for j in range(i + 1, t)
             for l in range(j + 1, t)]
    masks, costs = [], []
    for rows in sets:
        mask = jnp.zeros((pool,), jnp.bool_)
        cost = jnp.zeros(()) if not rows else cheap_cost[rows[0]]
        for i in rows:
            mask = mask.at[i].set(True)
        for i in rows[1:]:
            cost = cost + cheap_cost[i]
        masks.append(mask)
        costs.append(cost)
    cand_masks, cand_costs = jnp.stack(masks), jnp.stack(costs)
    kk = min(k, len(sets))
    _, sel = jax.lax.top_k(-cand_costs, kk)

    def build(mask_row):
        flip = jnp.zeros((n,), jnp.bool_).at[cheap_rows].set(mask_row)
        return jax.nn.one_hot(jnp.where(flip, second_col, best_col), m,
                              dtype=jnp.float32)

    actions = jax.vmap(build)(cand_masks[sel])
    return jnp.concatenate(
        [actions, jnp.repeat(actions[-1:], k - kk, axis=0)], axis=0)


def _protos(kind, n, m, batch=6, seed=0):
    """``batch`` protos [batch, n, m] of one kind: continuous, rounded to
    quarters (ties, and −0 beside +0), with ±inf entries and rows, or with
    NaN entries and rows of either sign."""
    rng = np.random.default_rng([seed, n, m])
    x = rng.uniform(-1.0, 1.0, (batch, n, m)).astype(np.float32)

    def spots(count):
        return (np.arange(batch)[:, None],
                rng.integers(n, size=(batch, count)),
                rng.integers(m, size=(batch, count)))
    if kind == "quarters":
        x = np.round(x * 4) / 4
    elif kind == "inf":
        x[spots(3)], x[spots(2)] = np.inf, -np.inf
        x[:, 0], x[:, -1] = np.inf, -np.inf
    elif kind == "nan":
        x[spots(3)], x[spots(2)] = np.nan, -np.nan
        x[:, 1], x[:, -2] = np.nan, -np.nan
    return jnp.asarray(x)


@pytest.mark.parametrize("kind", ["continuous", "quarters", "inf", "nan"])
@pytest.mark.parametrize("n,m,k", [(7, 3, 4), (25, 6, 6), (40, 10, 8),
                                   (100, 10, 12), (100, 10, 256)])
def test_beam_matches_top_k_formulation_bit_for_bit(n, m, k, kind):
    """The rank-based beam returns the same candidates, in the same order,
    as the ``lax.top_k`` formulation: ties to the lower index, floats in
    IEEE total order (+NaN highest, −NaN lowest, −0 below +0).  (100, 10,
    256) is the deploy-time K: every row is in the pool, 133 candidates."""
    protos = _protos(kind, n, m)
    want = jax.jit(jax.vmap(lambda p: topk_beam(p, k)))(protos)
    got = jax.vmap(lambda p: knn_actions_jax(p, k))(protos)
    assert got.shape == (protos.shape[0], k, n, m)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_nearest_assignment_is_row_argmax():
    proto = jnp.asarray([[0.1, 0.9], [0.7, 0.3]])
    a = nearest_assignment(proto)
    np.testing.assert_array_equal(np.asarray(a),
                                  [[0.0, 1.0], [1.0, 0.0]])


def test_distance_to():
    proto = jnp.zeros((3, 4))
    a = jax.nn.one_hot(jnp.array([0, 1, 2]), 4)
    assert float(distance_to(proto, a)) == pytest.approx(3.0)
