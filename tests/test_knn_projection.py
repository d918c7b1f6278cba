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
    explicitly on CPU) and must match the lax.top_k beam bit for bit."""
    for seed, (n, m, k) in [(0, (40, 10, 8)), (1, (25, 6, 6)),
                            (2, (7, 3, 4)), (3, (100, 10, 16))]:
        proto = jax.random.uniform(jax.random.PRNGKey(seed), (n, m))
        beam = np.asarray(knn_actions_jax(proto, k))
        pallas = np.asarray(knn_actions_jax(proto, k, use_pallas=True,
                                            interpret=True))
        np.testing.assert_array_equal(pallas, beam)


def test_nearest_assignment_is_row_argmax():
    proto = jnp.asarray([[0.1, 0.9], [0.7, 0.3]])
    a = nearest_assignment(proto)
    np.testing.assert_array_equal(np.asarray(a),
                                  [[0.0, 1.0], [1.0, 0.0]])


def test_distance_to():
    proto = jnp.zeros((3, 4))
    a = jax.nn.one_hot(jnp.array([0, 1, 2]), 4)
    assert float(distance_to(proto, a)) == pytest.approx(3.0)
