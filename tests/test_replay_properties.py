"""Property-based ring-buffer semantics for core/replay.py.

The replay buffer is the thing the streaming agents delete, so its
semantics are pinned here as properties rather than examples: after any
number of ``replay_add`` calls the buffer holds exactly the newest
``min(n, capacity)`` transitions (wraparound overwrites oldest-first),
the write pointer is ``n mod capacity``, and ``replay_sample`` only ever
returns indices inside the filled prefix — including the degenerate
cases ``batch > size`` (sampling with replacement over what exists) and
sampling an EMPTY buffer (index 0 against the zero-filled slot, never
out of bounds)."""
import jax
import jax.numpy as jnp
import numpy as np

from hypothesis_compat import given, settings, st

from repro.core.replay import replay_add, replay_init, replay_sample


def _fill(capacity: int, n: int, state_dim: int = 3):
    """Add transitions tagged 1..n (state leaf constant at the tag)."""
    buf = replay_init(capacity, state_dim, 1)
    for t in range(1, n + 1):
        buf = replay_add(buf,
                         jnp.full((state_dim,), float(t)),
                         jnp.asarray([float(t)]),
                         jnp.asarray(float(t)),
                         jnp.full((state_dim,), float(-t)))
    return buf


@settings(max_examples=40, deadline=None)
@given(capacity=st.integers(min_value=1, max_value=12),
       n=st.integers(min_value=0, max_value=30))
def test_add_wraparound_keeps_newest_min_n_cap(capacity, n):
    buf = _fill(capacity, n)
    assert int(buf.size) == min(n, capacity)
    assert int(buf.ptr) == n % capacity
    stored = set(np.asarray(buf.rewards[: int(buf.size)]).tolist())
    newest = set(float(t) for t in range(max(1, n - capacity + 1), n + 1))
    assert stored == newest
    # slots beyond the filled prefix are still the zero init
    assert (np.asarray(buf.rewards[int(buf.size):]) == 0.0).all()
    # all four leaves wrap in lockstep: the tag agrees across leaves
    for i in range(int(buf.size)):
        tag = float(buf.rewards[i])
        assert float(buf.states[i, 0]) == tag
        assert float(buf.actions[i, 0]) == tag
        assert float(buf.next_states[i, 0]) == -tag


@settings(max_examples=40, deadline=None)
@given(capacity=st.integers(min_value=1, max_value=12),
       n=st.integers(min_value=0, max_value=30),
       batch=st.integers(min_value=1, max_value=64),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_sample_indices_stay_inside_filled_prefix(capacity, n, batch, seed):
    """Even when ``batch`` exceeds the filled entries, every sampled row
    must come from the filled prefix (with replacement) — and an empty
    buffer samples the zero-filled slot 0, never uninitialized garbage."""
    buf = _fill(capacity, n)
    s, a, r, s_next = replay_sample(jax.random.PRNGKey(seed), buf, batch)
    assert s.shape == (batch, 3) and r.shape == (batch,)
    if n == 0:
        assert (np.asarray(r) == 0.0).all()
        return
    valid = set(np.asarray(buf.rewards[: int(buf.size)]).tolist())
    for tag in np.asarray(r).tolist():
        assert tag in valid
    # leaves sampled at the same index stay consistent
    np.testing.assert_array_equal(np.asarray(s[:, 0]), np.asarray(r))
    np.testing.assert_array_equal(np.asarray(s_next[:, 0]),
                                  -np.asarray(r))


@settings(max_examples=20, deadline=None)
@given(capacity=st.integers(min_value=2, max_value=8),
       extra=st.integers(min_value=1, max_value=20))
def test_overwritten_transitions_never_resurface(capacity, extra):
    """After wrapping, a large sample must never contain an overwritten
    tag — the off-by-one this guards: ptr advancing before vs after the
    slot write."""
    n = capacity + extra
    buf = _fill(capacity, n)
    _, _, r, _ = replay_sample(jax.random.PRNGKey(0), buf, 256)
    overwritten = set(float(t) for t in range(1, n - capacity + 1))
    assert not (set(np.asarray(r).tolist()) & overwritten)


@settings(max_examples=40, deadline=None)
@given(capacity=st.integers(min_value=1, max_value=12),
       n=st.integers(min_value=0, max_value=30),
       batch=st.integers(min_value=1, max_value=64),
       action_dim=st.sampled_from([1, 3]),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_sample_equals_plain_gather_bit_for_bit(capacity, n, batch,
                                                action_dim, seed):
    """``replay_sample`` returns exactly ``col[idx]`` of every column, with
    ``idx`` drawn from the same key: the select-reduce that reads a
    one-value column (rewards; a one-wide action) changes no bit, and the
    wide action rows keep their gather."""
    rng = np.random.default_rng(seed)
    buf = replay_init(capacity, 3, action_dim)
    for _ in range(n):
        buf = replay_add(buf, *(jnp.asarray(rng.standard_normal(shape),
                                            jnp.float32)
                                for shape in ((3,), (action_dim,), (), (3,))))
    key = jax.random.PRNGKey(seed)
    idx = jax.random.randint(key, (batch,), 0, max(min(n, capacity), 1))
    want = (buf.states[idx], buf.actions[idx], buf.rewards[idx],
            buf.next_states[idx])
    got = replay_sample(key, buf, batch)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g).view(np.uint32),
                                      np.asarray(w).view(np.uint32))
