"""Experience replay buffer (paper: |B| = 1000, minibatch H = 32).

Fixed-capacity ring buffer held as device arrays so sampling and the DDPG
update jit together; oldest samples are overwritten when full (paper §3.2.1)."""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class Replay(NamedTuple):
    states: jnp.ndarray        # [cap, state_dim]
    actions: jnp.ndarray       # [cap, action_dim]
    rewards: jnp.ndarray       # [cap]
    next_states: jnp.ndarray   # [cap, state_dim]
    ptr: jnp.ndarray           # scalar int32 — next write slot
    size: jnp.ndarray          # scalar int32


def replay_init(capacity: int, state_dim: int, action_dim: int) -> Replay:
    return Replay(
        states=jnp.zeros((capacity, state_dim), jnp.float32),
        actions=jnp.zeros((capacity, action_dim), jnp.float32),
        rewards=jnp.zeros((capacity,), jnp.float32),
        next_states=jnp.zeros((capacity, state_dim), jnp.float32),
        ptr=jnp.zeros((), jnp.int32),
        size=jnp.zeros((), jnp.int32),
    )


def replay_add(buf: Replay, s, a, r, s_next) -> Replay:
    cap = buf.states.shape[0]
    i = buf.ptr
    return Replay(
        states=buf.states.at[i].set(s),
        actions=buf.actions.at[i].set(a),
        rewards=buf.rewards.at[i].set(r),
        next_states=buf.next_states.at[i].set(s_next),
        ptr=(i + 1) % cap,
        size=jnp.minimum(buf.size + 1, cap),
    )


def pick(col: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``col[..., idx]`` per row: one value of ``col``'s last axis per index.

    ``col`` is ``[n]`` (one value per replay slot) or ``[*idx.shape, n]``
    (one row per index, as Q(s, ·) is); ``idx`` holds indices in ``[0, n)``.
    This is ``where(idx == arange(n), col, 0)`` summed over the last axis:
    every term but the selected one is an exact zero, so the sum is the
    selected value (a -0.0 comes back as +0.0), and its gradient is a
    select, not a scatter.  On a TPU a gather is priced per index, so for a
    one-value column of a 1000-slot replay this reads faster than
    ``col[idx]``; it reads all ``n`` values per index, so it is meant for
    short axes."""
    n = col.shape[-1]
    return jnp.where(idx[..., None] == jnp.arange(n), col, 0).sum(-1)


def replay_sample(key: jax.Array, buf: Replay, batch: int):
    """Uniform sample with replacement over the filled prefix.

    State rows and wide action rows are gathered; the rewards and a
    one-wide action column are read with `pick`."""
    idx = jax.random.randint(key, (batch,), 0, jnp.maximum(buf.size, 1))
    if buf.actions.shape[1] == 1:
        actions = pick(buf.actions[:, 0], idx)[:, None]
    else:
        actions = buf.actions[idx]
    return (
        buf.states[idx],
        actions,
        pick(buf.rewards, idx),
        buf.next_states[idx],
    )
