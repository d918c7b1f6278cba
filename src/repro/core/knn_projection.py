"""Exact replacement for the paper's MIQP-NN optimizer (DESIGN.md §2).

The paper finds the K nearest feasible assignments to a continuous
proto-action â ∈ R^{N×M} by solving K Mixed-Integer Quadratic Programs with
Gurobi.  Because the feasible set is a product of independent row simplices
({0,1} rows summing to 1), the squared distance decomposes per row:

    ||a − â||² = Σ_i (1 − 2·â[i, j_i] + ||â_i||²)

so the 1-NN is the row-wise argmax of â, and the k-th NN differs from the
1-NN by "flipping" some rows to lower-ranked columns, paying per-row regret

    Δ[i, c] = 2·(â[i, (1)] − â[i, (c)])      (sorted descending per row).

Finding the K nearest assignments is then the classic *k-smallest sums*
problem over N independent regret ladders, solved exactly with a best-first
heap (host path), or with a vectorized candidate beam (JAX path used inside
the jitted DDPG update).  Both are validated against brute force in tests."""
from __future__ import annotations

import heapq
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.diagnostics.spans import KNN_PROJECTION

# --------------------------------------------------------------------------
# Host path: exact best-first k-best enumeration (replaces Gurobi loop).
# --------------------------------------------------------------------------
def knn_assignments_exact(proto: np.ndarray, k: int) -> np.ndarray:
    """Exact K nearest one-hot assignments to ``proto`` ([N, M]).

    Returns ranks ``[k, N]`` of chosen columns, ordered by distance."""
    proto = np.asarray(proto, dtype=np.float64)
    n, m = proto.shape
    order = np.argsort(-proto, axis=1)                   # [N, M] cols by desc value
    sorted_vals = np.take_along_axis(proto, order, axis=1)
    # regret ladder: cost of moving row i from rank 0 to rank c
    regret = 2.0 * (sorted_vals[:, :1] - sorted_vals)    # [N, M], col 0 = 0

    # best-first search over rank vectors
    start = (0.0, tuple([0] * n))
    heap = [start]
    seen = {start[1]}
    out = []
    while heap and len(out) < k:
        cost, ranks = heapq.heappop(heap)
        out.append(ranks)
        for i in range(n):
            c = ranks[i] + 1
            if c >= m:
                continue
            nxt = list(ranks)
            nxt[i] = c
            nxt_t = tuple(nxt)
            if nxt_t in seen:
                continue
            seen.add(nxt_t)
            heapq.heappush(heap, (cost - regret[i, ranks[i]] + regret[i, c], nxt_t))

    cols = np.stack([
        order[np.arange(n), np.asarray(ranks)] for ranks in out
    ])                                                    # [k', N]
    if cols.shape[0] < k:                                 # degenerate tiny spaces
        cols = np.concatenate([cols, np.repeat(cols[-1:], k - cols.shape[0], 0)])
    return cols


def knn_actions_exact(proto: np.ndarray, k: int) -> np.ndarray:
    """One-hot action set [k, N, M] (host / numpy)."""
    proto = np.asarray(proto)
    n, m = proto.shape
    cols = knn_assignments_exact(proto, k)
    return np.eye(m, dtype=np.float32)[cols]              # [k, N, M]


# --------------------------------------------------------------------------
# JAX path: vectorized candidate beam used inside jit (DDPG target values).
#
# Candidates: the 1-NN, all single-row flips ranked by regret, plus pair and
# triple combinations of the cheapest single flips.  For continuous protos
# this recovers the exact top-K with overwhelming probability (tests check
# equality against the host path); by construction it always contains the
# exact 1-NN and only feasible actions.
#
# ``use_pallas=True`` computes the per-row top-2/regret reduction with the
# kernels-layer Pallas kernel (kernels/knn_topk) instead of lax.top_k, so
# the DDPG select hot path exercises the kernel.  The kernel is compiled
# unless the caller asks for ``interpret=True`` — the only way to run it
# on a backend without Mosaic (the CPU).
# --------------------------------------------------------------------------
def _row_top2(proto: jnp.ndarray, use_pallas: bool, interpret: bool):
    """(best_col [N] i32, second_col [N] i32, flip_regret [N] f32)."""
    if use_pallas:
        from repro.kernels.knn_topk import row_top2_regret
        return row_top2_regret(proto, interpret=interpret)
    top2_vals, top2_idx = jax.lax.top_k(proto, 2)         # [N, 2]
    flip_regret = 2.0 * (top2_vals[:, 0] - top2_vals[:, 1])   # [N]
    return top2_idx[:, 0], top2_idx[:, 1], flip_regret


@partial(jax.jit, static_argnames=("k", "pair_pool", "triple_pool",
                                   "use_pallas", "interpret"))
def knn_actions_jax(
    proto: jnp.ndarray, k: int, pair_pool: int = 8, triple_pool: int = 4,
    use_pallas: bool = False, interpret: bool = False,
) -> jnp.ndarray:
    """[k, N, M] one-hot candidate actions, ordered by distance to proto,
    under the ``knn_projection`` scope (diagnostics.SUBSCOPES)."""
    with jax.named_scope(KNN_PROJECTION):
        return _knn_beam(proto, k, pair_pool, triple_pool, use_pallas,
                         interpret)


def _knn_beam(proto, k, pair_pool, triple_pool, use_pallas, interpret):
    n, m = proto.shape
    # best / 2nd-best machine per row + single-flip regret to the 2nd-best
    best_col, second_col, flip_regret = _row_top2(proto, use_pallas,
                                                  interpret)

    pool = min(max(pair_pool, triple_pool, k), n)
    cheap_cost, cheap_rows = jax.lax.top_k(-flip_regret, pool)
    cheap_cost = -cheap_cost                              # ascending regrets

    # candidate flip masks over the `pool` cheapest rows
    masks = [jnp.zeros((pool,), jnp.bool_)]
    costs = [jnp.zeros(())]
    for i in range(pool):                                 # singles
        masks.append(jnp.zeros((pool,), jnp.bool_).at[i].set(True))
        costs.append(cheap_cost[i])
    for i in range(min(pair_pool, pool)):                 # pairs
        for j in range(i + 1, min(pair_pool, pool)):
            masks.append(jnp.zeros((pool,), jnp.bool_).at[i].set(True).at[j].set(True))
            costs.append(cheap_cost[i] + cheap_cost[j])
    for i in range(min(triple_pool, pool)):               # triples
        for j in range(i + 1, min(triple_pool, pool)):
            for l in range(j + 1, min(triple_pool, pool)):
                masks.append(
                    jnp.zeros((pool,), jnp.bool_).at[i].set(True).at[j].set(True).at[l].set(True)
                )
                costs.append(cheap_cost[i] + cheap_cost[j] + cheap_cost[l])
    cand_masks = jnp.stack(masks)                         # [C, pool]
    cand_costs = jnp.stack(costs)                         # [C]

    kk = min(k, cand_costs.shape[0])
    _, sel = jax.lax.top_k(-cand_costs, kk)               # k cheapest candidates

    def build(mask_row):
        # rows in `cheap_rows` flagged by mask flip to their 2nd-best column
        flip_full = jnp.zeros((n,), jnp.bool_).at[cheap_rows].set(mask_row)
        cols = jnp.where(flip_full, second_col, best_col)
        return jax.nn.one_hot(cols, m, dtype=jnp.float32)

    actions = jax.vmap(build)(cand_masks[sel])            # [kk, N, M]
    if kk < k:
        actions = jnp.concatenate(
            [actions, jnp.repeat(actions[-1:], k - kk, axis=0)], axis=0
        )
    return actions


def nearest_assignment(proto: jnp.ndarray) -> jnp.ndarray:
    """Exact 1-NN: row-wise argmax, one-hot."""
    return jax.nn.one_hot(jnp.argmax(proto, axis=-1), proto.shape[-1],
                          dtype=jnp.float32)


def distance_to(proto: jnp.ndarray, action: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(jnp.square(action - proto), axis=(-2, -1))
