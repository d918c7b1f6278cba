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
import itertools
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
# Every selection is made by comparison ranks, not sorts: an element's rank
# is the number of elements that order before it (larger, or equal with a
# lower index), so ``rank == r`` picks the r-th largest.  The TPU lowers a
# top-k to a full sort of every element and a row scatter to an index sort;
# a rank costs n² compares (10,000 per beam for N = 100 rows), far less chip
# time than a sort at the deployments' sizes.
# Floats are compared by their IEEE total order, as ``lax.top_k`` orders
# them: +NaN above +inf, −NaN below −inf, −0 below +0, ties to the lower
# index.  The beam returns the same candidates, in the same order, as the
# ``lax.top_k`` formulation.
#
# ``use_pallas=True`` computes the per-row top-2/regret reduction with the
# kernels-layer Pallas kernel (kernels/knn_topk) instead of two XLA
# reductions, so the DDPG select hot path exercises the kernel.  The kernel
# is compiled unless the caller asks for ``interpret=True`` — the only way to
# run it on a backend without Mosaic (the CPU).
# --------------------------------------------------------------------------
_INT_MIN = np.iinfo(np.int32).min


def _total_order(bits: jnp.ndarray) -> jnp.ndarray:
    """Flips the magnitude bits of negative floats' int32 bit patterns, so
    that int32 order is the floats' total order; it is its own inverse."""
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _order_key(x: jnp.ndarray) -> jnp.ndarray:
    """int32 keys whose order is the IEEE total order of float32 ``x``."""
    return _total_order(jax.lax.bitcast_convert_type(x, jnp.int32))


def _key_value(key: jnp.ndarray) -> jnp.ndarray:
    """The float32 whose ``_order_key`` is ``key``, bit for bit."""
    return jax.lax.bitcast_convert_type(_total_order(key), jnp.float32)


def _first_max(key: jnp.ndarray, valid: jnp.ndarray | None = None):
    """Largest ``key`` over the last axis (among ``valid`` entries), and the
    lowest index holding it: ([...] i32 key, [...] i32 index)."""
    col = jax.lax.broadcasted_iota(jnp.int32, key.shape, key.ndim - 1)
    if valid is not None:
        key = jnp.where(valid, key, _INT_MIN)
    top = key.max(-1)
    hit = key == top[..., None]
    if valid is not None:
        hit = hit & valid
    return top, jnp.where(hit, col, key.shape[-1]).min(-1)


def _ranks(key: jnp.ndarray) -> jnp.ndarray:
    """[n] key -> [n] i32 rank in descending order, ties to the lower
    index: a permutation of 0..n-1."""
    n = key.shape[-1]
    idx = np.arange(n)
    lower = jnp.asarray(idx[None, :] < idx[:, None])     # [i, j]: j < i
    before = (key[None, :] > key[:, None]) | (
        (key[None, :] == key[:, None]) & lower)
    return before.sum(-1, dtype=jnp.int32)


def _row_top2(proto: jnp.ndarray, use_pallas: bool, interpret: bool):
    """(best_col [N] i32, second_col [N] i32, flip_regret [N] f32)."""
    if use_pallas:
        from repro.kernels.knn_topk import row_top2_regret
        return row_top2_regret(proto, interpret=interpret)
    key = _order_key(proto)                               # [N, M]
    k1, best_col = _first_max(key)
    col = jax.lax.broadcasted_iota(jnp.int32, key.shape, 1)
    k2, second_col = _first_max(key, col != best_col[:, None])
    flip_regret = 2.0 * (_key_value(k1) - _key_value(k2))   # [N]
    return best_col, second_col, flip_regret


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


def _flip_sets(pool: int, pair_pool: int, triple_pool: int):
    """The candidate flip sets, as tuples of slots among the ``pool``
    cheapest flip rows: the empty set, every single, the pairs among the
    ``pair_pool`` cheapest and the triples among the ``triple_pool``
    cheapest."""
    p, t = min(pair_pool, pool), min(triple_pool, pool)
    return ([()] + [(i,) for i in range(pool)]
            + [(i, j) for i in range(p) for j in range(i + 1, p)]
            + [(i, j, l) for i in range(t) for j in range(i + 1, t)
               for l in range(j + 1, t)])


def _set_costs(cheap_cost: jnp.ndarray, sets) -> jnp.ndarray:
    """[C] cost of each flip set, summed in its order c[i] + c[j] (+ c[l]).
    Sets that differ only in their last slot follow each other over a run
    of slots, so each run is one slice of ``cheap_cost``: one op per run,
    not one per set, which halves the beam's TPU fusions."""
    parts = [jnp.zeros((1,), cheap_cost.dtype)]           # the empty set
    for head, run in itertools.groupby(sets[1:], key=lambda s: s[:-1]):
        last = [s[-1] for s in run]
        costs = cheap_cost[last[0]:last[-1] + 1]
        if head:
            prefix = cheap_cost[head[0]]
            for i in head[1:]:
                prefix = prefix + cheap_cost[i]
            costs = prefix + costs
        parts.append(costs)
    return jnp.concatenate(parts)


def _knn_beam(proto, k, pair_pool, triple_pool, use_pallas, interpret):
    n, m = proto.shape
    # best / 2nd-best machine per row + single-flip regret to the 2nd-best
    best_col, second_col, flip_regret = _row_top2(proto, use_pallas,
                                                  interpret)

    # the `pool` cheapest flip rows: slot[i, p] marks row i as the p-th
    pool = min(max(pair_pool, triple_pool, k), n)
    row_key = _order_key(-flip_regret)                    # cheapest largest
    slot = _ranks(row_key)[:, None] == jnp.arange(pool)   # [N, pool]
    cheap_key = jnp.where(slot, row_key[:, None], _INT_MIN).max(0)
    cheap_cost = -_key_value(cheap_key)                   # ascending regrets

    # candidate flip sets over the `pool` cheapest rows, and their costs
    sets = _flip_sets(pool, pair_pool, triple_pool)
    cand_masks = np.zeros((len(sets), pool), bool)        # [C, pool]
    for c, rows in enumerate(sets):
        cand_masks[c, list(rows)] = True
    cand_costs = _set_costs(cheap_cost, sets)             # [C]

    # the kk cheapest candidates, in order: sel[q] = flip set of rank q
    kk = min(k, cand_costs.shape[0])
    pick = _ranks(_order_key(-cand_costs))[None, :] == jnp.arange(kk)[:, None]
    sel = (pick[:, :, None] & cand_masks[None]).any(1)    # [kk, pool]

    # rows whose slot is in the set flip to their 2nd-best column
    flip = (sel[:, None, :] & slot[None]).any(-1)         # [kk, N]
    cols = jnp.where(flip, second_col, best_col)
    actions = jax.nn.one_hot(cols, m, dtype=jnp.float32)  # [kk, N, M]
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
