"""Pallas kernel for the MIQP-NN projection's hot inner step.

Computes, for every row of the proto-action matrix [N, M], the best and
second-best machine and the flip regret Δᵢ = 2(âᵢ,(1) − âᵢ,(2)) — the
quantities the exact k-best enumeration consumes (core/knn_projection.py).
Replaces the paper's per-instance Gurobi MIQP solve (~10 ms on a desktop)
with one vectorized pass.

Layout is lane-dense: the wrapper transposes the proto to [M, N] so the N
rows lie along the 128-wide lane axis and the M machines along sublanes.
Each program reduces an [M, row_blk] tile over sublanes and writes three
[1, row_blk] rows.  Every block's last dimension is a multiple of 128 and
its second-to-last is the array's full extent, so the kernel compiles for
TPU at any N and under ``vmap`` (which adds a grid axis per batch
dimension).  Ties resolve to the lowest machine index and NaN ranks
highest, as in ``lax.top_k``."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
LANES = 128


def _top2_kernel(proto_ref, best_ref, second_ref, regret_ref):
    p = proto_ref[...].astype(jnp.float32)                  # [M, row_blk]
    m = p.shape[0]
    machine = jax.lax.broadcasted_iota(jnp.int32, p.shape, 0).astype(
        jnp.float32)

    def first_argmax(x):
        # NaN ranks above every number, as in lax.top_k
        # (0/1 floats, not booleans: Mosaic cannot broadcast an i1 mask)
        nan = jnp.where(x != x, 1.0, 0.0)
        has_nan = nan.max(axis=0, keepdims=True)            # [1, row_blk]
        top = x.max(axis=0, keepdims=True)
        eq = jnp.where(x == top, 1.0, 0.0)
        hit = has_nan * nan + (1.0 - has_nan) * eq
        idx = jnp.where(hit > 0, machine, float(m)).min(axis=0, keepdims=True)
        return jnp.where(has_nan > 0, jnp.nan, top), idx

    best_val, best = first_argmax(p)
    second_val, second = first_argmax(jnp.where(machine == best, NEG_INF, p))
    best_ref[...] = best.astype(jnp.int32)
    second_ref[...] = second.astype(jnp.int32)
    regret_ref[...] = 2.0 * (best_val - second_val)


@functools.partial(jax.jit, static_argnames=("row_blk", "interpret"))
def row_top2_regret(proto: jnp.ndarray, *, row_blk: int = 512,
                    interpret: bool = False):
    """proto: [N, M] -> (best [N] i32, second [N] i32, regret [N] f32).

    ``row_blk`` (a multiple of 128) caps the proto rows one program
    reduces.  ``interpret=True`` emulates the kernel with plain XLA ops,
    for backends that cannot compile Mosaic (the CPU)."""
    if row_blk % LANES:
        raise ValueError(f"row_blk must be a multiple of {LANES}, "
                         f"got {row_blk}")
    N, M = proto.shape
    row_blk = min(row_blk, pl.cdiv(N, LANES) * LANES)
    Np = pl.cdiv(N, row_blk) * row_blk
    lanes = jnp.pad(proto.T, ((0, 0), (0, Np - N)))          # [M, Np]
    row_spec = pl.BlockSpec((1, row_blk), lambda i: (0, i))
    best, second, regret = pl.pallas_call(
        _top2_kernel,
        grid=(Np // row_blk,),
        in_specs=[pl.BlockSpec((M, row_blk), lambda i: (0, i))],
        out_specs=(row_spec, row_spec, row_spec),
        out_shape=(
            jax.ShapeDtypeStruct((1, Np), jnp.int32),
            jax.ShapeDtypeStruct((1, Np), jnp.int32),
            jax.ShapeDtypeStruct((1, Np), jnp.float32),
        ),
        interpret=interpret,
    )(lanes)
    return best[0, :N], second[0, :N], regret[0, :N]
