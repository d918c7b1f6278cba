"""Logical-axis sharding policy with divisibility fallback (DESIGN.md §5).

Maps every parameter / activation / cache tensor to a PartitionSpec over
the production mesh axes:

  dp  = ("pod", "data")  (or ("data",) single-pod)  — FSDP / batch
  tp  = "model"                                      — TP / EP / SP

Rules are name-based on the param-tree path and *shape-aware*: a dimension
is only sharded if divisible by the mesh-axis size, otherwise the policy
falls back to sharding the other (contraction) dimension — e.g. yi-34b's
56 heads don't split 16 ways, so its attention projections shard d_model
and GSPMD inserts the partial-sum all-reduce; granite's 40 experts aren't
16-divisible so experts stay local and each expert FFN tensor-parallelizes
over d_ff."""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    dp: tuple[str, ...]          # data/FSDP axes, e.g. ("pod", "data")
    tp: str = "model"

    @classmethod
    def from_mesh(cls, mesh: Mesh) -> "MeshAxes":
        names = mesh.axis_names
        return cls(dp=tuple(n for n in names if n != "model"), tp="model")


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


class ShardingPolicy:
    def __init__(self, mesh: Mesh, cfg: ModelConfig, fsdp: bool = True):
        """fsdp=False replicates parameters across the data axes (pure DP +
        TP): no per-layer weight all-gathers, grads all-reduce once — the
        right trade below ~30B params where weights fit replicated (a §Perf
        hillclimb lever)."""
        self.mesh = mesh
        self.cfg = cfg
        self.fsdp = fsdp
        self.axes = MeshAxes.from_mesh(mesh)
        self.dp_size = _axis_size(mesh, self.axes.dp)
        self.tp_size = _axis_size(mesh, self.axes.tp)

    # -- helpers -------------------------------------------------------------
    def _fits(self, dim: int, axes) -> bool:
        if axes == self.axes.dp and not self.fsdp:
            return False          # parameters never shard over dp
        return dim % _axis_size(self.mesh, axes) == 0

    def _mm(self, shape, out_dim: int, in_dim: int) -> P:
        """Matmul weight [*, in, out]: prefer (in->dp, out->tp); fall back to
        (in->tp, out->dp); else replicate what doesn't fit."""
        dp, tp = self.axes.dp, self.axes.tp
        lead = (None,) * (len(shape) - 2)
        din, dout = shape[in_dim], shape[out_dim]
        if self._fits(dout, tp) and self._fits(din, dp):
            return P(*lead, dp, tp)
        if self._fits(dout, dp) and self._fits(din, tp):
            return P(*lead, tp, dp)
        if self._fits(dout, tp):
            return P(*lead, None, tp)
        if self._fits(din, tp):
            return P(*lead, tp, None)
        if self._fits(dout, dp):
            return P(*lead, None, dp)
        return P(*lead, None, None)

    def _mm_T(self, shape) -> P:
        """Weight [*, in, out] where in = the 'wide' model dim (down/out
        projections): prefer (in->tp, out->dp)."""
        dp, tp = self.axes.dp, self.axes.tp
        lead = (None,) * (len(shape) - 2)
        din, dout = shape[-2], shape[-1]
        if self._fits(din, tp) and self._fits(dout, dp):
            return P(*lead, tp, dp)
        if self._fits(din, dp) and self._fits(dout, tp):
            return P(*lead, dp, tp)
        if self._fits(din, tp):
            return P(*lead, tp, None)
        if self._fits(dout, tp):
            return P(*lead, None, tp)
        return P(*lead, None, None)

    def _vec(self, shape) -> P:
        lead = (None,) * (len(shape) - 1)
        if self._fits(shape[-1], self.axes.tp):
            return P(*lead, self.axes.tp)
        return P(*lead, None)

    # -- parameters ------------------------------------------------------------
    def param_spec(self, path: str, shape: tuple[int, ...]) -> P:
        dp, tp = self.axes.dp, self.axes.tp
        lead = (None,) * max(len(shape) - 2, 0)

        if "embed/table" in path:
            # [V, d]: vocab->tp when divisible (sharded logits); replicating
            # otherwise is cheaper than d-sharding (the token gather's
            # jvp/transpose trips the SPMD partitioner on d-sharded tables)
            if self._fits(shape[0], tp) and self._fits(shape[1], dp):
                return P(tp, dp)
            if self._fits(shape[0], tp):
                return P(tp, None)
            return P(None, None)
        if "lm_head" in path:
            return self._mm(shape, out_dim=-1, in_dim=-2)
        if "gnn/" in path:
            # graph-policy message-passing layers (core/graph_policy.py):
            # matrices tensor-parallelize over the model axis — the first
            # agent family where that axis is non-degenerate (the fleet's
            # data axes carry lanes, so pass fsdp=False)
            if len(shape) >= 2:
                return self._mm(shape, out_dim=-1, in_dim=-2)
            return self._vec(shape)
        if path.endswith("/b"):
            return self._vec(shape)
        if "norm" in path or "ln_x" in path:
            return P(*((None,) * len(shape)))
        if "router" in path:
            return P(*((None,) * len(shape)))

        # MoE stacked experts [..., E, in, out] (leading scan-block dim)
        if (any(k in path for k in ("ffn/gate", "ffn/up", "ffn/down"))
                and "shared" not in path and len(shape) >= 3):
            lead3 = (None,) * (len(shape) - 3)
            E = shape[-3]
            if self._fits(E, tp):
                # expert parallelism: experts over tp, d_ff over dp
                wide = -2 if "down" in path else -1   # the d_ff dimension
                spec = [None, None, None]
                spec[0] = tp
                if self._fits(shape[wide], dp):
                    spec[wide] = dp
                return P(*lead3, *spec)
            # TP fallback inside each expert
            if "down" in path:
                return P(*lead3, None, *self._mm_T(shape[-2:]))
            return P(*lead3, None, *self._mm(shape[-2:], out_dim=-1, in_dim=-2))

        if any(k in path for k in ("/gate/w", "/up/w", "wq/w", "wk/w", "wv/w",
                                   "in_proj/w", "Wr/w", "Wk/w", "Wv/w", "Wg/w",
                                   "Wck/w", "Wcr/w", "x_proj/w", "dt_proj/w",
                                   "w_lora1/w", "cross")):
            if "cross" in path and ("wo/w" in path):
                return self._mm_T(shape)
            return self._mm(shape, out_dim=-1, in_dim=-2)
        if any(k in path for k in ("/down/w", "wo/w", "out_proj/w", "Wo/w",
                                   "Wcv/w", "w_lora2/w")):
            return self._mm_T(shape)
        if "conv_w" in path:
            return P(*lead, None, tp) if self._fits(shape[-1], tp) else \
                P(*((None,) * len(shape)))
        if "A_log" in path or path.endswith("/D"):
            if self._fits(shape[-2] if len(shape) >= 2 else shape[-1], tp):
                return P(*((None,) * (len(shape) - 2)), tp, None) \
                    if len(shape) >= 2 else P(tp)
            return P(*((None,) * len(shape)))
        if path.endswith("/u") or "/mu" in path or "w_base" in path:
            return P(*((None,) * len(shape)))
        # default: replicate
        return P(*((None,) * len(shape)))

    def params_tree(self, abstract_params) -> Any:
        def spec_for(path, leaf):
            pstr = jax.tree_util.keystr(path, simple=True, separator="/")
            return self.param_spec(pstr, leaf.shape)
        return jax.tree_util.tree_map_with_path(spec_for, abstract_params)

    def params_sharding(self, abstract_params) -> Any:
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            self.params_tree(abstract_params))

    # -- batch / activations ----------------------------------------------------
    def batch_spec(self, batch_size: int) -> P:
        if batch_size % self.dp_size == 0:
            return P(self.axes.dp)
        return P(None)

    def batch_sharding(self, abstract_batch) -> Any:
        def spec(path, leaf):
            b = leaf.shape[0]
            base = self.batch_spec(b)
            return NamedSharding(self.mesh,
                                 P(*base, *([None] * (len(leaf.shape) - 1))))
        return jax.tree_util.tree_map_with_path(spec, abstract_batch)

    # -- decode cache -------------------------------------------------------------
    def cache_spec(self, path: str, shape: tuple[int, ...]) -> P:
        """Cache leaves are stacked [nb, B, ...]."""
        dp, tp = self.axes.dp, self.axes.tp
        if path.endswith("len") or len(shape) < 2:
            return P(*([None] * len(shape)))
        batch_ax = dp if shape[1] % self.dp_size == 0 else None
        if any(k in path for k in ("/k", "/v", "/ck", "/cv")):
            nb, B, S, hkv, hd = shape
            if hkv % self.tp_size == 0:
                return P(None, batch_ax, None, tp, None)
            if S % self.tp_size == 0:
                # sequence-sharded cache (flash-decoding style partial softmax)
                return P(None, batch_ax, tp, None, None)
            return P(None, batch_ax, None, None, None)
        if path.endswith("/h"):       # mamba state [nb,B,di,ds]
            return P(None, batch_ax, tp if shape[2] % self.tp_size == 0 else None, None)
        if path.endswith("/conv"):    # [nb,B,dc-1,di]
            return P(None, batch_ax, None, tp if shape[3] % self.tp_size == 0 else None)
        if path.endswith("/S"):       # rwkv state [nb,B,H,hd,hd]
            return P(None, batch_ax, tp if shape[2] % self.tp_size == 0 else None,
                     None, None)
        if "x_tm" in path or "x_cm" in path:
            return P(None, batch_ax, None)
        return P(*([None] * len(shape)))

    def cache_sharding(self, abstract_cache) -> Any:
        def spec(path, leaf):
            pstr = jax.tree_util.keystr(path, simple=True, separator="/")
            return NamedSharding(self.mesh, self.cache_spec(pstr, leaf.shape))
        return jax.tree_util.tree_map_with_path(spec, abstract_cache)

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())
