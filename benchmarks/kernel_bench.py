"""Kernel microbenchmarks: XLA reference paths timed on CPU (wall time is
NOT a TPU prediction — the derived column reports the structural metric
that matters per kernel: exact-causal FLOPs, VMEM working set, etc.).
Pallas kernels themselves are validated in interpret mode (tests/); their
rows appear only on a TPU, where they compile."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def timeit(fn, *args, iters: int = 3) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def bench_flash_attention_ref() -> list[tuple]:
    from repro.models.attention import flash_attention
    rows = []
    for (S, H, Hkv, hd) in [(1024, 8, 2, 64), (2048, 8, 2, 64)]:
        B = 1
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, Hkv, hd), jnp.float32)
        f = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
        us = timeit(f, q, k, v)
        useful_flops = 2 * 2 * B * H * hd * S * (S + 1) / 2
        rows.append((f"flash_attn_ref_S{S}", us,
                     f"causal_flops={useful_flops:.3e}"))
    return rows


def bench_wkv6_ref() -> list[tuple]:
    from repro.kernels.rwkv6_scan.ref import wkv6_ref
    rows = []
    for (T, H, hd) in [(512, 8, 64), (1024, 8, 64)]:
        B = 1
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        w = jax.nn.sigmoid(jax.random.normal(ks[0], (B, T, H, hd))) * .5 + .45
        r, k, v = (jax.random.normal(ks[i], (B, T, H, hd)) for i in (1, 2, 3))
        u = jax.random.normal(ks[4], (H, hd)) * 0.1
        f = jax.jit(lambda *a: wkv6_ref(*a)[0])
        us = timeit(f, w, r, k, v, u)
        state_bytes = H * hd * hd * 4
        rows.append((f"wkv6_ref_T{T}", us,
                     f"vmem_state_bytes={state_bytes}"))
    return rows


def bench_knn_projection() -> list[tuple]:
    from repro.core.knn_projection import knn_actions_exact, knn_actions_jax
    rows = []
    for (n, m, k) in [(100, 10, 16), (100, 10, 32)]:
        proto = np.random.default_rng(0).uniform(size=(n, m))
        t0 = time.perf_counter()
        for _ in range(20):
            knn_actions_exact(proto, k)
        us = (time.perf_counter() - t0) / 20 * 1e6
        rows.append((f"knn_exact_N{n}M{m}K{k}", us,
                     "replaces_gurobi_miqp~10000us"))
        pj = jnp.asarray(proto)
        f = jax.jit(lambda p, k=k: knn_actions_jax(p, k))
        us = timeit(f, pj)
        rows.append((f"knn_beam_N{n}M{m}K{k}", us, "xla_row_reductions"))
        # Pallas-backed top-2/regret reduction (kernels/knn_topk): timed
        # only where it compiles; the interpreter's time is not the kernel's
        if pj.device.platform != "tpu":
            continue
        fp = jax.jit(lambda p, k=k: knn_actions_jax(p, k, use_pallas=True))
        us = timeit(fp, pj)
        rows.append((f"knn_beam_pallas_N{n}M{m}K{k}", us,
                     "row_top2_regret_kernel"))
    return rows


def bench_simulator() -> list[tuple]:
    from repro.dsdps import SchedulingEnv, apps
    from repro.dsdps.apps import default_workload
    topo = apps.continuous_queries("large")
    env = SchedulingEnv(topo, default_workload(topo))
    w = env.workload.init()
    X = env.round_robin_assignment()
    f = jax.jit(lambda X, w: env.evaluate(X, w))
    us = timeit(f, X, w)
    return [("dsdps_sim_eval_100x10", us, "env_reward_latency")]


def run_all() -> list[tuple]:
    rows = []
    rows += bench_simulator()
    rows += bench_knn_projection()
    rows += bench_flash_attention_ref()
    rows += bench_wkv6_ref()
    return rows
