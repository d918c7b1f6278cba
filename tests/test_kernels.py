"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the kernel bodies in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.knn_topk import row_top2_regret, row_top2_regret_ref
from repro.kernels.rwkv6_scan import wkv6, wkv6_ref

TOLS = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# -- flash attention -----------------------------------------------------------
@pytest.mark.parametrize("S,H,Hkv,hd,causal,dtype", [
    (128, 4, 4, 64, True, jnp.float32),      # MHA causal
    (128, 4, 2, 64, True, jnp.float32),      # GQA 2:1
    (256, 8, 2, 32, True, jnp.float32),      # GQA 4:1, longer
    (128, 4, 1, 64, True, jnp.float32),      # MQA
    (128, 4, 2, 64, False, jnp.float32),     # bidirectional (encoder)
    (128, 4, 2, 64, True, jnp.bfloat16),     # bf16 inputs
])
def test_flash_attention_vs_ref(S, H, Hkv, hd, causal, dtype):
    B = 2
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, q_blk=64, kv_blk=64)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=TOLS[dtype], rtol=TOLS[dtype])


def test_flash_attention_block_shape_invariance():
    B, S, H, Hkv, hd = 1, 256, 2, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, Hkv, hd))
    v = jax.random.normal(ks[2], (B, S, Hkv, hd))
    o1 = flash_attention(q, k, v, q_blk=64, kv_blk=64)
    o2 = flash_attention(q, k, v, q_blk=128, kv_blk=32)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               atol=1e-5, rtol=1e-5)


def test_jnp_chunked_attention_matches_ref():
    """models/attention.flash_attention (the XLA path used in the dry-run)
    against the same oracle."""
    from repro.models.attention import flash_attention as fa_jnp
    B, S, H, Hkv, hd = 2, 256, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, Hkv, hd))
    v = jax.random.normal(ks[2], (B, S, Hkv, hd))
    out = fa_jnp(q, k, v, causal=True, q_chunk=64, kv_chunk=64)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_decode_attention_matches_full():
    from repro.models.attention import decode_attention
    B, S, H, Hkv, hd = 2, 33, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q_all = jax.random.normal(ks[0], (B, S, H, hd))
    k_all = jax.random.normal(ks[1], (B, S, Hkv, hd))
    v_all = jax.random.normal(ks[2], (B, S, Hkv, hd))
    full = attention_ref(q_all, k_all, v_all, causal=True)
    dec = decode_attention(q_all[:, -1:], k_all, v_all,
                           jnp.asarray(S, jnp.int32))
    np.testing.assert_allclose(np.asarray(dec[:, 0]),
                               np.asarray(full[:, -1]), atol=2e-5, rtol=2e-5)


# -- rwkv6 ----------------------------------------------------------------------
@pytest.mark.parametrize("T,H,hd,chunk,dtype", [
    (64, 2, 16, 16, jnp.float32),
    (128, 3, 16, 32, jnp.float32),
    (96, 2, 8, 32, jnp.float32),       # T not a multiple of 64
    (64, 2, 16, 16, jnp.bfloat16),
])
def test_wkv6_vs_ref(T, H, hd, chunk, dtype):
    B = 2
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    w = (jax.nn.sigmoid(jax.random.normal(ks[0], (B, T, H, hd))) * 0.5
         + 0.45).astype(dtype)
    r = jax.random.normal(ks[1], (B, T, H, hd), dtype)
    k = jax.random.normal(ks[2], (B, T, H, hd), dtype)
    v = jax.random.normal(ks[3], (B, T, H, hd), dtype)
    u = (jax.random.normal(ks[4], (H, hd)) * 0.1).astype(dtype)
    out = wkv6(w, r, k, v, u, chunk=chunk)
    ref, _ = wkv6_ref(w, r, k, v, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=TOLS[jnp.float32 if dtype == jnp.float32
                                         else jnp.bfloat16] * 5, rtol=1e-2)


def test_wkv6_chunk_invariance():
    B, T, H, hd = 1, 64, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    w = jax.nn.sigmoid(jax.random.normal(ks[0], (B, T, H, hd))) * 0.5 + 0.45
    r, k, v = (jax.random.normal(ks[i], (B, T, H, hd)) for i in (1, 2, 3))
    u = jax.random.normal(ks[4], (H, hd)) * 0.1
    o1 = wkv6(w, r, k, v, u, chunk=16)
    o2 = wkv6(w, r, k, v, u, chunk=64)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               atol=1e-5, rtol=1e-5)


# -- knn_topk --------------------------------------------------------------------
@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000), st.integers(2, 60), st.integers(2, 16))
def test_knn_topk_vs_ref(seed, n, m):
    proto = jax.random.uniform(jax.random.PRNGKey(seed), (n, m))
    b, s, r = row_top2_regret(proto, row_blk=128, interpret=True)
    br, sr, rr = row_top2_regret_ref(proto)
    assert bool(jnp.all(b == br))
    assert bool(jnp.all(s == sr))
    np.testing.assert_allclose(np.asarray(r), np.asarray(rr),
                               atol=1e-6, rtol=1e-6)


def test_knn_topk_regret_nonnegative():
    proto = jax.random.uniform(jax.random.PRNGKey(1), (50, 10))
    _, _, r = row_top2_regret(proto, interpret=True)
    assert bool(jnp.all(r >= 0))


@pytest.mark.parametrize("lead,n,m,row_blk", [
    ((), 300, 10, 128),        # several programs, ragged last block
    ((), 512, 10, 512),        # one wide program
    ((3,), 100, 10, 512),      # vmapped over a fleet axis
    ((2, 3), 40, 6, 128),      # nested vmap, as the DDPG target values
])
def test_knn_topk_blocks_and_vmap_vs_ref(lead, n, m, row_blk):
    """Multi-block grids and vmap batching (one grid axis per batch
    dimension) reduce exactly like lax.top_k: ties to the lowest index,
    NaN above every number."""
    proto = jax.random.uniform(jax.random.PRNGKey(n), lead + (n, m))
    proto = proto.at[..., 0, 1].set(proto[..., 0, 0])       # a tie
    proto = proto.at[..., 1, 3].set(jnp.nan)                # one NaN
    proto = proto.at[..., 2, 0].set(jnp.nan).at[..., 2, m - 1].set(jnp.nan)
    f = lambda p: row_top2_regret(p, row_blk=row_blk, interpret=True)
    g = row_top2_regret_ref
    for _ in lead:
        f, g = jax.vmap(f), jax.vmap(g)
    b, s, r = f(proto)
    br, sr, rr = g(proto)
    np.testing.assert_array_equal(np.asarray(b), np.asarray(br))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))
    np.testing.assert_allclose(np.asarray(r), np.asarray(rr),
                               atol=1e-6, rtol=1e-6)
