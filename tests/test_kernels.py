"""Per-kernel allclose sweeps against the pure-jnp oracles in
kernels/ref.py (interpret mode executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


# ------------------------------------------------------------------
# parle_update
# ------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(8, 1024), (3, 1000), (17,), (2, 5, 129)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_parle_update_shapes(shape, dtype, key):
    ks = jax.random.split(key, 5)
    y, z, v, g, x = [jax.random.normal(k, shape, dtype) for k in ks]
    kw = dict(inv_gamma=0.01, lr=0.1, mu=0.9, alpha=0.75)
    ko = ops.parle_inner_update({"w": y}, {"w": z}, {"w": v}, {"w": g},
                                {"w": x}, **kw)
    ro = ref.parle_inner_update(y, z, v, g, x, **kw)
    for a, b in zip(ko, ro):
        np.testing.assert_allclose(np.asarray(a["w"]), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_parle_update_multi_leaf_tree(key):
    tree = {"a": jax.random.normal(key, (4, 7)),
            "b": {"c": jax.random.normal(key, (33,))}}
    zeros = jax.tree.map(jnp.zeros_like, tree)
    kw = dict(inv_gamma=0.1, lr=0.05, mu=0.9, alpha=0.5)
    y2, z2, v2 = ops.parle_inner_update(tree, zeros, zeros, tree, zeros, **kw)
    ry, rz, rv = ref.parle_inner_update(tree["a"], zeros["a"], zeros["a"],
                                        tree["a"], zeros["a"], **kw)
    np.testing.assert_allclose(np.asarray(y2["a"]), np.asarray(ry), rtol=1e-6)


# ------------------------------------------------------------------
# flash_attention
# ------------------------------------------------------------------

# tier-1 keeps one block-shape combo per head dim; the full sweep
# rides the slow lane (CI kernel job runs with addopts overridden)
@pytest.mark.parametrize("T,bq,bk", [
    (128, 128, 64),
    pytest.param(128, 64, 64, marks=pytest.mark.slow),
    pytest.param(256, 128, 128, marks=pytest.mark.slow),
    pytest.param(64, 64, 64, marks=pytest.mark.slow),
])
@pytest.mark.parametrize("hd", [32, 64])
def test_flash_attention_causal(T, bq, bk, hd, key):
    B, H = 2, 3
    ks = jax.random.split(key, 3)
    q, k, v = [jax.random.normal(kk, (B, T, H, hd)) for kk in ks]
    o_k = ops.flash_attention(q, k, v, block_q=bq, block_k=bk)
    o_r = ref.flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [
    32,
    pytest.param(16, marks=pytest.mark.slow),
    pytest.param(100, marks=pytest.mark.slow),
])
def test_flash_attention_window(window, key):
    B, T, H, hd = 1, 128, 2, 32
    ks = jax.random.split(key, 3)
    q, k, v = [jax.random.normal(kk, (B, T, H, hd)) for kk in ks]
    o_k = ops.flash_attention(q, k, v, window=window, block_q=64, block_k=64)
    o_r = ref.flash_attention(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype, key):
    B, T, H, hd = 1, 128, 2, 64
    ks = jax.random.split(key, 3)
    q, k, v = [jax.random.normal(kk, (B, T, H, hd)).astype(dtype) for kk in ks]
    o_k = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    o_r = ref.flash_attention(q, k, v)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32), rtol=tol, atol=tol)


# ------------------------------------------------------------------
# ssd_scan
# ------------------------------------------------------------------

@pytest.mark.parametrize("T,chunk", [
    (128, 128),
    pytest.param(64, 16, marks=pytest.mark.slow),
    pytest.param(128, 32, marks=pytest.mark.slow),
    pytest.param(96, 32, marks=pytest.mark.slow),
])
@pytest.mark.parametrize("N,P", [(16, 32), (64, 64)])
def test_ssd_scan_vs_naive(T, chunk, N, P, key):
    B, nh = 2, 4            # whole 128-lane groups of heads at P 32 and 64
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, T, nh, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, T, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, T, N)) * 0.5
    yk, hk = ops.ssd_scan(x.reshape(B, T, nh * P), dt, A, Bm, Cm,
                          chunk=chunk)
    yr, hr = ref.ssd_scan(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(yk).reshape(B, T, nh, P),
                               np.asarray(yr), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hk), np.asarray(hr),
                               rtol=1e-4, atol=1e-4)


# (replicas or None, B, T, chunk, nh, P, N, fast decay)
_SSD_VJP_CASES = {
    # 3 chunks; 2 blocks of 16 heads, each 8 lane groups of 2 heads
    "chunks_and_head_blocks": (None, 1, 96, 32, 32, 64, 16, False),
    "batch": (None, 3, 64, 32, 4, 32, 8, False),
    # the Parle round's vmap over a leading replica axis
    "replicas_vmap": (2, 1, 64, 32, 8, 64, 16, False),
    # dt |A| = 128 a step: exp(cum_i - cum_j) overflows above the diagonal
    # unless masked before the exp
    "fast_decay": (None, 1, 64, 32, 2, 64, 4, True),
}
_SSD_OUTS = ("y", "h", "dx", "ddt", "dA", "dB", "dC")
# max |error| / max |value| of the bfloat16-operand branch against float32
# ssd_chunked: <= 0.0065 over four keys; with the in-chunk cumulative sums
# rounded to bfloat16 as well it reads >= 0.035
BF16_SSD_ERR = 0.015


def _ssd_vjp(side, case, key):
    """y, the final state and the full VJP (dx, ddt, dA, dB_mat, dC_mat)
    of the fused op (``side`` "fused") or of ``ssd_chunked``."""
    from repro.models.mamba2 import ssd_chunked
    R, B, T, chunk, nh, P, N, fast = _SSD_VJP_CASES[case]
    lead = (R,) if R else ()
    ks = jax.random.split(key, 7)
    x = jax.random.normal(ks[0], lead + (B, T, nh * P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], lead + (B, T, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], lead + (nh,)) * 0.3)
    if fast:
        dt, A = jnp.full_like(dt, 8.0), jnp.full_like(A, -16.0)
    Bm = jax.random.normal(ks[3], lead + (B, T, N)) * 0.5
    Cm = jax.random.normal(ks[4], lead + (B, T, N)) * 0.5
    cts = (jax.random.normal(ks[5], lead + (B, T, nh * P)),
           jax.random.normal(ks[6], lead + (B, nh, N, P)))

    def chunked(x, dt, A, Bm, Cm):
        y, h = ssd_chunked(x.reshape(B, T, nh, P), dt, A, Bm, Cm, chunk)
        return y.reshape(B, T, nh * P), h

    def fused(x, dt, A, Bm, Cm):
        return ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)

    def vjp(f):
        def g(x, dt, A, Bm, Cm, cts):
            out, pull = jax.vjp(f, x, dt, A, Bm, Cm)
            return out + pull(cts)
        return jax.vmap(g) if R else g

    f = fused if side == "fused" else chunked
    return [np.asarray(a) for a in vjp(f)(x, dt, A, Bm, Cm, cts)]


@pytest.mark.parametrize("case", sorted(_SSD_VJP_CASES))
def test_ssd_scan_vjp_matches_chunked(case, key):
    """The fused op's y, final state and full VJP (dx, ddt, dA, dB_mat,
    dC_mat) against ``jax.vjp`` of the pure-jnp ``ssd_chunked``."""
    got = _ssd_vjp("fused", case, key)
    want = _ssd_vjp("chunked", case, key)
    for name, a, b in zip(_SSD_OUTS, got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and np.isfinite(a).all(), name
        # dA sums over every position: both sides round that sum in f32
        atol = (5e-4 if name == "dA" else 2e-5) * np.abs(b).max()
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=atol, err_msg=name)


def test_ssd_scan_bf16_dots_match_chunked(key):
    """The branch a TPU compiles (bfloat16 dot operands, float32
    accumulation), interpreted under a bfloat16
    ``default_matmul_precision``: every output stays within bfloat16
    rounding of float32 ``ssd_chunked``, and differs from the float32
    branch, so the bfloat16 branch is the one that ran."""
    case = "chunks_and_head_blocks"
    want = _ssd_vjp("chunked", case, key)
    f32 = _ssd_vjp("fused", case, key)
    with jax.default_matmul_precision("bfloat16"):
        got = _ssd_vjp("fused", case, key)
    for name, a, a32, b in zip(_SSD_OUTS, got, f32, want):
        assert np.isfinite(a).all() and not np.array_equal(a, a32), name
        err = np.abs(a - b).max() / np.abs(b).max()
        assert err < BF16_SSD_ERR, (name, err)


@pytest.mark.slow
def test_ssd_chunked_jnp_path_vs_naive(key):
    """The model's pure-jnp chunked path against the naive recurrence,
    including a resume-from-state (h0) case the kernel delegates."""
    from repro.models.mamba2 import ssd_chunked
    B, T, nh, P, N = 1, 64, 2, 16, 8
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (B, T, nh, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, T, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, T, N)) * 0.5
    h0 = jax.random.normal(ks[5], (B, nh, N, P)) * 0.1
    yk, hk = ssd_chunked(x, dt, A, Bm, Cm, 16, h0=h0)
    yr, hr = ref.ssd_scan(x, dt, A, Bm, Cm, h0=h0)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hk), np.asarray(hr),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------------
# paged_attention
# ------------------------------------------------------------------

@pytest.mark.parametrize("H,KV,hd", [
    (4, 2, 32),
    pytest.param(4, 4, 64, marks=pytest.mark.slow),     # MHA, no GQA fold
    pytest.param(8, 2, 16, marks=pytest.mark.slow),     # wide GQA group
])
@pytest.mark.parametrize("ps,M", [
    (16, 4),
    pytest.param(8, 7, marks=pytest.mark.slow),         # odd page count
])
def test_paged_attention_vs_oracle(H, KV, hd, ps, M, key):
    """The Pallas paged-decode kernel against the gather-then-softmax
    oracle: random page tables (rows share pages, trash page unused
    entries) and ragged per-row lengths."""
    B, P = 3, 12
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, H, hd))
    k_pool = jax.random.normal(ks[1], (P, ps, KV, hd))
    v_pool = jax.random.normal(ks[2], (P, ps, KV, hd))
    # each row gets a random permutation of usable pages; entries past
    # the row's live extent point at the trash page (id 0)
    rng = np.random.default_rng(0)
    table = np.stack([rng.permutation(np.arange(1, P))[:M] for _ in range(B)])
    lengths = np.array([1, ps * M, ps * (M - 1) + ps // 2], np.int32)[:B]
    for b in range(B):
        used = -(-int(lengths[b]) // ps)
        table[b, used:] = 0
    o_k = ops.paged_attention(q, k_pool, v_pool, jnp.asarray(table, jnp.int32),
                              jnp.asarray(lengths))
    o_r = ref.paged_attention(q, k_pool, v_pool, jnp.asarray(table, jnp.int32),
                              jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_paged_attention_shared_pages(key):
    """Two rows whose tables name the SAME pages (prefix sharing) score
    identically up to their common live extent."""
    H, KV, hd, ps, M, P = 4, 2, 32, 8, 3, 8
    ks = jax.random.split(key, 3)
    q1 = jax.random.normal(ks[0], (1, H, hd))
    q = jnp.concatenate([q1, q1], axis=0)
    k_pool = jax.random.normal(ks[1], (P, ps, KV, hd))
    v_pool = jax.random.normal(ks[2], (P, ps, KV, hd))
    table = jnp.asarray([[3, 5, 1], [3, 5, 2]], jnp.int32)  # shared prefix
    lengths = jnp.asarray([2 * ps, 2 * ps], jnp.int32)      # live < page 3
    o = ops.paged_attention(q, k_pool, v_pool, table, lengths)
    np.testing.assert_array_equal(np.asarray(o[0]), np.asarray(o[1]))
