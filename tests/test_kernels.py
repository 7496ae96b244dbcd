"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# flash attention

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,K,Sq,Sk,D", [
    (1, 2, 2, 64, 64, 32),
    (2, 4, 2, 128, 128, 64),
    (1, 4, 1, 64, 128, 32),      # MQA, Sq != Sk
])
@pytest.mark.parametrize("window", [0, 16])
def test_flash_attention(B, H, K, Sq, Sk, D, window, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, K, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, K, D), dtype)
    out = ops.attention(q, k, v, causal=True, window=window, bq=32, bk=32)
    kr = jnp.repeat(k, H // K, 2).transpose(0, 2, 1, 3)
    vr = jnp.repeat(v, H // K, 2).transpose(0, 2, 1, 3)
    expect = ref.attention_ref(q.transpose(0, 2, 1, 3), kr, vr, causal=True,
                               window=window or None)
    expect = expect.transpose(0, 2, 1, 3).reshape(B, Sq, H * D)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


@pytest.mark.parametrize("Sq,Sk", [(129, 129), (131, 131), (129, 131),
                                   (64, 131)])
def test_flash_attention_odd_lengths_padded(Sq, Sk):
    """Prime / 128-indivisible sequence lengths must pad to the next block
    multiple with masked rows (the gram_log_volume recipe) instead of
    tripping the old hard ``Sq % bq == 0`` assert."""
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (1, Sq, 4, 16))
    k = jax.random.normal(ks[1], (1, Sk, 2, 16))
    v = jax.random.normal(ks[2], (1, Sk, 2, 16))
    for window in (0, 16):
        out = ops.attention(q, k, v, causal=True, window=window,
                            bq=32, bk=32)
        assert out.shape == (1, Sq, 4 * 16)
        kr = jnp.repeat(k, 2, 2).transpose(0, 2, 1, 3)
        vr = jnp.repeat(v, 2, 2).transpose(0, 2, 1, 3)
        expect = ref.attention_ref(q.transpose(0, 2, 1, 3), kr, vr,
                                   causal=True, window=window or None)
        expect = expect.transpose(0, 2, 1, 3).reshape(1, Sq, 4 * 16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   atol=2e-4, rtol=2e-4)


def test_flash_attention_block_shape_independence():
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 32))
    k = jax.random.normal(ks[1], (1, 128, 2, 32))
    v = jax.random.normal(ks[2], (1, 128, 2, 32))
    a = ops.attention(q, k, v, bq=32, bk=32)
    b = ops.attention(q, k, v, bq=128, bk=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# ---------------------------------------------------------------------------
# paged decode attention (the serving engine's Sq=1 hot path)

@pytest.mark.parametrize("H,K,D,ps,M", [(4, 2, 16, 8, 6),   # GQA
                                        (4, 4, 32, 4, 8),   # MHA
                                        (4, 1, 16, 16, 3)]) # MQA
@pytest.mark.parametrize("window", [0, 16])
@pytest.mark.parametrize("layer", [0, 2])
def test_paged_attention_kernel(H, K, D, ps, M, window, layer):
    """Pallas paged kernel (interpret) and the jnp gather path must both
    match the oracle — mixed fill levels incl. an idle (len 0) slot, read
    from one layer of a three-layer pool of (page, entry, K * D) rows."""
    B, P, Lr = 4, 24, 3
    ks = jax.random.split(jax.random.key(11), 4)
    q = jax.random.normal(ks[0], (B, 1, H, D))
    kp = jax.random.normal(ks[1], (Lr, P, ps, K * D))
    vp = jax.random.normal(ks[2], (Lr, P, ps, K * D))
    bt = jax.random.randint(ks[3], (B, M), 1, P)
    lens = jnp.array([1, ps + 1, M * ps, 0], jnp.int32)
    w = jnp.int32(window if window else 1 << 30)
    lyr = jnp.int32(layer)
    want = np.asarray(ref.paged_attention_ref(
        q, kp, vp, layer, bt, lens, window=window or None)
        ).reshape(B, 1, H * D)
    got_kernel = ops.paged_attention(q, kp, vp, lyr, bt, lens, w,
                                     use_kernel=True, interpret=True)
    got_jnp = ops.paged_attention(q, kp, vp, lyr, bt, lens, w,
                                  use_kernel=False)
    np.testing.assert_allclose(np.asarray(got_kernel), want,
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(got_jnp), want,
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("H,K", [(4, 2), (4, 4)])          # GQA, MHA
@pytest.mark.parametrize("layer", [0, 1])
def test_paged_attention_matches_contiguous(H, K, layer):
    """A paged cache whose block table is a permutation must reproduce
    plain end-aligned causal attention over the logically contiguous KV,
    from whichever layer of the pool holds it."""
    B, D, ps, M, Lr = 2, 16, 8, 4, 2
    S = M * ps
    ks = jax.random.split(jax.random.key(12), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D))
    k = jax.random.normal(ks[1], (B, S, K, D))
    v = jax.random.normal(ks[2], (B, S, K, D))
    # scatter the contiguous KV into a shuffled physical pool; the other
    # layer holds noise
    perm = np.array([[3, 6, 1, 5], [2, 7, 4, 8]], np.int32)
    kp = jax.random.normal(ks[0], (Lr, 9, ps, K * D))
    vp = jax.random.normal(ks[1], (Lr, 9, ps, K * D))
    for b in range(B):
        for j in range(M):
            blk = slice(j * ps, (j + 1) * ps)
            kp = kp.at[layer, perm[b, j]].set(k[b, blk].reshape(ps, K * D))
            vp = vp.at[layer, perm[b, j]].set(v[b, blk].reshape(ps, K * D))
    lens = jnp.array([S, S], jnp.int32)
    got = ops.paged_attention(q, kp, vp, jnp.int32(layer), jnp.asarray(perm),
                              lens, jnp.int32(1 << 30), use_kernel=True,
                              interpret=True)
    kr = jnp.repeat(k, H // K, 2).transpose(0, 2, 1, 3)
    vr = jnp.repeat(v, H // K, 2).transpose(0, 2, 1, 3)
    expect = ref.attention_ref(q.transpose(0, 2, 1, 3), kr, vr, causal=True)
    expect = expect.transpose(0, 2, 1, 3).reshape(B, 1, H * D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# gram volume

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,k,d", [(32, 2, 16), (64, 4, 32), (128, 5, 64),
                                   (16, 8, 8)])
def test_gram_volume(B, k, d, dtype):
    vs = jax.random.normal(jax.random.key(2), (B, k, d), dtype)
    mask = jax.random.bernoulli(jax.random.key(3), 0.7, (B, k))
    got = ops.gram_log_volume(vs, mask)
    want = ref.gram_log_volume_ref(vs, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-4,
                               rtol=1e-2)


def test_gram_volume_no_mask():
    vs = jax.random.normal(jax.random.key(4), (64, 3, 16))
    np.testing.assert_allclose(np.asarray(ops.gram_log_volume(vs)),
                               np.asarray(ref.gram_log_volume_ref(vs)),
                               atol=1e-4)


@pytest.mark.parametrize("B", [131, 257, 129])
def test_gram_volume_prime_batch_padded(B):
    """Prime (and otherwise 128-indivisible) batch sizes > 128 must pad to
    the next 128 multiple with masked rows — NOT degrade to a bb=1 grid of
    one step per row (the PR 4 block-size fallback bugfix)."""
    vs = jax.random.normal(jax.random.key(5), (B, 4, 16))
    mask = jax.random.bernoulli(jax.random.key(6), 0.7, (B, 4))
    got = ops.gram_log_volume(vs, mask)
    assert got.shape == (B,)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.gram_log_volume_ref(vs, mask)),
                               atol=1e-4, rtol=1e-4)
    # no-mask variant exercises the synthesized all-ones mask + padding
    got2 = ops.gram_log_volume(vs)
    np.testing.assert_allclose(np.asarray(got2),
                               np.asarray(ref.gram_log_volume_ref(vs)),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# lora matmul

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("M,K,N,r", [(64, 64, 64, 4), (128, 256, 128, 8),
                                     (256, 128, 64, 16)])
def test_lora_matmul(M, K, N, r, dtype):
    ks = jax.random.split(jax.random.key(5), 4)
    x = jax.random.normal(ks[0], (M, K), dtype)
    w = jax.random.normal(ks[1], (K, N), dtype)
    a = jax.random.normal(ks[2], (K, r), dtype)
    b = jax.random.normal(ks[3], (r, N), dtype)
    got = ops.lora_matmul(x, w, a, b, scale=2.0, bm=64, bn=64, bk=64)
    want = ref.lora_matmul_ref(x, w, a, b, 2.0)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want),
        atol=(2.0 if dtype == jnp.bfloat16 else 1e-3),
        rtol=(5e-2 if dtype == jnp.bfloat16 else 1e-4))


# ---------------------------------------------------------------------------
# ssd

@pytest.mark.parametrize("B,S,H,P,G,N,chunk", [
    (1, 32, 2, 8, 1, 4, 8),
    (2, 64, 4, 16, 2, 8, 16),
    (1, 128, 2, 32, 1, 16, 32),
])
def test_ssd_chunk_kernel_vs_recurrent(B, S, H, P, G, N, chunk):
    ks = jax.random.split(jax.random.key(6), 5)
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.2)
    B_ = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
    C_ = jax.random.normal(ks[4], (B, S, G, N)) * 0.5
    got = ops.ssd_chunked(x, dt, A, B_, C_, chunk=chunk)
    want = ref.ssd_recurrent_ref(x, dt, A, B_, C_)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-3)


def test_ssd_jnp_chunked_matches_kernel_path():
    from repro.models.ssm import ssd_reference
    ks = jax.random.split(jax.random.key(7), 5)
    B, S, H, P, G, N = 2, 64, 4, 16, 2, 8
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.2)
    B_ = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
    C_ = jax.random.normal(ks[4], (B, S, G, N)) * 0.5
    a = ssd_reference(x, dt, A, B_, C_, 16)
    b = ops.ssd_chunked(x, dt, A, B_, C_, chunk=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                               rtol=1e-3)
