"""Per-kernel allclose vs pure-jnp oracles, swept over shapes/dtypes
(interpret=True executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("B,H,KV,S,hd,bq,bk,causal,dtype", [
    (2, 4, 2, 256, 64, 128, 128, True, jnp.float32),
    (1, 4, 4, 128, 32, 64, 64, False, jnp.float32),
    (1, 8, 2, 256, 128, 128, 64, True, jnp.float32),
    (2, 4, 1, 128, 64, 64, 128, True, jnp.bfloat16),
])
def test_flash_attention_fwd(B, H, KV, S, hd, bq, bk, causal, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), dtype)
    k = jax.random.normal(ks[1], (B, KV, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, KV, S, hd), dtype)
    o = ops.flash_attention(q, k, v, causal, bq, bk, True)
    o_ref = ref.attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref), atol=tol)


def test_flash_attention_grads_match_ref_autodiff():
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    B, H, KV, S, hd = 1, 4, 2, 256, 64
    q = jax.random.normal(ks[0], (B, H, S, hd))
    k = jax.random.normal(ks[1], (B, KV, S, hd))
    v = jax.random.normal(ks[2], (B, KV, S, hd))
    do = jax.random.normal(ks[3], (B, H, S, hd))

    def loss_kernel(q, k, v):
        return jnp.sum(ops.flash_attention(q, k, v, True, 128, 128, True) * do)

    def loss_ref(q, k, v):
        return jnp.sum(ref.attention_ref(q, k, v, causal=True) * do)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(gk, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   err_msg=f"d{nm}")


@pytest.mark.parametrize("B,H,KV,S,hd,bk", [
    (2, 8, 2, 1024, 64, 512),
    (1, 4, 4, 512, 128, 128),
    (3, 2, 1, 256, 32, 256),
])
def test_decode_attention(B, H, KV, S, hd, bk):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, KV, S, hd))
    v = jax.random.normal(ks[2], (B, KV, S, hd))
    lens = jax.random.randint(ks[3], (B,), 1, S + 1)
    o = ops.decode_attention(q, k, v, lens, block_k=bk)
    o_ref = ref.decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)


@pytest.mark.parametrize("l,chunk,n,p", [(256, 64, 32, 64), (128, 32, 16, 32)])
def test_mamba2_ssd_kernel(l, chunk, n, p):
    b, h = 2, 3
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = jax.random.normal(ks[3], (b, l, n))
    Cm = jax.random.normal(ks[4], (b, l, n))
    y, s = ops.mamba2_ssd(x, dt, A, Bm, Cm, chunk=chunk)
    y_r, s_r = ref.mamba2_ssd_ref(x, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r), atol=2e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_r), atol=2e-4)


@pytest.mark.parametrize("l,chunk,hd", [(128, 64, 64), (64, 32, 32)])
def test_rwkv6_kernel(l, chunk, hd):
    b, h = 2, 3
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    r = jax.random.normal(ks[0], (b, l, h, hd))
    k = jax.random.normal(ks[1], (b, l, h, hd))
    v = jax.random.normal(ks[2], (b, l, h, hd))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, l, h, hd))) * 0.5 + 0.4
    u = jax.random.normal(ks[4], (h, hd)) * 0.1
    o, s = ops.rwkv6_wkv(r, k, v, w, u, chunk=chunk)
    o_r, s_r = ref.rwkv6_wkv_ref(r, k, v, w, u, chunk=chunk)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_r), atol=5e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_r), atol=5e-4)


@pytest.mark.parametrize("T,D,F,E,tile", [(512, 128, 256, 8, 128),
                                          (256, 256, 128, 4, 128)])
def test_moe_gmm(T, D, F, E, tile):
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(ks[0], (T, D))
    w = jax.random.normal(ks[1], (E, D, F)) * 0.05
    eids = jax.random.randint(ks[2], (T,), 0, E)
    out = ops.moe_gmm_apply(x, w, eids, n_experts=E, tile_m=tile)
    out_ref = jnp.einsum("td,tdf->tf", x, w[eids])
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=2e-4)


# ------------------------------------------- paged split-KV flash-decode ----

def _pack_pages(k, v, block_size, n_blocks, rng):
    """Scatter a dense [B,KV,S,hd] cache into a shuffled paged arena with
    block tables (block 0 stays reserved as the pool's null block)."""
    B, KV, S, hd = k.shape
    MB = S // block_size
    bt = rng.permutation(np.arange(1, n_blocks))[:B * MB]
    bt = bt.reshape(B, MB).astype(np.int32)
    kp = np.zeros((n_blocks, block_size, KV, hd), np.float32)
    vp = np.zeros_like(kp)
    for b in range(B):
        for p in range(MB):
            lo = p * block_size
            kp[bt[b, p]] = np.moveaxis(
                np.asarray(k)[b, :, lo:lo + block_size], 0, 1)
            vp[bt[b, p]] = np.moveaxis(
                np.asarray(v)[b, :, lo:lo + block_size], 0, 1)
    return jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt)


@pytest.mark.parametrize("B,H,KV,S,hd,bs,ns", [
    (2, 4, 2, 64, 32, 8, 4),      # GQA, splits divide the pages evenly
    (1, 8, 8, 48, 16, 4, 3),      # MHA, 12 pages over 3 splits
    (3, 2, 1, 32, 32, 16, 4),     # MQA, want 4 splits of 2 pages -> 2
    (2, 4, 2, 64, 32, 8, 1),      # single split (plain paged decode)
])
def test_paged_decode_matches_dense(B, H, KV, S, hd, bs, ns):
    """Split-KV flash-decode through a shuffled block table == the dense
    decode oracle, under ragged lens (masked tail blocks)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, KV, S, hd))
    v = jax.random.normal(ks[2], (B, KV, S, hd))
    lens = jax.random.randint(ks[3], (B,), 1, S + 1)
    rng = np.random.default_rng(11)
    kp, vp, bt = _pack_pages(k, v, bs, B * (S // bs) + 3, rng)
    o = ops.paged_decode_attention(q, kp, vp, bt, lens, n_splits=ns)
    o_ref = ref.decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=3e-5)


def test_paged_decode_garbage_beyond_lens_is_masked():
    """Tokens past lens[b] — including whole trailing pages pointing at
    arbitrary (even shared) blocks — must not leak into the output."""
    B, H, KV, S, hd, bs = 2, 2, 2, 32, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, KV, S, hd))
    v = jax.random.normal(ks[2], (B, KV, S, hd))
    lens = jnp.asarray([9, 16])
    rng = np.random.default_rng(3)
    kp, vp, bt = _pack_pages(k, v, bs, B * (S // bs) + 2, rng)
    o1 = ops.paged_decode_attention(q, kp, vp, bt, lens, n_splits=2)
    # trash the arena blocks past each row's valid length: same output
    bt_np = np.asarray(bt).copy()
    dead = [bt_np[b, p] for b in range(B)
            for p in range(-(-int(lens[b]) // bs), S // bs)]
    kp2 = kp.at[jnp.asarray(dead)].set(999.0)
    vp2 = vp.at[jnp.asarray(dead)].set(-999.0)
    o2 = ops.paged_decode_attention(q, kp2, vp2, bt, lens, n_splits=2)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)


def test_update_kv_buffer_scatters_and_drops():
    """Paged append into a stacked arena: each entry lands at (layer,
    block, offset) of its row and nothing else moves, in that layer or any
    other; an entry aimed past the arena (a chunk's padding) drops
    instead of wrapping."""
    L, NB, BS, KV, hd = 3, 5, 4, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    kp0 = jax.random.normal(ks[0], (L, NB, BS, KV, hd))
    new = jax.random.normal(ks[1], (3, 1, KV, hd))
    blocks = jnp.asarray([[1], [3], [NB + 1]])       # last is out of range
    offsets = jnp.asarray([[2], [1], [0]])
    kp = np.asarray(ops.update_kv_buffer(kp0, 1, blocks, offsets, new))
    np.testing.assert_allclose(kp[1, 1, 2], np.asarray(new)[0, 0])
    np.testing.assert_allclose(kp[1, 3, 1], np.asarray(new)[1, 0])
    moved = np.zeros((L, NB, BS), bool)
    moved[1, 1, 2] = moved[1, 3, 1] = True
    np.testing.assert_allclose(kp[~moved], np.asarray(kp0)[~moved])


def test_moe_gmm_skewed_experts():
    """All tokens on one expert — ragged extreme."""
    T, D, F, E = 256, 64, 64, 8
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    x = jax.random.normal(ks[0], (T, D))
    w = jax.random.normal(ks[1], (E, D, F)) * 0.05
    eids = jnp.full((T,), 3, jnp.int32)
    out = ops.moe_gmm_apply(x, w, eids, n_experts=E, tile_m=128)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(x @ w[3]), atol=2e-4)
