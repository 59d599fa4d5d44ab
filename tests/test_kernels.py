"""Per-kernel shape/dtype sweeps, asserted allclose against ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


# ---------------------------------------------------------------- gated mm
GM_CASES = [
    # (M, K, N, zero_cols, zero_rows)
    (128, 128, 128, 0, 0),
    (256, 256, 512, 256, 0),     # N-underutilization (paper Fig 10 case 2)
    (384, 512, 256, 0, 256),     # K-underutilization (case 3)
    (128, 256, 384, 128, 128),   # both
    (512, 128, 128, 0, 0),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", GM_CASES)
def test_gated_matmul(case, dtype):
    M, K, N, zn, zk = case
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, hash(case) % 2**30))
    x = _rand(k1, (M, K), dtype)
    w = _rand(k2, (K, N), dtype)
    if zn:
        w = w.at[:, N - zn:].set(0.0)
    if zk:
        w = w.at[K - zk:, :].set(0.0)
    out = ops.gated_matmul(x, w, interpret=True)
    want = ref.ref_matmul(x, w)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=tol * np.abs(np.asarray(want)).max() + 1e-5, rtol=tol)


def test_gated_matmul_skips_zero_tiles():
    """The bitmap marks exactly the zero tiles (the energy/latency win)."""
    w = jnp.ones((256, 512)).at[:, 256:].set(0.0).at[128:, :].set(0.0)
    bm = ops.tile_nonzero_bitmap(w, 128, 128)
    assert bm.tolist() == [[1, 1, 0, 0], [0, 0, 0, 0]]


# ------------------------------------------------------------------- flash
FA_CASES = [
    (1, 256, 2, 64, True),
    (2, 256, 4, 128, True),
    (1, 512, 2, 64, False),
    (2, 128, 1, 128, True),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", FA_CASES)
def test_flash_attention_kernel(case, dtype):
    B, S, H, D, causal = case
    ks = jax.random.split(jax.random.fold_in(KEY, hash(case) % 2**30), 3)
    q = _rand(ks[0], (B, S, H, D), dtype)
    k = _rand(ks[1], (B, S, H, D), dtype)
    v = _rand(ks[2], (B, S, H, D), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, interpret=True)
    want = ref.ref_attention(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol * 3, rtol=tol)


# --------------------------------------------------------------------- ssd
SSD_CASES = [
    (2, 256, 64, 32, 128),
    (4, 256, 32, 16, 64),
    (1, 512, 64, 64, 128),
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_scan_kernel(case):
    BH, S, P, N, chunk = case
    ks = jax.random.split(jax.random.fold_in(KEY, hash(case) % 2**30), 5)
    x = _rand(ks[0], (BH, S, P), jnp.float32)
    dt = jax.nn.softplus(_rand(ks[1], (BH, S), jnp.float32))
    A = -jnp.exp(jax.random.uniform(ks[2], (BH,), minval=0.0, maxval=1.5))
    B = _rand(ks[3], (BH, S, N), jnp.float32)
    C = _rand(ks[4], (BH, S, N), jnp.float32)
    y, h = ops.ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    yr, hr = ref.ref_ssd(x, dt, A, B, C)
    scale = float(jnp.abs(yr).max()) + 1e-6
    np.testing.assert_allclose(np.asarray(y) / scale,
                               np.asarray(yr) / scale, atol=1e-4)
    hscale = float(jnp.abs(hr).max()) + 1e-6
    np.testing.assert_allclose(np.asarray(h) / hscale,
                               np.asarray(hr) / hscale, atol=1e-4)


def test_ssd_kernel_matches_model_path():
    """The Pallas kernel and the model's _ssd_chunk_scan agree."""
    from repro.models.blocks import _ssd_chunk_scan
    ks = jax.random.split(KEY, 5)
    Bz, S, nh, hd, N = 2, 256, 3, 32, 16
    x = _rand(ks[0], (Bz, S, nh, hd), jnp.float32)
    dt = jax.nn.softplus(_rand(ks[1], (Bz, S, nh), jnp.float32))
    A = -jnp.exp(jax.random.uniform(ks[2], (nh,), minval=0.0, maxval=1.5))
    Bm = _rand(ks[3], (Bz, S, nh, N), jnp.float32)
    Cm = _rand(ks[4], (Bz, S, nh, N), jnp.float32)
    y_model, h_model = _ssd_chunk_scan(x, dt, A, Bm, Cm)

    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(Bz * nh, S, -1)
    xk = fold(x)
    dtk = dt.transpose(0, 2, 1).reshape(Bz * nh, S)
    Ak = jnp.tile(A, (Bz,))
    Bk, Ck = fold(Bm), fold(Cm)
    yk, hk = ops.ssd_scan(xk, dtk, Ak, Bk, Ck, chunk=128, interpret=True)
    yk = yk.reshape(Bz, nh, S, hd).transpose(0, 2, 1, 3)
    scale = float(jnp.abs(y_model).max()) + 1e-6
    np.testing.assert_allclose(np.asarray(yk) / scale,
                               np.asarray(y_model) / scale, atol=2e-4)
    hk = hk.reshape(Bz, nh, hd, N)
    hscale = float(jnp.abs(h_model).max()) + 1e-6
    np.testing.assert_allclose(np.asarray(hk) / hscale,
                               np.asarray(h_model) / hscale, atol=2e-4)


# ----------------------------------------------------------- decode attn
DA_CASES = [(4, 1024, 64, 256, 300), (2, 2048, 128, 512, 2047),
            (3, 512, 32, 128, 0)]


@pytest.mark.parametrize("case", DA_CASES)
def test_decode_attention_kernel(case):
    from repro.kernels.decode_attention import decode_attention_p
    BH, S, D, bk, clen = case
    ks = jax.random.split(jax.random.fold_in(KEY, hash(case) % 2**30), 3)
    q = _rand(ks[0], (BH, D), jnp.float32)
    kc = _rand(ks[1], (BH, S, D), jnp.float32)
    vc = _rand(ks[2], (BH, S, D), jnp.float32)
    out = decode_attention_p(q, kc, vc, jnp.int32(clen), bk=bk,
                             interpret=True)
    s = jnp.einsum("bd,bkd->bk", q * D ** -0.5, kc)
    s = jnp.where(jnp.arange(S)[None, :] <= clen, s, -1e30)
    ref = jnp.einsum("bk,bkd->bd", jax.nn.softmax(s, -1), vc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
