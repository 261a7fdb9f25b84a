"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def rand(rng, shape, dtype):
    x = rng.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(42)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,T,H,KV,hd", [
    (1, 128, 128, 2, 2, 32),
    (2, 256, 256, 4, 2, 64),     # GQA G=2
    (1, 128, 256, 4, 1, 32),     # MQA, cross-length
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_causal(rng, B, S, T, H, KV, hd, dtype):
    q = rand(rng, (B, S, H, hd), dtype)
    k = rand(rng, (B, T, KV, hd), dtype)
    v = rand(rng, (B, T, KV, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=True, bq=64, bk=64)
    want = ref.attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_non_causal(rng):
    q = rand(rng, (1, 128, 2, 32), jnp.float32)
    k = rand(rng, (1, 128, 2, 32), jnp.float32)
    v = rand(rng, (1, 128, 2, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=False, bq=64, bk=64)
    want = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [32, 64, 128])
def test_flash_local_window(rng, window):
    q = rand(rng, (1, 256, 2, 32), jnp.float32)
    k = rand(rng, (1, 256, 2, 32), jnp.float32)
    v = rand(rng, (1, 256, 2, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              bq=64, bk=64)
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_matches_model_chunked(rng):
    """Pallas kernel == the model's XLA chunked path (same math)."""
    from repro.models.attention import chunked_attention
    q = rand(rng, (2, 128, 4, 32), jnp.float32)
    k = rand(rng, (2, 128, 2, 32), jnp.float32)
    v = rand(rng, (2, 128, 2, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, bq=64, bk=64)
    want = chunked_attention(q, k, v, causal=True, chunk=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# RG-LRU scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,W,chunk,bw", [
    (1, 64, 128, 16, 128),
    (2, 256, 256, 64, 128),
    (1, 128, 512, 128, 512),
])
def test_rglru(rng, B, S, W, chunk, bw):
    a = jnp.asarray(rng.uniform(0.8, 0.999, (B, S, W)), jnp.float32)
    b = rand(rng, (B, S, W), jnp.float32)
    h0 = rand(rng, (B, W), jnp.float32)
    h, hl = ops.rglru_scan(a, b, h0, chunk=chunk, bw=bw)
    want_h, want_hl = ref.rglru_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(h), np.asarray(want_h),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(want_hl),
                               rtol=1e-5, atol=1e-5)


def test_rglru_no_h0(rng):
    a = jnp.asarray(rng.uniform(0.5, 0.99, (1, 32, 128)), jnp.float32)
    b = rand(rng, (1, 32, 128), jnp.float32)
    h, hl = ops.rglru_scan(a, b, chunk=16, bw=128)
    want_h, _ = ref.rglru_ref(a, b)
    np.testing.assert_allclose(np.asarray(h), np.asarray(want_h),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# WKV6
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,N,chunk", [
    (1, 32, 2, 16, 8),
    (2, 64, 4, 32, 16),
    (1, 48, 2, 64, 16),
])
def test_wkv6(rng, B, S, H, N, chunk):
    r = rand(rng, (B, S, H, N), jnp.float32) * 0.5
    k = rand(rng, (B, S, H, N), jnp.float32) * 0.5
    v = rand(rng, (B, S, H, N), jnp.float32)
    w = jnp.asarray(rng.uniform(0.05, 0.999, (B, S, H, N)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(H, N)) * 0.1, jnp.float32)
    st = rand(rng, (B, H, N, N), jnp.float32) * 0.1
    out, s_out = ops.wkv6(r, k, v, w, u, st, chunk=chunk)
    want, want_s = ref.wkv6_ref(r, k, v, w, u, st)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_out), np.asarray(want_s),
                               rtol=1e-4, atol=1e-4)


def test_wkv6_matches_model_chunked(rng):
    """The model's jnp chunked path == the sequential oracle too."""
    from repro.models.rwkv6 import wkv6_chunked
    B, S, H, N = 1, 64, 2, 16
    r = rand(rng, (B, S, H, N), jnp.float32) * 0.5
    k = rand(rng, (B, S, H, N), jnp.float32) * 0.5
    v = rand(rng, (B, S, H, N), jnp.float32)
    w = jnp.asarray(rng.uniform(0.05, 0.999, (B, S, H, N)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(H, N)) * 0.1, jnp.float32)
    out, s_out = wkv6_chunked(r, k, v, w, u, chunk=16)
    want, want_s = ref.wkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_out), np.asarray(want_s),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# take / dict gather
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("R,W,M", [(64, 128, 32), (128, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_take_rows(rng, R, W, M, dtype):
    vals = jnp.asarray(rng.integers(0, 100, (R, W)), dtype)
    idx = jnp.asarray(rng.integers(0, R, (M,)), jnp.int32)
    out = ops.take_rows(vals, idx)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.take_rows_ref(vals, idx)))


@pytest.mark.parametrize("R,W,M,bm", [(16, 128, 256, 64), (64, 128, 512, 256)])
def test_dict_decode(rng, R, W, M, bm):
    dic = jnp.asarray(rng.normal(size=(R, W)), jnp.float32)
    codes = jnp.asarray(rng.integers(0, R, (M,)), jnp.int32)
    out = ops.dict_decode(codes, dic, bm=bm)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.dict_decode_ref(codes, dic)),
        rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# gather edge cases: empty inputs, odd widths, domain validation
# ---------------------------------------------------------------------------

def test_take_rows_zero_indices(rng):
    vals = jnp.asarray(rng.normal(size=(9, 7)), jnp.float32)
    out = ops.take_rows(vals, jnp.asarray([], jnp.int32))
    assert out.shape == (0, 7) and out.dtype == vals.dtype


def test_take_rows_out_of_range_raises(rng):
    vals = jnp.asarray(rng.normal(size=(9, 7)), jnp.float32)
    with pytest.raises(IndexError, match="out of range"):
        ops.take_rows(vals, jnp.asarray([0, 9], jnp.int32))
    with pytest.raises(IndexError, match="out of range"):
        ops.take_rows(vals, jnp.asarray([-1], jnp.int32))


def test_dict_decode_zero_codes(rng):
    dic = jnp.asarray(rng.normal(size=(5, 4)), jnp.float32)
    out = ops.dict_decode(jnp.asarray([], jnp.int32), dic)
    assert out.shape == (0, 4) and out.dtype == dic.dtype


@pytest.mark.parametrize("M", [1, 7, 103, 257])
def test_dict_decode_length_not_block_multiple(rng, M):
    """Any code count works: the wrapper pads M up to the block size and
    slices the pad rows back off."""
    dic = jnp.asarray(rng.normal(size=(6, 8)), jnp.float32)
    codes = jnp.asarray(rng.integers(0, 6, (M,)), jnp.int32)
    out = ops.dict_decode(codes, dic, bm=64)
    assert out.shape == (M, 8)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(dic)[np.asarray(codes)])


def test_dict_decode_out_of_range_codes_raise(rng):
    """A code outside the dictionary raises instead of silently decoding
    garbage (the one-hot matmul would emit zero rows)."""
    dic = jnp.asarray(rng.normal(size=(5, 4)), jnp.float32)
    with pytest.raises(IndexError, match="out of range"):
        ops.dict_decode(jnp.asarray([0, 5], jnp.int32), dic)
    with pytest.raises(IndexError, match="out of range"):
        ops.dict_decode(jnp.asarray([-1], jnp.int32), dic)


# ---------------------------------------------------------------------------
# interpret-mode resolution: explicit env override beats device sniffing
# ---------------------------------------------------------------------------

def test_default_interpret_env_override(monkeypatch):
    monkeypatch.setenv("ZERROW_PALLAS_INTERPRET", "1")
    assert ops.default_interpret() is True
    monkeypatch.setenv("ZERROW_PALLAS_INTERPRET", "0")
    assert ops.default_interpret() is False
    monkeypatch.setenv("ZERROW_PALLAS_INTERPRET", "yes")
    with pytest.raises(ValueError, match="ZERROW_PALLAS_INTERPRET"):
        ops.default_interpret()
    # unset (and empty): interpret only on the CPU backend
    monkeypatch.setenv("ZERROW_PALLAS_INTERPRET", "")
    assert ops.default_interpret() == (jax.default_backend() == "cpu")
    monkeypatch.delenv("ZERROW_PALLAS_INTERPRET")
    assert ops.default_interpret() == (jax.default_backend() == "cpu")


def test_interpret_override_takes_effect_per_call(rng, monkeypatch):
    """The override is read at call time, not baked into a jit trace:
    flipping the env var between two calls of the *same* wrapper
    changes the lowering.  On a CPU backend, '0' (force compiled) must
    reach the kernel and fail with Pallas's interpret-only CPU error —
    a stale trace would silently keep interpreting."""
    if ops.on_tpu():
        pytest.skip("compiled mode is the normal path on TPU")
    vals = jnp.asarray(rng.integers(0, 100, (8, 16)), jnp.int32)
    idx = jnp.asarray(rng.integers(0, 8, (32,)), jnp.int32)
    monkeypatch.setenv("ZERROW_PALLAS_INTERPRET", "1")
    a = np.asarray(ops.take_rows(vals, idx))
    np.testing.assert_array_equal(a, np.asarray(vals)[np.asarray(idx)])
    monkeypatch.setenv("ZERROW_PALLAS_INTERPRET", "0")
    with pytest.raises(ValueError, match="[Ii]nterpret"):
        ops.take_rows(vals, idx)
    monkeypatch.setenv("ZERROW_PALLAS_INTERPRET", "1")
    np.testing.assert_array_equal(np.asarray(ops.take_rows(vals, idx)), a)


def test_on_tpu_matches_backend():
    assert ops.on_tpu() == (jax.default_backend() == "tpu")
