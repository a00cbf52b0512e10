"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle,
shape/dtype sweeps + hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from numpy.testing import assert_allclose

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.decode_attention import ops as da_ops
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.grouped_gemm import ops as gg_ops
from repro.kernels.grouped_gemm.ref import grouped_gemm_ref, moe_ffn_ref
from repro.kernels.ssm_scan import ops as ssm_ops
from repro.kernels.ssm_scan.ref import ssm_scan_ref
from repro.kernels.rglru_scan import ops as lru_ops
from repro.kernels.rglru_scan.ref import rglru_scan_ref

jax.config.update("jax_platform_name", "cpu")


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


def rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ------------------------------------------------------------ flash attn ----
@pytest.mark.parametrize("B,S,H,K,hd", [
    (1, 128, 4, 2, 32),
    (2, 256, 4, 4, 64),
    (1, 96, 2, 1, 16),      # padding path (96 < block)
    (1, 160, 8, 2, 32),     # padding path (160 % 128 != 0)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(B, S, H, K, hd, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = rand(ks[0], (B, S, H, hd), dtype)
    k = rand(ks[1], (B, S, K, hd), dtype)
    v = rand(ks[2], (B, S, K, hd), dtype)
    out = fa_ops.flash_attention(q, k, v, causal=True, block_q=64,
                                 block_k=64, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True)
    assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                    **tol(dtype))


@pytest.mark.parametrize("window", [0, 64, 33])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_masks(window, causal):
    if not causal and window > 0:
        pytest.skip("windowed non-causal unused")
    ks = jax.random.split(jax.random.key(1), 3)
    B, S, H, K, hd = 1, 192, 4, 2, 32
    q, k, v = (rand(ks[i], (B, S, (H if i == 0 else K), hd)) for i in range(3))
    out = fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                 block_q=64, block_k=64, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_attention_softcap():
    ks = jax.random.split(jax.random.key(2), 3)
    B, S, H, K, hd = 1, 128, 2, 2, 32
    q, k, v = (rand(ks[i], (B, S, (H if i == 0 else K), hd), scale=3.0)
               for i in range(3))
    out = fa_ops.flash_attention(q, k, v, causal=True, softcap=20.0,
                                 block_q=64, block_k=64, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True, softcap=20.0)
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@settings(max_examples=6, deadline=None)
@given(s=st.integers(17, 96), h=st.sampled_from([2, 4]),
       g=st.sampled_from([1, 2]))
def test_flash_attention_property(s, h, g):
    # s up to 96 crosses the 64-wide block boundary with ragged padding,
    # which is all the padding-correctness property needs; each distinct
    # shape is a fresh interpret-mode compile, so examples are the budget
    """Property: kernel == oracle for arbitrary lengths (padding correct)."""
    ks = jax.random.split(jax.random.key(s * 7 + h), 3)
    hd, K = 16, h // g if h % g == 0 else 1
    K = max(1, h // (g if h % g == 0 else 1))
    q = rand(ks[0], (1, s, h, hd))
    k = rand(ks[1], (1, s, K, hd)) if h % K == 0 else None
    if k is None:
        return
    v = rand(ks[2], (1, s, K, hd))
    out = fa_ops.flash_attention(q, k, v, causal=True, block_q=64,
                                 block_k=64, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True)
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-5, atol=3e-5)


# ----------------------------------------------------------- decode attn ----
@pytest.mark.parametrize("W,pos", [(64, 5), (64, 63), (100, 31), (64, 200)])
@pytest.mark.parametrize("H,K", [(8, 2), (4, 4), (10, 1)])
def test_decode_attention(W, pos, H, K):
    ks = jax.random.split(jax.random.key(3), 3)
    B, hd = 2, 32
    q = rand(ks[0], (B, H, hd))
    k = rand(ks[1], (B, W, K, hd))
    v = rand(ks[2], (B, W, K, hd))
    out = da_ops.decode_attention(q, k, v, pos=jnp.int32(pos), window=W,
                                  block_k=32, interpret=True)
    ref = decode_attention_ref(q, k, v, pos=pos, window=W)
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("W", [64, 100])
def test_decode_attention_reads_a_layer_of_a_stack(W):
    """Given a stack of layers' caches and a layer index, the kernel reads
    that layer in place: the output of the kernel on the layer's slice
    (a ragged W, padded, included)."""
    ks = jax.random.split(jax.random.key(5), 3)
    n, B, H, K, hd = 3, 2, 8, 2, 32
    q = rand(ks[0], (B, H, hd))
    k = rand(ks[1], (n, B, W, K, hd))
    v = rand(ks[2], (n, B, W, K, hd))
    for layer in range(n):
        out = da_ops.decode_attention(q, k, v, pos=jnp.int32(W // 2),
                                      window=W, layer=jnp.int32(layer),
                                      block_k=32, interpret=True)
        one = da_ops.decode_attention(q, k[layer], v[layer],
                                      pos=jnp.int32(W // 2), window=W,
                                      block_k=32, interpret=True)
        assert_allclose(np.asarray(out), np.asarray(one), rtol=0, atol=0)


# ----------------------------------------------------------- grouped gemm ---
@pytest.mark.parametrize("E,M,K,N", [
    (4, 128, 64, 128), (3, 50, 33, 17), (1, 8, 8, 8), (8, 256, 128, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_gemm(E, M, K, N, dtype):
    ks = jax.random.split(jax.random.key(4), 2)
    x = rand(ks[0], (E, M, K), dtype)
    w = rand(ks[1], (E, K, N), dtype)
    out = gg_ops.grouped_gemm(x, w, block_m=32, block_n=32, block_k=32,
                              interpret=True)
    ref = grouped_gemm_ref(x, w)
    assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                    **tol(dtype))


def test_moe_ffn_composed():
    ks = jax.random.split(jax.random.key(5), 4)
    E, C, D, F = 4, 64, 32, 48
    disp = rand(ks[0], (E, C, D))
    wg, wu = rand(ks[1], (E, D, F)), rand(ks[2], (E, D, F))
    wd = rand(ks[3], (E, F, D))
    out = gg_ops.moe_ffn(disp, wg, wu, wd, interpret=True)
    ref = moe_ffn_ref(disp, wg, wu, wd)
    assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)


# -------------------------------------------------------------- ssm scan ----
@pytest.mark.parametrize("B,S,Din,N", [(2, 64, 32, 8), (1, 100, 48, 4)])
def test_ssm_scan(B, S, Din, N):
    ks = jax.random.split(jax.random.key(6), 5)
    dt = jax.nn.softplus(rand(ks[0], (B, S, Din)))
    A = -jnp.exp(rand(ks[1], (Din, N)) * 0.5)
    B_ = rand(ks[2], (B, S, N))
    C_ = rand(ks[3], (B, S, N))
    x = rand(ks[4], (B, S, Din))
    y, h = ssm_ops.ssm_scan(dt, A, B_, C_, x, block_d=16, chunk=16,
                            interpret=True)
    yr, hr = ssm_scan_ref(dt, A, B_, C_, x)
    assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-4, atol=1e-4)
    assert_allclose(np.asarray(h), np.asarray(hr), rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------- rglru scan ---
@pytest.mark.parametrize("B,S,W", [(2, 64, 32), (1, 96, 64)])
def test_rglru_scan(B, S, W):
    ks = jax.random.split(jax.random.key(7), 3)
    a = jax.nn.sigmoid(rand(ks[0], (B, S, W)))  # decay in (0,1)
    b = rand(ks[1], (B, S, W))
    h0 = rand(ks[2], (B, W))
    y, h = lru_ops.rglru_scan(a, b, h0, block_w=16, chunk=16, interpret=True)
    yr, hr = rglru_scan_ref(a, b, h0)
    assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-5, atol=1e-5)
    assert_allclose(np.asarray(h), np.asarray(hr), rtol=1e-5, atol=1e-5)


@settings(max_examples=8, deadline=None)
@given(s1=st.integers(8, 24), s2=st.integers(8, 24))
def test_rglru_scan_chaining_property(s1, s2):
    # 8..24 crosses the chunk=8 boundary at ragged offsets — the whole
    # chaining contract — at a fraction of the CI cost of long sequences
    """Property: scanning [a1;a2] == scan(a2) seeded with scan(a1) state.
    (The decode/prefill continuation contract.)"""
    ks = jax.random.split(jax.random.key(s1 * 100 + s2), 3)
    B, W = 1, 16
    a = jax.nn.sigmoid(rand(ks[0], (B, s1 + s2, W)))
    b = rand(ks[1], (B, s1 + s2, W))
    h0 = rand(ks[2], (B, W))
    y_all, h_all = lru_ops.rglru_scan(a, b, h0, block_w=16, chunk=8,
                                      interpret=True)
    y1, h1 = lru_ops.rglru_scan(a[:, :s1], b[:, :s1], h0, block_w=16,
                                chunk=8, interpret=True)
    y2, h2 = lru_ops.rglru_scan(a[:, s1:], b[:, s1:], h1, block_w=16,
                                chunk=8, interpret=True)
    assert_allclose(np.asarray(h_all), np.asarray(h2), rtol=1e-5, atol=1e-5)
    assert_allclose(np.asarray(y_all[:, s1:]), np.asarray(y2),
                    rtol=1e-5, atol=1e-5)


# ---------------------------------------------- model-level kernel parity ---
def test_attention_impl_parity():
    """attention_apply(pallas_interpret) == attention_apply(xla)."""
    from repro.configs import get_smoke_config
    from repro.models import attention as attn
    cfg = get_smoke_config("glm4-9b")
    key = jax.random.key(8)
    p = attn.init_attention(key, cfg)
    x = rand(jax.random.key(9), (2, 32, cfg.d_model), jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(32, dtype=jnp.int32), (2, 32))
    y_ref = attn.attention_apply(p, cfg, x, pos, impl="xla")
    y_pal = attn.attention_apply(p, cfg, x, pos, impl="pallas_interpret")
    assert_allclose(np.asarray(y_pal, np.float32),
                    np.asarray(y_ref, np.float32), rtol=3e-2, atol=3e-2)


def test_mamba_impl_parity():
    from repro.configs import get_smoke_config
    from repro.models import ssm as ssm_mod
    cfg = get_smoke_config("falcon-mamba-7b")
    p = ssm_mod.init_mamba(jax.random.key(10), cfg)
    x = rand(jax.random.key(11), (2, 32, cfg.d_model), jnp.bfloat16)
    y_ref = ssm_mod.mamba_apply(p, cfg, x, impl="xla")
    y_pal = ssm_mod.mamba_apply(p, cfg, x, impl="pallas_interpret")
    assert_allclose(np.asarray(y_pal, np.float32),
                    np.asarray(y_ref, np.float32), rtol=3e-2, atol=3e-2)


def test_rglru_impl_parity():
    from repro.configs import get_smoke_config
    from repro.models import recurrent as rec
    cfg = get_smoke_config("recurrentgemma-2b")
    p = rec.init_rglru(jax.random.key(12), cfg)
    x = rand(jax.random.key(13), (2, 32, cfg.d_model), jnp.bfloat16)
    y_ref = rec.rglru_apply(p, cfg, x, impl="xla")
    y_pal = rec.rglru_apply(p, cfg, x, impl="pallas_interpret")
    assert_allclose(np.asarray(y_pal, np.float32),
                    np.asarray(y_ref, np.float32), rtol=3e-2, atol=3e-2)
