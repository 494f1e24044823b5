"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.csr_spmm import csr_spmm_pallas
from repro.kernels.edge_softmax import edge_softmax_agg_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.gqa_decode import gqa_decode_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


def test_interpret_mode_only_on_the_cpu_backend(monkeypatch):
    """Pallas compiles natively on TPU and is interpreted on the CPU oracle;
    any other backend is refused instead of silently interpreted."""
    for backend, interpret in (("tpu", False), ("cpu", True)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert ops._interpret() is interpret
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        ops._interpret()


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,deg,h", [(64, 4, 32), (200, 12, 96), (257, 7, 130), (128, 24, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_csr_spmm(n, deg, h, dtype):
    x = jnp.asarray(RNG.normal(size=(n, h)), dtype)
    idx = jnp.asarray(RNG.integers(0, n, (n, deg)), jnp.int32)
    w = jnp.asarray(RNG.uniform(0, 1, (n, deg)) * (RNG.uniform(size=(n, deg)) < 0.7),
                    jnp.float32)
    out = csr_spmm_pallas(x, idx, w, interpret=True)
    ref = ops.csr_spmm_ref(x, idx, w)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               **_tol(dtype))


@pytest.mark.parametrize("n,deg,h", [(64, 6, 32), (150, 16, 64), (96, 3, 128)])
def test_edge_softmax(n, deg, h):
    z = jnp.asarray(RNG.normal(size=(n, h)), jnp.float32)
    ss = jnp.asarray(RNG.normal(size=n), jnp.float32)
    sd = jnp.asarray(RNG.normal(size=n), jnp.float32)
    idx = jnp.asarray(RNG.integers(0, n, (n, deg)), jnp.int32)
    mask = jnp.asarray((RNG.uniform(size=(n, deg)) < 0.6).astype(np.float32))
    bias = jnp.asarray(RNG.normal(size=(n, deg)) * 0.1, jnp.float32)
    out = edge_softmax_agg_pallas(z, ss, sd, idx, mask, bias, interpret=True)
    ref = ops.edge_softmax_agg_ref(z, ss, sd, idx, mask, bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (6, 1)])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 96), (False, None)])
def test_flash_attention(hq, hkv, causal, window):
    b, s, dh = 2, 256, 64
    q = jnp.asarray(RNG.normal(size=(b, hq, s, dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, hkv, s, dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, hkv, s, dh)), jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 block_q=64, block_k=64, interpret=True)
    ref = ops.mha_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    b, hq, hkv, s, dh = 1, 4, 2, 128, 64
    q = jnp.asarray(RNG.normal(size=(b, hq, s, dh)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, hkv, s, dh)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, hkv, s, dh)), dtype)
    out = flash_attention_pallas(q, k, v, block_q=64, block_k=64, interpret=True)
    ref = ops.mha_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               **_tol(dtype))


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("hq,hkv,s", [(8, 2, 640), (4, 4, 256), (16, 2, 1024)])
@pytest.mark.parametrize("window", [None, 128])
def test_gqa_decode(hq, hkv, s, window):
    b, dh = 3, 64
    q = jnp.asarray(RNG.normal(size=(b, hq, dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, hkv, s, dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, hkv, s, dh)), jnp.float32)
    kl = jnp.asarray(RNG.integers(1, s + 1, b), jnp.int32)
    out = gqa_decode_pallas(q, k, v, kv_len=kl, window=window, block_k=128,
                            interpret=True)
    ref = ops.gqa_decode_ref(q, k, v, kv_len=kl, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("s,chunk", [(128, 64), (256, 128), (512, 128)])
@pytest.mark.parametrize("h,p,n", [(4, 64, 32), (2, 32, 64)])
def test_ssd_scan(s, chunk, h, p, n):
    b = 2
    x = jnp.asarray(RNG.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (b, s, h)), jnp.float32)
    a = jnp.asarray(-RNG.uniform(0.5, 2.0, h), jnp.float32)
    bb = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)
    cc = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)
    dd = jnp.asarray(RNG.normal(size=h), jnp.float32)
    out = ssd_scan_pallas(x, dt, a, bb, cc, dd, chunk=chunk, interpret=True)
    ref = ops.ssd_scan_ref(x, dt, a, bb, cc, dd)
    scale = float(jnp.abs(ref).max())
    np.testing.assert_allclose(np.asarray(out) / scale, np.asarray(ref) / scale,
                               atol=3e-5)


def test_ssd_chunked_ref_matches_sequential():
    b, s, h, p, n = 2, 192, 3, 16, 24
    x = jnp.asarray(RNG.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.3, (b, s, h)), jnp.float32)
    a = jnp.asarray(-RNG.uniform(0.2, 3.0, h), jnp.float32)
    bb = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)
    cc = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)
    out = ops.ssd_chunked_ref(x, dt, a, bb, cc, chunk=64)
    ref = ops.ssd_scan_ref(x, dt, a, bb, cc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-3)
