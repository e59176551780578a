"""Per-kernel allclose sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.int8_matmul.ops import int8_matmul
from repro.kernels.int8_matmul.ref import int8_matmul_ref
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.ssm_scan.ops import ssm_scan
from repro.kernels.ssm_scan.ref import ssm_scan_ref

RNG = np.random.default_rng(0)


def _randn(shape, dtype=jnp.bfloat16):
    return jnp.asarray(RNG.normal(size=shape), dtype)


@pytest.mark.parametrize("B,Sq,Sk,Hq,Hkv,D,causal", [
    (2, 256, 256, 4, 2, 64, True),
    (1, 128, 256, 8, 8, 128, False),
    (2, 256, 256, 4, 1, 64, True),       # MQA
    (1, 512, 512, 2, 2, 32, True),
])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention(B, Sq, Sk, Hq, Hkv, D, causal, dtype):
    q, k, v = (_randn((B, Sq, Hq, D), dtype), _randn((B, Sk, Hkv, D), dtype),
               _randn((B, Sk, Hkv, D), dtype))
    out = flash_attention(q, k, v, causal=causal, use_kernel=True,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal)
    tol = 0.06 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


@pytest.mark.parametrize("B,Hq,Hkv,D,page,maxp,P", [
    (2, 4, 2, 64, 8, 4, 16),
    (3, 8, 1, 128, 16, 3, 64),
    (1, 4, 4, 64, 8, 6, 12),
    (4, 8, 2, 64, 8, 5, 64),
    (2, 16, 8, 64, 16, 4, 32),           # granite-moe-1b-a400m widths
])
def test_paged_attention(B, Hq, Hkv, D, page, maxp, P):
    """Head-major pools (Hkv, P, page, D): the kernel in interpret mode
    against the gather-then-dense reference."""
    q = _randn((B, Hq, D))
    kp, vp = _randn((Hkv, P, page, D)), _randn((Hkv, P, page, D))
    bt = jnp.asarray(RNG.choice(P, size=(B, maxp),
                                replace=B * maxp > P), jnp.int32)
    sl = jnp.asarray(RNG.integers(1, page * maxp + 1, size=(B,)), jnp.int32)
    out = paged_attention(q, kp, vp, bt, sl, use_kernel=True, interpret=True)
    ref = paged_attention_ref(q, kp, vp, bt, sl)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=0.06)


@pytest.mark.parametrize("M,K,N", [(256, 256, 256), (512, 512, 256),
                                   (256, 1024, 512)])
def test_int8_matmul(M, K, N):
    xq = jnp.asarray(RNG.integers(-127, 128, (M, K)), jnp.int8)
    wq = jnp.asarray(RNG.integers(-127, 128, (K, N)), jnp.int8)
    xs = jnp.asarray([0.013], jnp.float32)
    ws = jnp.asarray(RNG.uniform(0.001, 0.02, (1, N)), jnp.float32)
    out = int8_matmul(xq, wq, xs, ws, use_kernel=True, interpret=True)
    ref = int8_matmul_ref(xq, wq, xs, ws)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)


@pytest.mark.parametrize("B,S,di,N,chunk,bdi", [
    (2, 512, 512, 16, 128, 256),
    (1, 256, 1024, 8, 256, 512),
    (3, 512, 512, 4, 64, 512),
])
def test_ssm_scan(B, S, di, N, chunk, bdi):
    u = jnp.asarray(RNG.normal(size=(B, S, di)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (B, S, di)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(B, S, N)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, (di, N)), jnp.float32)
    D = jnp.asarray(RNG.normal(size=(di,)), jnp.float32)
    h0 = jnp.asarray(RNG.normal(size=(B, di, N)), jnp.float32)
    y, h = ssm_scan(u, dt, Bm, Cm, A, D, h0, use_kernel=True,
                    interpret=True, chunk=chunk, block_di=bdi)
    yr, hr = ssm_scan_ref(u, dt, Bm, Cm, A, D, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-4)


def test_ssm_scan_state_continuity():
    """Scanning two halves with carried state == one full scan."""
    B, S, di, N = 1, 256, 256, 8
    u = jnp.asarray(RNG.normal(size=(B, S, di)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (B, S, di)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(B, S, N)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, (di, N)), jnp.float32)
    D = jnp.asarray(RNG.normal(size=(di,)), jnp.float32)
    y_full, h_full = ssm_scan_ref(u, dt, Bm, Cm, A, D)
    half = S // 2
    y1, h1 = ssm_scan_ref(u[:, :half], dt[:, :half], Bm[:, :half],
                          Cm[:, :half], A, D)
    y2, h2 = ssm_scan_ref(u[:, half:], dt[:, half:], Bm[:, half:],
                          Cm[:, half:], A, D, h0=h1)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full), atol=1e-4)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y_full[:, half:]),
                               atol=1e-4)
