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


# Lengths of the edge cases of `test_paged_attention`, keyed by the case;
# the other cases draw theirs. At page 16 a grid step covers up to 64
# pages, 1024 tokens, and 32 at phi3-medium's widths
# (`kernel.pages_per_block`).
EDGE_LENS = {
    (4, 16, 8, 64, 16, 20, 96): (0, 320, 0, 320),    # empty next to full
    (6, 16, 8, 64, 16, 80, 128): (1, 16, 17, 1023, 1024, 1025),  # page,
    #                                    block edges; 80 pages = 1.25 blocks
    (3, 16, 8, 64, 16, 5, 16): (80, 1, 33),          # table under one block
    (4, 16, 8, 64, 16, 20, 12): (320, 200, 77, 319),  # rows share pages
    (2, 8, 8, 64, 16, 20, 48): (300, 40),            # G = 1
    (2, 16, 4, 128, 16, 20, 48): (300, 40),          # G = 4, D = 128
    (2, 16, 2, 128, 16, 80, 48): (1100, 513),        # G = 8, two blocks
    (3, 40, 10, 128, 16, 40, 64): (600, 0, 512),     # phi3-medium widths
}


@pytest.mark.parametrize("B,Hq,Hkv,D,page,maxp,P", [
    (2, 4, 2, 64, 8, 4, 16),
    (3, 8, 1, 128, 16, 3, 64),
    (1, 4, 4, 64, 8, 6, 12),
    (4, 8, 2, 64, 8, 5, 64),
    (2, 16, 8, 64, 16, 4, 32),           # granite-moe-1b-a400m widths
    *EDGE_LENS,
])
def test_paged_attention(B, Hq, Hkv, D, page, maxp, P):
    """Head-major pools (Hkv, P, page, D): the kernel in interpret mode
    against the gather-then-dense reference. A pool smaller than the
    tables makes rows share pages."""
    edge = EDGE_LENS.get((B, Hq, Hkv, D, page, maxp, P))
    rng = RNG if edge is None else np.random.default_rng(B * maxp + P)
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.bfloat16)
    kp, vp = (jnp.asarray(rng.normal(size=(Hkv, P, page, D)), jnp.bfloat16)
              for _ in range(2))
    bt = jnp.asarray(rng.choice(P, size=(B, maxp),
                                replace=B * maxp > P), jnp.int32)
    sl = jnp.asarray(rng.integers(1, page * maxp + 1, size=(B,))
                     if edge is None else edge, jnp.int32)
    out = paged_attention(q, kp, vp, bt, sl, use_kernel=True, interpret=True)
    ref = paged_attention_ref(q, kp, vp, bt, sl)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=0.06)


def test_step_pages_hold_the_last_live_page():
    """The page each pool operand holds at each grid step: the table's
    where the page is live, else what the operand held the step before,
    so the pipeline copies nothing at or past a row's length."""
    from repro.kernels.paged_attention.kernel import step_pages
    page, ppb = 4, 2
    bt = np.arange(100, 115).reshape(3, 5)
    lens = (9, 0, 4)
    held, want = [bt[0, 0]] * ppb, []
    for b in range(3):
        for i in range(3):                  # ceil(5 pages / 2 a block)
            for j in range(ppb):
                p = i * ppb + j
                if p < 5 and p * page < lens[b]:
                    held[j] = bt[b, p]
                want.append(held[j])
    got = step_pages(jnp.asarray(bt, jnp.int32), jnp.asarray(lens, jnp.int32),
                     page, ppb)
    np.testing.assert_array_equal(np.asarray(got).ravel(), want)


def test_paged_attention_is_one_named_call():
    """All of the kernel's work is one `pallas_call` named
    `paged_attention` (the name the roofline reads in a trace): nothing
    but reshapes follows it."""
    from repro.kernels.paged_attention.kernel import paged_attention_kernel
    B, Hq, Hkv, D, page, maxp = 4, 16, 8, 64, 16, 20
    args = (jax.ShapeDtypeStruct((B, Hq, D), jnp.bfloat16),
            *[jax.ShapeDtypeStruct((Hkv, 64, page, D), jnp.bfloat16)] * 2,
            jax.ShapeDtypeStruct((B, maxp), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32))
    (jitted,) = jax.make_jaxpr(paged_attention_kernel)(*args).jaxpr.eqns
    inner = jitted.params["jaxpr"].jaxpr.eqns
    eqns = [e.primitive.name for e in inner]
    assert eqns.count("pallas_call") == 1
    call = inner[eqns.index("pallas_call")]
    assert call.params["name"] == "paged_attention"
    assert set(eqns[eqns.index("pallas_call") + 1:]) <= {"reshape"}


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
