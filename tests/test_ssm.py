"""SSD (mamba2) correctness: chunked scan vs naive sequential recurrence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_arch
from repro.models.ssm import _ssd_chunked


def naive_ssd(xh, dt, A, Bc, Cc):
    """Sequential reference: h_t = h_{t-1}*exp(dt_t*A) + dt_t*B_t (x) x_t."""
    B, L, H, P = xh.shape
    N = Bc.shape[-1]
    h = np.zeros((B, H, P, N))
    ys = np.zeros((B, L, H, P))
    xh, dt, Bc, Cc = map(np.asarray, (xh, dt, Bc, Cc))
    A = np.asarray(A)
    for t in range(L):
        decay = np.exp(dt[:, t] * A[None, :])            # (B,H)
        dBx = np.einsum("bh,bn,bhp->bhpn", dt[:, t], Bc[:, t], xh[:, t])
        h = h * decay[..., None, None] + dBx
        ys[:, t] = np.einsum("bn,bhpn->bhp", Cc[:, t], h)
    return ys, h


def _rand(key, *shape):
    return jax.random.normal(key, shape, jnp.float32)


@pytest.mark.slow  # 15 random shapes -> 15 XLA compiles (~35 s)
@given(st.integers(1, 2), st.integers(3, 40), st.integers(1, 3),
       st.integers(2, 8), st.integers(2, 8), st.sampled_from([4, 8, 16]))
@settings(max_examples=15, deadline=None)
def test_chunked_matches_naive(B, L, H, P, N, chunk):
    cfg = dataclasses.replace(get_arch("mamba2-130m").reduced(),
                              ssm_chunk=chunk)
    k = jax.random.PRNGKey(B * 1000 + L * 10 + H)
    ks = jax.random.split(k, 5)
    xh = _rand(ks[0], B, L, H, P)
    dt = jax.nn.softplus(_rand(ks[1], B, L, H))
    A = -jnp.exp(_rand(ks[2], H) * 0.5)
    Bc = _rand(ks[3], B, L, N)
    Cc = _rand(ks[4], B, L, N)
    y, hT = _ssd_chunked(cfg, xh, dt, A, Bc, Cc)
    y_ref, h_ref = naive_ssd(xh, dt, A, Bc, Cc)
    np.testing.assert_allclose(np.asarray(y), y_ref, atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(hT), h_ref, atol=2e-4, rtol=1e-3)


def test_chunked_matches_naive_quick():
    """Tier-1 stand-in for the slow property: two fixed shapes, one with a
    ragged final chunk, one chunk-aligned."""
    inner = test_chunked_matches_naive.hypothesis.inner_test
    for B, L, H, P, N, chunk in [(1, 13, 2, 4, 3, 8), (2, 16, 1, 8, 4, 4)]:
        inner(B, L, H, P, N, chunk)


def test_final_state_feeds_decode():
    """Prefill final state == state after naive recurrence, so decode
    continues exactly (already covered end-to-end by test_decode)."""
    cfg = get_arch("mamba2-130m").reduced()
    k = jax.random.PRNGKey(0)
    ks = jax.random.split(k, 5)
    B, L, H, P, N = 1, 20, 2, 4, 4
    xh = _rand(ks[0], B, L, H, P)
    dt = jax.nn.softplus(_rand(ks[1], B, L, H))
    A = -jnp.exp(_rand(ks[2], H) * 0.5)
    Bc = _rand(ks[3], B, L, N)
    Cc = _rand(ks[4], B, L, N)
    _, hT = _ssd_chunked(cfg, xh, dt, A, Bc, Cc)
    _, h_ref = naive_ssd(xh, dt, A, Bc, Cc)
    np.testing.assert_allclose(np.asarray(hT), h_ref, atol=1e-4, rtol=1e-3)
