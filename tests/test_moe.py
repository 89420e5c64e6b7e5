"""MoE layer: dispatch engines agree, capacity drops, aux loss behavior."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_arch
from repro.models.moe import _capacity, moe_apply, moe_specs
from repro.models.common import init_params


def _setup(cfg, key, B=2, S=16):
    params = init_params(moe_specs(cfg, jnp.float32), key)
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, S, cfg.d_model))
    return params, x


def test_dispatch_engines_agree():
    cfg = dataclasses.replace(get_arch("mixtral-8x7b").reduced(),
                              capacity_factor=8.0)  # no drops
    params, x = _setup(cfg, jax.random.PRNGKey(0))
    y1, a1 = moe_apply(cfg, params, x, dispatch="einsum")
    y2, a2 = moe_apply(cfg, params, x, dispatch="scatter")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(float(a1), float(a2), rtol=1e-5)


@pytest.mark.slow  # 10 random shapes -> 10 XLA compiles (~18 s)
@given(st.integers(1, 3), st.integers(4, 32), st.sampled_from(["einsum", "scatter"]))
@settings(max_examples=10, deadline=None)
def test_moe_output_finite(B, S, dispatch):
    cfg = get_arch("mixtral-8x7b").reduced()
    params, x = _setup(cfg, jax.random.PRNGKey(B * 100 + S), B, S)
    y, aux = moe_apply(cfg, params, x, dispatch=dispatch)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    assert float(aux) >= 0.0


def test_capacity_formula():
    cfg = get_arch("mixtral-8x7b")  # E=8, k=2, cf=1.25
    c = _capacity(cfg, 4096)
    assert c == 1280
    assert _capacity(cfg, 1) == 4  # floor of 4, rounded to multiple of 4


def test_capacity_drops_tokens():
    """With tiny capacity, outputs of dropped tokens are zero (+shared)."""
    cfg = dataclasses.replace(get_arch("mixtral-8x7b").reduced(),
                              capacity_factor=0.02)
    params, x = _setup(cfg, jax.random.PRNGKey(2), 1, 64)
    y, _ = moe_apply(cfg, params, x, dispatch="einsum")
    # most rows should be exactly 0 (dropped; mixtral has no shared expert)
    norms = np.linalg.norm(np.asarray(y[0]), axis=-1)
    assert (norms == 0).sum() > 32


def test_shared_expert_always_active():
    cfg = dataclasses.replace(get_arch("deepseek-v3-671b").reduced(),
                              capacity_factor=0.02)
    params, x = _setup(cfg, jax.random.PRNGKey(3), 1, 64)
    y, _ = moe_apply(cfg, params, x, dispatch="einsum")
    norms = np.linalg.norm(np.asarray(y[0]), axis=-1)
    assert (norms > 0).all()  # shared expert output survives drops


def test_gradients_flow_through_both_dispatches():
    cfg = dataclasses.replace(get_arch("mixtral-8x7b").reduced(),
                              capacity_factor=8.0)
    params, x = _setup(cfg, jax.random.PRNGKey(4))
    for dispatch in ("einsum", "scatter"):
        g = jax.grad(lambda p: jnp.sum(
            moe_apply(cfg, p, x, dispatch=dispatch)[0] ** 2))(params)
        gn = sum(float(jnp.sum(v ** 2)) for v in g.values())
        assert np.isfinite(gn) and gn > 0, dispatch
