"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
executed with interpret=True on CPU (deliverable c)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import Stencil
from repro.kernels.attention.ops import flash_attention
from repro.kernels.attention.ref import attention_ref
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.stencil.ops import stencil_apply
from repro.kernels.stencil.ref import stencil_ref

DTYPES = [np.float32, jnp.bfloat16]


def tol(dtype):
    return 5e-2 if dtype == jnp.bfloat16 else 1e-5


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("hw", [(8, 16), (16, 128), (33, 40)])
@pytest.mark.parametrize("sname", ["nn", "hops", "comp"])
def test_stencil_kernel_sweep(dtype, hw, sname):
    H, W = hw
    st_obj = {"nn": Stencil.nearest_neighbor(2),
              "hops": Stencil.nn_with_hops(2),
              "comp": Stencil.component(2)}[sname]
    offsets = st_obj.offsets
    halo = int(np.abs(np.asarray(offsets)).max())
    weights = tuple(1.0 / st_obj.k for _ in range(st_obj.k))
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.standard_normal((H + 2 * halo, W + 2 * halo)),
                    dtype=dtype)
    out = stencil_apply(u, offsets, weights, halo=halo, interpret=True)
    ref = stencil_ref(u, offsets, weights, halo=halo)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("hw,tile_rows", [((33, 40), 8), ((64, 24), 16),
                                          ((50, 16), 32)])
@pytest.mark.parametrize("sname", ["nn", "hops"])
def test_stencil_kernel_row_panels(dtype, hw, tile_rows, sname):
    """Several row panels per shard, each reading its halo rows from the
    next panel's tail block, and a partial last panel — the tiling the
    chip runs at 2048^2, at interpretable sizes."""
    from repro.kernels.stencil.stencil import stencil_pallas
    H, W = hw
    st_obj = (Stencil.nearest_neighbor(2) if sname == "nn"
              else Stencil.nn_with_hops(2))
    offsets = st_obj.offsets
    halo = int(np.abs(np.asarray(offsets)).max())
    weights = tuple(1.0 / st_obj.k for _ in range(st_obj.k))
    rng = np.random.default_rng(H * W)
    u = jnp.asarray(rng.standard_normal((H + 2 * halo, W + 2 * halo)),
                    dtype=dtype)
    out = stencil_pallas(u, offsets, weights, halo, tile_rows=tile_rows,
                         interpret=True)
    ref = stencil_ref(u, offsets, weights, halo=halo)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol(dtype))


@given(st.integers(1, 3), st.integers(1, 33), st.sampled_from([128, 256, 384]))
@settings(max_examples=12, deadline=None)
def test_rmsnorm_kernel_property(b, rows, d):
    rng = np.random.default_rng(rows * d)
    x = jnp.asarray(rng.standard_normal((b, rows, d)), dtype=jnp.float32)
    w = jnp.asarray(rng.standard_normal((d,)), dtype=jnp.float32)
    out = rmsnorm(x, w, interpret=True)
    ref = rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("dtype", DTYPES)
def test_rmsnorm_dtypes(dtype):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((4, 64, 256)), dtype=dtype)
    w = jnp.asarray(rng.standard_normal((256,)), dtype=dtype)
    out = rmsnorm(x, w, interpret=True)
    ref = rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol(dtype))


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("Sq,Sk,window", [(64, 64, None), (128, 128, 32),
                                          (64, 128, None), (96, 96, None)])
def test_flash_attention_sweep(dtype, Sq, Sk, window):
    B, H, K, D = 1, 4, 2, 32
    rng = np.random.default_rng(Sq + Sk)
    q = jnp.asarray(rng.standard_normal((B, Sq, H, D)), dtype=dtype)
    k = jnp.asarray(rng.standard_normal((B, Sk, K, D)), dtype=dtype)
    v = jnp.asarray(rng.standard_normal((B, Sk, K, D)), dtype=dtype)
    causal = Sq == Sk
    out = flash_attention(q, k, v, causal=causal, window=window,
                          interpret=True)
    ref = flash_attention(q, k, v, causal=causal, window=window,
                          use_pallas=False)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol(dtype) * 2)


def test_flash_matches_model_blocked_sdpa():
    """The Pallas kernel and the model's jnp double-scan agree."""
    from repro.models.attention import _blocked_sdpa
    B, S, K, G, D = 1, 256, 2, 2, 16
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((B, S, K, G, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, K, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, K, D)), jnp.float32)
    pos = jnp.arange(S)
    out_model = _blocked_sdpa(q, k, v, pos, pos, True, None,
                              1.0 / np.sqrt(D), q_block=64, kv_block=64)
    out_kernel = flash_attention(q.reshape(B, S, K * G, D), k, v,
                                 causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out_model).reshape(B, S, K * G, D),
                               np.asarray(out_kernel), atol=1e-4)


def test_model_level_pallas_attention_flag():
    """cfg.use_pallas_attention routes model attention through the Pallas
    kernel (interpret on CPU) and matches the jnp path end to end."""
    import dataclasses
    import jax
    from repro.configs import get_arch
    from repro.models import lm
    cfg = get_arch("qwen3-8b").reduced()
    cfgp = dataclasses.replace(cfg, use_pallas_attention=True)
    key = jax.random.PRNGKey(0)
    params = lm.init(cfg, key)
    toks = jax.random.randint(key, (2, 16), 0, cfg.vocab)
    batch = {"inputs": toks, "targets": toks}
    l0, _, _ = lm.forward(cfg, params, batch)
    l1, _, _ = lm.forward(cfgp, params, batch)
    np.testing.assert_allclose(np.asarray(l0, np.float32),
                               np.asarray(l1, np.float32), atol=2e-3)


@pytest.mark.parametrize("dims", [(4, 8, 16), (6, 12, 20)])
@pytest.mark.parametrize("sname", ["nn3", "hops3"])
def test_stencil3d_kernel(dims, sname):
    from repro.kernels.stencil.ref import stencil3d_ref
    from repro.kernels.stencil.stencil import stencil3d_pallas
    st_obj = (Stencil.nearest_neighbor(3) if sname == "nn3"
              else Stencil.nn_with_hops(3, hops=(2,)))
    offsets = st_obj.offsets
    halo = int(np.abs(np.asarray(offsets)).max())
    weights = tuple(1.0 / st_obj.k for _ in range(st_obj.k))
    rng = np.random.default_rng(1)
    D, H, W = dims
    u = jnp.asarray(rng.standard_normal((D + 2 * halo, H + 2 * halo,
                                         W + 2 * halo)), jnp.float32)
    out = stencil3d_pallas(u, offsets, weights, halo, interpret=True)
    ref = stencil3d_ref(u, offsets, weights, halo)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
