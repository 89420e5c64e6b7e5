"""Optimizer: AdamW math, quantized state, clipping, schedules, compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.optim import (AdamWConfig, adamw_update, dequantize_blockwise,
                         ef_compress, ef_decompress, init_error_state,
                         init_opt_state, quantize_blockwise)
from repro.sharding.partition import ParamSpec


def _specs():
    return {"w": ParamSpec((8, 16), jnp.float32, (None, None)),
            "b": ParamSpec((16,), jnp.float32, (None,))}


def _params(key):
    specs = _specs()
    return {k: jax.random.normal(jax.random.fold_in(key, i), v.shape)
            for i, (k, v) in enumerate(sorted(specs.items()))}


def test_adamw_first_step_matches_reference():
    cfg = AdamWConfig(lr=0.1, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0,
                      clip_norm=None, schedule="constant")
    params = _params(jax.random.PRNGKey(0))
    grads = {k: jnp.ones_like(v) for k, v in params.items()}
    state = init_opt_state(_specs(), cfg)
    p1, s1, _ = adamw_update(params, grads, state, cfg)
    # bias-corrected first step of Adam with g=1 everywhere: update = lr
    for k in params:
        np.testing.assert_allclose(np.asarray(params[k] - p1[k]),
                                   0.1, rtol=1e-5)


def test_weight_decay_only_on_matrices():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.5, clip_norm=None,
                      schedule="constant")
    params = _params(jax.random.PRNGKey(1))
    grads = {k: jnp.zeros_like(v) for k, v in params.items()}
    state = init_opt_state(_specs(), cfg)
    p1, _, _ = adamw_update(params, grads, state, cfg)
    # 1-d bias: no decay, zero grad -> unchanged
    np.testing.assert_allclose(np.asarray(p1["b"]), np.asarray(params["b"]))
    assert not np.allclose(np.asarray(p1["w"]), np.asarray(params["w"]))


def test_clipping_bounds_update():
    cfg = AdamWConfig(lr=1.0, clip_norm=1e-3, schedule="constant",
                      weight_decay=0.0)
    params = _params(jax.random.PRNGKey(2))
    grads = {k: 1e6 * jnp.ones_like(v) for k, v in params.items()}
    state = init_opt_state(_specs(), cfg)
    _, _, metrics = adamw_update(params, grads, state, cfg)
    assert float(metrics["grad_norm"]) > 1e5  # reported pre-clip


@given(st.integers(1, 4), st.sampled_from([16, 100, 128, 300]))
@settings(max_examples=20, deadline=None)
def test_quantize_roundtrip_error_bound(rows, d):
    rng = np.random.default_rng(rows * d)
    x = jnp.asarray(rng.standard_normal((rows, d)) * 3.0, jnp.float32)
    q, s = quantize_blockwise(x)
    deq = dequantize_blockwise(q, s, d)
    # absmax int8: error <= scale/2 = max|block|/254
    err = np.abs(np.asarray(deq) - np.asarray(x))
    assert err.max() <= float(jnp.abs(x).max()) / 127.0 + 1e-6


def test_quantized_state_specs_smaller():
    specs = {"w": ParamSpec((1024, 1024), jnp.bfloat16, (None, None))}
    fp = init_opt_state(specs, AdamWConfig(quantized=False))
    q = init_opt_state(specs, AdamWConfig(quantized=True))
    bytes_fp = sum(np.asarray(v).nbytes for v in fp.values())
    bytes_q = sum(np.asarray(v).nbytes for v in q.values())
    assert bytes_q < bytes_fp / 3


def test_quantized_adamw_tracks_fp32():
    cfgq = AdamWConfig(lr=0.05, quantized=True, clip_norm=None,
                       schedule="constant", weight_decay=0.0)
    cfgf = AdamWConfig(lr=0.05, quantized=False, clip_norm=None,
                       schedule="constant", weight_decay=0.0)
    specs = {"w": ParamSpec((64, 128), jnp.float32, (None, None))}
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (64, 128))}
    sq, sf = init_opt_state(specs, cfgq), init_opt_state(specs, cfgf)
    pq, pf = dict(params), dict(params)
    key = jax.random.PRNGKey(1)
    for i in range(5):
        g = {"w": jax.random.normal(jax.random.fold_in(key, i), (64, 128))}
        pq, sq, _ = adamw_update(pq, g, sq, cfgq)
        pf, sf, _ = adamw_update(pf, g, sf, cfgf)
    diff = float(jnp.max(jnp.abs(pq["w"] - pf["w"])))
    scale = float(jnp.max(jnp.abs(pf["w"] - params["w"])))
    assert diff < 0.1 * scale  # quantized tracks full-precision closely


def test_schedule_warmup_cosine_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                      min_lr_ratio=0.1)
    lrs = [float(cfg.lr_at(jnp.asarray(s))) for s in range(0, 101, 5)]
    assert lrs[0] < lrs[1] <= 1.0          # warmup rises
    assert abs(lrs[2] - 1.0) < 0.02        # peak at end of warmup
    assert lrs[-1] == pytest.approx(0.1, abs=0.02)  # decays to min ratio


def test_error_feedback_compression_unbiased_over_steps():
    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.standard_normal((8, 256)), jnp.float32)}
    err = init_error_state(g)
    total_deq = np.zeros((8, 256))
    for _ in range(20):
        q, s, err = ef_compress(g, err)
        deq = ef_decompress(q, s, {"w": (8, 256)})
        total_deq += np.asarray(deq["w"])
    # accumulated transmitted gradient converges to 20*g (error feedback)
    np.testing.assert_allclose(total_deq / 20, np.asarray(g["w"]), atol=2e-2)
