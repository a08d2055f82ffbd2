"""Per-architecture smoke tests: reduced same-family configs, one forward +
one train step on CPU, asserting output shapes and finiteness (no NaNs)."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import ArchFamily
from repro.models.transformer import (
    init_decode_state,
    lm_apply,
    lm_decode_step,
    lm_init,
    lm_loss,
)

REDUCED_MODULES = {
    "qwen3-1.7b": "repro.configs.qwen3_1p7b",
    "qwen3-8b": "repro.configs.qwen3_8b",
    "deepseek-67b": "repro.configs.deepseek_67b",
    "glm4-9b": "repro.configs.glm4_9b",
    "musicgen-medium": "repro.configs.musicgen_medium",
    "dbrx-132b": "repro.configs.dbrx_132b",
    "kimi-k2-1t-a32b": "repro.configs.kimi_k2_1t_a32b",
    "hymba-1.5b": "repro.configs.hymba_1p5b",
    "xlstm-350m": "repro.configs.xlstm_350m",
    "paligemma-3b": "repro.configs.paligemma_3b",
}


def reduced_cfg(arch):
    return importlib.import_module(REDUCED_MODULES[arch]).reduced()


def make_batch(cfg, B=2, S=16, seed=0):
    rng = np.random.default_rng(seed)
    batch = {}
    if cfg.family == ArchFamily.AUDIO:
        batch["frontend"] = jnp.asarray(
            rng.normal(0, 1, (B, S, cfg.d_model)), jnp.float32)
        batch["labels"] = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    elif cfg.family == ArchFamily.VLM:
        F = cfg.frontend_tokens
        batch["frontend"] = jnp.asarray(
            rng.normal(0, 1, (B, F, cfg.d_model)), jnp.float32)
        batch["tokens"] = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
        batch["labels"] = batch["tokens"]
    else:
        batch["tokens"] = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
        batch["labels"] = batch["tokens"]
    return batch


@pytest.mark.parametrize("arch", sorted(REDUCED_MODULES))
def test_forward_shapes_and_finiteness(arch):
    cfg = reduced_cfg(arch)
    params, axes = lm_init(cfg, seed=0)
    # axes tree must mirror params tree
    jax.tree_util.tree_map(lambda p, a: None, params,
                           jax.tree_util.tree_map(lambda a: a, axes,
                                                  is_leaf=lambda x: isinstance(x, tuple)))
    batch = make_batch(cfg)
    logits = lm_apply(cfg, params, tokens=batch.get("tokens"),
                      frontend=batch.get("frontend"))
    B = 2
    S_total = 16 + (cfg.frontend_tokens if cfg.family == ArchFamily.VLM else 0)
    assert logits.shape == (B, S_total, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("arch", sorted(REDUCED_MODULES))
def test_one_train_step_no_nans(arch):
    cfg = reduced_cfg(arch)
    params, _ = lm_init(cfg, seed=0)
    batch = make_batch(cfg)

    def loss_fn(p):
        return lm_loss(cfg, p, batch)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert bool(jnp.isfinite(loss)), f"{arch}: loss {loss}"
    # SGD step; loss must decrease (learnable) and stay finite
    params2 = jax.tree_util.tree_map(lambda p, g: p - 0.05 * g, params, grads)
    loss2 = loss_fn(params2)
    assert bool(jnp.isfinite(loss2))
    flat = jax.tree_util.tree_leaves(grads)
    assert all(bool(jnp.isfinite(g).all()) for g in flat), f"{arch}: NaN grads"


@pytest.mark.parametrize("arch", sorted(REDUCED_MODULES))
def test_decode_step(arch):
    cfg = reduced_cfg(arch)
    params, _ = lm_init(cfg, seed=0)
    B, T = 2, 32
    state = init_decode_state(cfg, B, T)
    length = jnp.asarray([3, 5], jnp.int32)
    if cfg.family == ArchFamily.AUDIO:
        tok = jnp.asarray(np.random.default_rng(0).normal(0, 1, (B, cfg.d_model)), jnp.float32)
    else:
        tok = jnp.asarray([1, 2], jnp.int32)
    logits, new_state = lm_decode_step(cfg, params, state, tok, length)
    assert logits.shape == (B, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    # decode twice more to exercise cache writes
    logits, new_state = lm_decode_step(cfg, params, new_state, tok, length + 1)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("arch", ["paper-lenet5", "paper-cnn-b",
                                  "paper-resnet18"])
def test_cnn_conv_impls_agree(arch):
    """The GEMM (im2col) conv lowering must match the historical lax conv
    on full model forwards, and on gradients for pool-free models (max-pool
    backward legitimately routes gradient to a DIFFERENT tied element under
    the two lowerings — both valid subgradients, so lenet5's grads are
    exempt)."""
    from repro.config.registry import get_arch
    from repro.models import cnn_zoo

    cfg = get_arch(arch)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(0, 1, (4, *cfg.input_shape)), jnp.float32)
    y = jnp.asarray(rng.integers(0, cfg.num_classes, 4), jnp.int32)
    params = cnn_zoo.cnn_init(cfg, seed=0)

    def loss_and_grad():
        loss, _ = cnn_zoo.cnn_loss_and_accuracy(params, cfg, x, y)
        g = jax.grad(lambda p: cnn_zoo.cnn_loss_and_accuracy(p, cfg, x, y)[0])(params)
        return cnn_zoo.cnn_apply(params, cfg, x), loss, g

    try:
        cnn_zoo.set_conv_impl("gemm")
        out_g, loss_g, grad_g = loss_and_grad()
        cnn_zoo.set_conv_impl("lax")
        out_l, loss_l, grad_l = loss_and_grad()
    finally:
        cnn_zoo.set_conv_impl("gemm")
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_l),
                               atol=1e-4, rtol=1e-4)
    # Relative: one float32 ulp of a ~80 loss (resnet18) is already 7.6e-6,
    # so a fixed 1e-5 bound fails on two ulps of summation-order noise.
    assert abs(float(loss_g) - float(loss_l)) <= 1e-6 * abs(float(loss_l))
    has_pool = any(layer[0] == "convp" for layer in cfg.cnn_spec)
    if not has_pool:
        for a, b in zip(jax.tree_util.tree_leaves(grad_g),
                        jax.tree_util.tree_leaves(grad_l)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-3)
