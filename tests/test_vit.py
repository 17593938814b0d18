"""ViT tests: shapes, param count, pooling modes, sharded-mesh training."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpufw.models import VIT_CONFIGS, ViT, ViTConfig


def _tiny(pool="cls", **kw):
    return ViTConfig(
        image_size=32, patch_size=8, num_classes=10,
        d_model=32, n_layers=2, n_heads=4, d_ff=64, pool=pool, **kw
    )


def test_vit_b16_param_count():
    cfg = VIT_CONFIGS["vit_b16"]
    model = ViT(cfg)
    imgs = jnp.zeros((1, 224, 224, 3))
    variables = jax.eval_shape(model.init, jax.random.key(0), imgs)
    n = sum(np.prod(x.shape) for x in jax.tree.leaves(variables["params"]))
    # Canonical ViT-B/16 (1000 classes): ~86.6M params.
    assert 86.0e6 < n < 87.0e6, n
    assert n == cfg.n_params(), (n, cfg.n_params())


def test_forward_shapes_and_pooling():
    imgs = jax.random.normal(jax.random.key(0), (2, 32, 32, 3))
    for pool in ("cls", "mean"):
        cfg = _tiny(pool=pool)
        model = ViT(cfg)
        variables = model.init(jax.random.key(1), imgs)
        assert "batch_stats" not in variables  # stat-free by design
        out = model.apply(variables, imgs)
        assert out.shape == (2, 10)
        assert out.dtype == jnp.float32
        assert np.all(np.isfinite(np.asarray(out)))


def test_patchify_is_conv_equivalent():
    """The reshape+matmul patch embedding must equal a stride-p conv
    with the same kernel — the whole point of the rewrite is that the
    math is identical."""
    from flax import linen as nn

    from flax.core import meta

    cfg = _tiny(pool="mean")
    model = ViT(cfg)
    imgs = jax.random.normal(jax.random.key(2), (1, 32, 32, 3))
    variables = meta.unbox(model.init(jax.random.key(3), imgs))
    kernel = variables["params"]["patch_embed"]["kernel"]
    bias = variables["params"]["patch_embed"]["bias"]
    p = cfg.patch_size
    conv_kernel = np.asarray(kernel).reshape(p, p, 3, cfg.d_model)
    conv_out = jax.lax.conv_general_dilated(
        imgs, conv_kernel, (p, p), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    ) + np.asarray(bias)
    g = cfg.image_size // p
    x = imgs.reshape(1, g, p, g, p, 3)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(1, g * g, p * p * 3)
    manual = x @ np.asarray(kernel) + np.asarray(bias)
    np.testing.assert_allclose(
        np.asarray(conv_out).reshape(1, g * g, cfg.d_model),
        np.asarray(manual),
        rtol=1e-4, atol=1e-4,
    )


def test_remat_and_unscanned_match_scanned():
    imgs = jax.random.normal(jax.random.key(4), (2, 32, 32, 3))
    base = _tiny()
    variables = ViT(base).init(jax.random.key(5), imgs)
    out = ViT(base).apply(variables, imgs)
    remat_out = ViT(dataclasses.replace(base, remat=True)).apply(
        variables, imgs
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(remat_out), rtol=1e-5, atol=1e-5
    )


def test_vision_trainer_vit_end_to_end(devices8):
    """ViT through the shared VisionTrainer on the 8-device mesh —
    stat-free batch_stats path, loss decreases over a few steps."""
    from tpufw.mesh import MeshConfig
    from tpufw.train import (
        VisionTrainer,
        VisionTrainerConfig,
        synthetic_images,
    )

    cfg = VisionTrainerConfig(
        batch_size=8, image_size=32, num_classes=10, total_steps=4,
        lr=0.01,
    )
    trainer = VisionTrainer(
        ViT(_tiny()), cfg, MeshConfig(data=2, fsdp=4)
    )
    trainer.init_state()
    hist = trainer.run(
        synthetic_images(8, 32, 10),
        flops_per_image=_tiny().flops_per_image(),
    )
    assert len(hist) == 4
    assert np.isfinite(hist[-1].loss)
    assert hist[-1].mfu is None  # CPU mesh: no peak, no MFU


def test_config_validation():
    with pytest.raises(ValueError):
        ViTConfig(image_size=224, patch_size=15)
    with pytest.raises(ValueError):
        ViTConfig(pool="max")
    with pytest.raises(ValueError):
        ViTConfig(d_model=100, n_heads=7)
