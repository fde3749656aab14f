"""On-device input transforms, designed to live *inside* the jitted train
step so XLA fuses them into the first convolution (HBM-bandwidth-friendly:
the host ships uint8; everything else happens on-chip).

Replaces the reference's host-side torchvision transforms
(src/data_utils/custom_cifar10.py:43-54): RandomCrop(32, padding=4) +
RandomHorizontalFlip for training, plain normalize for al/test views.
Randomness comes from the JAX PRNG key threaded through the train step.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .core import Normalization, ViewSpec


def normalize(images_u8: jnp.ndarray, norm: Normalization) -> jnp.ndarray:
    """uint8 [B,H,W,C] -> float32 normalized (ToTensor + Normalize).

    Space-to-depth batches (data/pipeline.space_to_depth: channel index
    (di*2 + dj)*C + c) are per-PIXEL the same affine transform, so the
    mean/std vectors just tile 4x along the blocked channel axis."""
    mean = jnp.asarray(norm.mean, dtype=jnp.float32) * 255.0
    std = jnp.asarray(norm.std, dtype=jnp.float32) * 255.0
    blocks = images_u8.shape[-1] // mean.shape[0]
    if blocks > 1:
        mean = jnp.tile(mean, blocks)
        std = jnp.tile(std, blocks)
    return (images_u8.astype(jnp.float32) - mean) / std


def s2d_flip(images: jnp.ndarray, flip: jnp.ndarray) -> jnp.ndarray:
    """Per-sample horizontal flip of a space-to-depth batch [B, H/2, W/2,
    4C]: mirroring the original W axis reverses the blocked column axis
    AND swaps the dj∈{0,1} in-block offsets — channel (di, dj, c) maps to
    (di, 1-dj, c).  Exactly equal to s2d(flip(x)); pinned by
    tests/test_s2d_stem.py."""
    c4 = images.shape[-1]
    c = c4 // 4
    perm = jnp.arange(c4).reshape(2, 2, c)[:, ::-1, :].reshape(-1)
    flipped = images[:, :, ::-1, :][..., perm]
    return jnp.where(flip[:, None, None, None], flipped, images)


def random_crop_flip(images: jnp.ndarray, key: jax.Array,
                     pad: int = 4) -> jnp.ndarray:
    """Per-sample random crop (zero padding, torch RandomCrop semantics) +
    per-sample horizontal flip, fully vectorized.

    Shapes are static: pad -> vmapped dynamic_slice back to the original
    H x W, so the whole thing stays one fused XLA computation.
    """
    b, h, w, c = images.shape
    key_crop, key_flip = jax.random.split(key)
    if pad > 0:
        padded = jnp.pad(images, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
        offsets = jax.random.randint(key_crop, (b, 2), 0, 2 * pad + 1)

        def crop_one(img, off):
            return jax.lax.dynamic_slice(img, (off[0], off[1], 0), (h, w, c))

        cropped = jax.vmap(crop_one)(padded, offsets)
    else:
        # pad=0: flip-only augmentation (ImageNet's random-resized crop
        # happens host-side at decode time; only the flip is on-device).
        cropped = images
    flip = jax.random.bernoulli(key_flip, 0.5, (b,))
    flipped = jnp.where(flip[:, None, None, None], cropped[:, :, ::-1, :],
                        cropped)
    return flipped


def apply_view(images_u8: jnp.ndarray, view: ViewSpec,
               key: jax.Array = None, train: bool = True) -> jnp.ndarray:
    """Apply a dataset view's transform on device.

    augment=True + train=True: random crop/flip on raw uint8 (so the crop
    padding is black pixels, matching torch's RandomCrop-before-Normalize
    order), then normalize.  Otherwise: normalize only (the reference's val
    transform).
    """
    x = images_u8
    if view.normalization is None:
        # Rows of token ids: their view is the backbone's own input stage
        # (models/backbone.input_stage), which runs right behind this.
        return x
    s2d = len(view.normalization.mean) * 4 == x.shape[-1]
    if view.augment and train:
        assert key is not None, "augmentation requires a PRNG key"
        if s2d:
            # Space-to-depth batches only exist on the 224px path, whose
            # train view is flip-only (pad=0: the random crop happened at
            # decode time, data/imagenet.py).
            assert view.pad == 0, "s2d batches support flip-only views"
            _, key_flip = jax.random.split(key)
            x = s2d_flip(x, jax.random.bernoulli(key_flip, 0.5,
                                                 (x.shape[0],)))
        else:
            x = random_crop_flip(x, key, pad=view.pad)
    return normalize(x, view.normalization)
