"""TPU-native (NHWC, Flax) ResNet-18/50 with the SimCLR CIFAR stem and a
split encoder / linear-classification head.

Capability parity with the reference's model stack:
  * torchvision resnet18/50 v1.5 topology wrapped by ``ResNetSimCLR``
    (src/models/resnet_simclr.py:6-41): encoder with ``fc`` removed plus a
    separate ``linear`` head.
  * SimCLR CIFAR stem modification — 3x3 stride-1 first conv, no max pool —
    applied when the dataset is CIFAR (src/models/resnet_hacks.py:31-35,
    triggered at resnet_simclr.py:17-18).
  * Three forward modes (resnet_simclr.py:29-41): plain logits,
    ``return_features`` (logits + final embedding), and head-only from an
    embedding (``specify_input_layer='finalembed'``) — here the explicit
    ``head`` method.
  * ``freeze_feature`` detaches the embedding (resnet_simclr.py:36-37) —
    here ``jax.lax.stop_gradient``.

Design notes (TPU-first, not a translation):
  * NHWC layout — XLA's native conv layout on TPU; convs tile directly onto
    the MXU.
  * ``dtype`` controls the compute precision (bfloat16 on TPU); parameters
    and batch-norm statistics stay float32.
  * Global-batch BatchNorm: under ``jit`` over a data-sharded mesh the batch
    reduction lowers to a cross-replica collective automatically, giving
    SyncBatchNorm semantics (reference: strategy.py:292) with no special
    wrapper.
  * Space-to-depth stem (``stem="s2d"``): the 224px 7x7/s2 stem conv is an
    arithmetic-intensity sink on the 128x128 MXU (3 input channels leave
    126/128 of the contraction lanes idle).  Re-laying the input as
    112x112x12 (2x2 pixel blocks flattened into channels) and folding the
    7x7/s2 kernel into an exact 4x4/s1 kernel computes the identical
    convolution with 12 contraction channels — same multiplies, MXU-shaped
    (``s2d_stem_kernel`` is the exact weight transform; pinned bit-level by
    tests/test_s2d_stem.py).  The layout transform itself can run host-side
    (data/pipeline.space_to_depth — same byte count over PCIe) or on device
    (free reshape, fused); the encoder accepts either form.
  * Fused bf16 BN statistics (``bn_stats_dtype``): flax's BatchNorm promotes
    the FULL activation tensor to float32 before its mean/var reductions —
    on a bf16 model that materializes a 2x-size tensor between the conv and
    the stats pass and breaks producer fusion.  ``FusedBatchNorm`` reduces
    the bf16 activations directly with float32 ACCUMULATION (jnp.mean's dtype
    argument lowers to a bf16-read/f32-accumulate XLA reduce), so the stats
    pass reads half the bytes and fuses with its producer.  Parameters and
    running statistics stay float32 either way.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops import backward as backward_ops

ModuleDef = Any

# Space-to-depth block size for the 224px stem: 2x2 pixel blocks -> 12
# channels, turning the 7x7/s2 stem into a 4x4/s1 conv (see module
# docstring).  The channel order within a block is (di, dj, c) row-major —
# data/pipeline.space_to_depth, space_to_depth() below, and
# s2d_stem_kernel() must all agree on it.
S2D_BLOCK = 2


def space_to_depth(x: jnp.ndarray, block: int = S2D_BLOCK) -> jnp.ndarray:
    """[B, H, W, C] -> [B, H/b, W/b, b*b*C]; works on jnp and np arrays
    (pure reshape/transpose).  Channel index = (di * b + dj) * C + c."""
    b, h, w, c = x.shape
    x = x.reshape(b, h // block, block, w // block, block, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, h // block, w // block, block * block * c)


def s2d_stem_kernel(kernel7: jnp.ndarray) -> jnp.ndarray:
    """Fold a [7, 7, C, F] stride-2/pad-3 stem kernel into the exact
    [4, 4, 4C, F] stride-1 kernel over space-to-depth input.

    Derivation: output(i,j) sums W[a,b,c]·X[2i+a-3, 2j+b-3, c].  Writing
    the input row as u = 2p + di (p the s2d row, di the in-block offset)
    gives a = 2r + di - 1 for s2d tap r = p - i + 2 ∈ 0..3 — i.e. pad the
    kernel to 8x8 with one leading zero row/col, then regroup [4,2,4,2]
    into taps x in-block offsets.  Pure re-indexing: every product of the
    7x7 conv appears exactly once (plus 4C·F structural zeros), so the
    convolution is exact in every dtype.
    """
    kh, kw, c, f = kernel7.shape
    assert (kh, kw) == (7, 7), f"stem kernel must be 7x7, got {kh}x{kw}"
    padded = jnp.pad(jnp.asarray(kernel7),
                     ((1, 0), (1, 0), (0, 0), (0, 0)))
    k = padded.reshape(4, 2, 4, 2, c, f)          # [r, di, s, dj, c, f]
    k = k.transpose(0, 2, 1, 3, 4, 5)             # [r, s, di, dj, c, f]
    return k.reshape(4, 4, 4 * c, f)


def stem_kernel_from_s2d(kernel4: jnp.ndarray) -> jnp.ndarray:
    """Inverse of ``s2d_stem_kernel``: [4, 4, 4C, F] -> [7, 7, C, F]
    (drops the structural zero row/col)."""
    kh, kw, c4, f = kernel4.shape
    assert (kh, kw) == (4, 4) and c4 % 4 == 0
    c = c4 // 4
    k = kernel4.reshape(4, 4, 2, 2, c, f)         # [r, s, di, dj, c, f]
    k = k.transpose(0, 2, 1, 3, 4, 5)             # [r, di, s, dj, c, f]
    return k.reshape(8, 8, c, f)[1:, 1:]


class FusedBatchNorm(nn.Module):
    """Drop-in BatchNorm whose batch statistics read the activations in
    their COMPUTE dtype (bf16) with float32 accumulation, instead of
    flax's materialize-as-float32-then-reduce (see module docstring).

    Same collections and semantics as the ``nn.BatchNorm`` usage in this
    file: float32 scale/bias params, float32 running mean/var in
    ``batch_stats``, fast-variance formula E[x²]−E[x]² (flax's
    ``use_fast_variance=True`` default), momentum-0.9 EMA update.
    """

    use_running_average: Optional[bool] = None
    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: Any = jnp.float32
    # Cross-device statistics axis: None (the jit path — the partitioner
    # lowers the batch reductions to collectives itself, SyncBatchNorm
    # for free) or a mesh axis name when the module runs inside a
    # shard_map body (the int8 gradient-sync step), where local means
    # must be pmean'd explicitly to keep global-batch semantics.
    axis_name: Optional[str] = None
    scale_init: Callable = nn.initializers.ones
    bias_init: Callable = nn.initializers.zeros

    @nn.compact
    def __call__(self, x, use_running_average: Optional[bool] = None):
        use_ra = nn.merge_param(
            "use_running_average", self.use_running_average,
            use_running_average)
        features = x.shape[-1]
        axes = tuple(range(x.ndim - 1))
        ra_mean = self.variable("batch_stats", "mean",
                                lambda s: jnp.zeros(s, jnp.float32),
                                (features,))
        ra_var = self.variable("batch_stats", "var",
                               lambda s: jnp.ones(s, jnp.float32),
                               (features,))
        scale = self.param("scale", self.scale_init, (features,),
                           jnp.float32)
        bias = self.param("bias", self.bias_init, (features,), jnp.float32)

        if use_ra:
            mean, var = ra_mean.value, ra_var.value
        elif self.axis_name is None:
            # Training statistics + normalize via the custom-VJP kernel
            # (ops/backward.fused_bn_train): the primal is bit-identical
            # to the inline bf16-reads/f32-accumulation math that lived
            # here (the ``dtype`` reduce argument and in-reduce f32
            # convert — no float32 copy materialized; the SQUARE happens
            # in f32 because E[x²]−E[x]² amplifies bf16 squaring error
            # into a clamped-to-zero variance whenever mean² ≫ var), and
            # the BACKWARD keeps the same discipline instead of XLA's
            # materialize-everything-as-f32 derivation (DESIGN.md §4,
            # parity pinned in tests/test_backward.py).
            y, mean, var = backward_ops.fused_bn_train(
                x, scale, bias, dtype=self.dtype, epsilon=self.epsilon)
            if not self.is_initializing():
                m = self.momentum
                ra_mean.value = m * ra_mean.value + (1 - m) * mean
                ra_var.value = m * ra_var.value + (1 - m) * var
            return y
        else:
            # shard_map body (axis_name set): per-shard partial sums
            # pmean'd into GLOBAL batch statistics — same global-batch
            # BN the jit partitioner derives, up to reduction order.
            # Plain autodiff backward here: this branch only runs on the
            # quantized-gradient path, which is bounded-delta by
            # contract anyway (parallel/mesh.int8_allreduce).
            x_stats = x.astype(self.dtype)
            mean = jax.lax.pmean(
                jnp.mean(x_stats, axes, dtype=jnp.float32), self.axis_name)
            mean2 = jax.lax.pmean(
                jnp.mean(jax.lax.square(x_stats.astype(jnp.float32)),
                         axes), self.axis_name)
            var = jnp.maximum(mean2 - jax.lax.square(mean), 0.0)
            if not self.is_initializing():
                m = self.momentum
                ra_mean.value = m * ra_mean.value + (1 - m) * mean
                ra_var.value = m * ra_var.value + (1 - m) * var

        mul = (scale * jax.lax.rsqrt(var + self.epsilon)).astype(self.dtype)
        sub = (mean.astype(self.dtype) * mul - bias.astype(self.dtype))
        return x.astype(self.dtype) * mul - sub


# Flax auto-names unnamed submodules by CLASS name; the residual blocks'
# norms must keep their "BatchNorm_N" paths so checkpoints (and the torch
# overlay map in utils/pretrained.py) are identical whichever statistics
# path a model was built with — a bf16-stats training run must restore
# into an f32-stats eval model and vice versa.
FusedBatchNorm.__name__ = "BatchNorm"
FusedBatchNorm.__qualname__ = "BatchNorm"

# torch init_params semantics (src/models/utils.py:5-18): conv weights
# kaiming-normal fan_out, linear weights N(0, 1e-3), biases zero.  BatchNorm
# scale=1/bias=0 is the flax default.
conv_kernel_init = nn.initializers.variance_scaling(2.0, "fan_out", "normal")
dense_kernel_init = nn.initializers.normal(stddev=1e-3)


class S2DStemConv(nn.Module):
    """The s2d stem's 4x4/stride-1 conv with the hand-written backward
    (ops/backward.stem_conv): forward bit-identical to the ``nn.Conv``
    it replaces (same param name/shape/init — checkpoint trees are
    unchanged), backward with bf16 reads and a float32-ACCUMULATED
    weight gradient instead of XLA's bf16-accumulate-then-cast
    derivation (DESIGN.md §4; parity pinned in tests/test_backward.py).
    """

    features: int
    dtype: Any = jnp.float32
    kernel_init: Callable = conv_kernel_init

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", self.kernel_init,
                            (4, 4, x.shape[-1], self.features),
                            jnp.float32)
        return backward_ops.stem_conv(x, kernel, dtype=self.dtype,
                                      padding=((2, 1), (2, 1)))


class BasicBlock(nn.Module):
    """ResNet v1.5 basic block (two 3x3 convs) — resnet18/34."""

    filters: int
    strides: Tuple[int, int] = (1, 1)
    conv: ModuleDef = None
    norm: ModuleDef = None

    @nn.compact
    def __call__(self, x):
        # 3x3 convs use EXPLICIT (1, 1) padding, not "SAME": for stride-2
        # on even spatial sizes SAME pads (0, 1) while torch's padding=1
        # pads (1, 1) — a one-pixel window shift that silently breaks
        # converted torch checkpoints (tests/test_torch_parity.py).
        residual = x
        y = self.conv(self.filters, (3, 3), self.strides,
                      padding=[(1, 1), (1, 1)])(x)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters, (3, 3), padding=[(1, 1), (1, 1)])(y)
        y = self.norm(scale_init=nn.initializers.ones)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters, (1, 1), self.strides,
                                 name="downsample_conv")(residual)
            residual = self.norm(name="downsample_bn")(residual)
        return nn.relu(residual + y)


class BottleneckBlock(nn.Module):
    """ResNet v1.5 bottleneck (1x1 -> strided 3x3 -> 1x1 x4) — resnet50.

    The stride lives on the 3x3 conv, matching torchvision's v1.5 used by
    the reference (resnet_hacks.py docstring notes torchvision is v1.5).
    """

    filters: int
    strides: Tuple[int, int] = (1, 1)
    conv: ModuleDef = None
    norm: ModuleDef = None

    @nn.compact
    def __call__(self, x):
        # Same explicit-padding rule as BasicBlock for the strided 3x3.
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters, (3, 3), self.strides,
                      padding=[(1, 1), (1, 1)])(y)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.ones)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1), self.strides,
                                 name="downsample_conv")(residual)
            residual = self.norm(name="downsample_bn")(residual)
        return nn.relu(residual + y)


class ResNetEncoder(nn.Module):
    """Backbone producing the pooled final embedding (fc removed, mirroring
    ``self.encoder.fc = nn.Identity()`` at resnet_simclr.py:21)."""

    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_filters: int = 64
    cifar_stem: bool = False
    stem: str = "default"  # "default" | "s2d" (224px path only)
    bn_stats_dtype: Any = None  # None/f32 -> flax BatchNorm; bf16 -> fused
    # BN cross-device statistics axis for shard_map bodies (the int8
    # gradient-sync train step) — None under plain jit, where the
    # partitioner derives the collective itself.
    axis_name: Optional[str] = None
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = functools.partial(
            nn.Conv, use_bias=False, dtype=self.dtype,
            kernel_init=conv_kernel_init)
        fused_stats = self.bn_stats_dtype == jnp.bfloat16
        norm = functools.partial(
            FusedBatchNorm if fused_stats else nn.BatchNorm,
            use_running_average=not train, momentum=0.9,
            epsilon=1e-5, dtype=self.dtype, axis_name=self.axis_name)

        x = x.astype(self.dtype)
        if self.cifar_stem:
            # SimCLR CIFAR stem: 3x3 stride-1 conv, no max pool
            # (resnet_hacks.py:31-35).
            x = conv(self.num_filters, (3, 3), (1, 1), name="conv_stem")(x)
            x = norm(name="bn_stem")(x)
            x = nn.relu(x)
        elif self.stem == "s2d":
            if x.shape[-1] == 3:
                # Host didn't pre-transform (resident pools, epoch-scan
                # gathers): the layout change is a free on-device reshape
                # that XLA fuses with the conv's input read.
                x = space_to_depth(x)
            # Exact refactoring of the 7x7/s2 stem: 4x4/s1 over 2x2-block
            # channels, explicit (2, 1) padding = the 7x7's pad-3 window
            # in s2d coordinates (see s2d_stem_kernel).  S2DStemConv is
            # forward-identical to the nn.Conv it replaced (same param
            # tree) with the hand-written f32-accumulating backward.
            x = S2DStemConv(self.num_filters, dtype=self.dtype,
                            name="conv_stem")(x)
            x = norm(name="bn_stem")(x)
            x = nn.relu(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2),
                            padding=[(1, 1), (1, 1)])
        else:
            x = conv(self.num_filters, (7, 7), (2, 2),
                     padding=[(3, 3), (3, 3)], name="conv_stem")(x)
            x = norm(name="bn_stem")(x)
            x = nn.relu(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2),
                            padding=[(1, 1), (1, 1)])

        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = self.block_cls(
                    filters=self.num_filters * 2 ** i,
                    strides=strides, conv=conv, norm=norm,
                    name=f"stage{i + 1}_block{j}")(x)

        # Global average pool -> final embedding, float32 for the head and
        # for downstream acquisition math (margins, pairwise distances).
        x = jnp.mean(x, axis=(1, 2))
        return x.astype(jnp.float32)


class SSLClassifier(nn.Module):
    """Encoder + separate linear head (resnet_simclr.py:20-22).

    Forward modes:
      * ``apply(vars, x)``                      -> logits
      * ``apply(vars, x, return_features=True)``-> (logits, embedding)
      * ``apply(vars, emb, method="head")``     -> logits from an embedding
        (the reference's ``specify_input_layer='finalembed'``).
    """

    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int
    cifar_stem: bool = False
    stem: str = "default"
    bn_stats_dtype: Any = None
    # BN cross-device axis for shard_map bodies; the trainer clones the
    # model with this set when building the int8 gradient-sync step
    # (``model.clone(axis_name=...)`` — parameters are unaffected).
    axis_name: Optional[str] = None
    freeze_feature: bool = False
    dtype: Any = jnp.float32

    def setup(self):
        self.encoder = ResNetEncoder(
            stage_sizes=self.stage_sizes, block_cls=self.block_cls,
            cifar_stem=self.cifar_stem, stem=self.stem,
            bn_stats_dtype=self.bn_stats_dtype,
            axis_name=self.axis_name, dtype=self.dtype,
            name="encoder")
        self.linear = nn.Dense(
            self.num_classes, kernel_init=dense_kernel_init,
            bias_init=nn.initializers.zeros, name="linear")

    def __call__(self, x, train: bool = True, return_features: bool = False):
        embedding = self.encoder(x, train=train)
        if self.freeze_feature:
            # Stop-gradient on the backbone output (resnet_simclr.py:36-37);
            # combined with eval-mode BN in the trainer this freezes the
            # feature extractor for linear evaluation.
            embedding = jax.lax.stop_gradient(embedding)
        logits = self.linear(embedding)
        if return_features:
            return logits, embedding
        return logits

    def head(self, embedding):
        return self.linear(embedding)

    @property
    def embed_dim(self) -> int:
        mult = 4 if self.block_cls is BottleneckBlock else 1
        return 64 * 2 ** (len(self.stage_sizes) - 1) * mult


def _make(stage_sizes, block_cls, num_classes, cifar_stem, freeze_feature,
          dtype, stem, bn_stats_dtype):
    if stem == "s2d" and cifar_stem:
        raise ValueError("the s2d stem refactors the 7x7/s2 ImageNet stem; "
                         "the CIFAR stem (3x3/s1) has nothing to fold")
    if stem not in ("default", "s2d"):
        raise ValueError(f"unknown stem {stem!r}; expected 'default'/'s2d'")
    return SSLClassifier(
        stage_sizes=tuple(stage_sizes), block_cls=block_cls,
        num_classes=num_classes, cifar_stem=cifar_stem, stem=stem,
        bn_stats_dtype=bn_stats_dtype, freeze_feature=freeze_feature,
        dtype=dtype)


def resnet18(num_classes: int, cifar_stem: bool = False,
             freeze_feature: bool = False, dtype: Any = jnp.float32,
             stem: str = "default",
             bn_stats_dtype: Any = None) -> SSLClassifier:
    return _make([2, 2, 2, 2], BasicBlock, num_classes, cifar_stem,
                 freeze_feature, dtype, stem, bn_stats_dtype)


def resnet50(num_classes: int, cifar_stem: bool = False,
             freeze_feature: bool = False, dtype: Any = jnp.float32,
             stem: str = "default",
             bn_stats_dtype: Any = None) -> SSLClassifier:
    return _make([3, 4, 6, 3], BottleneckBlock, num_classes, cifar_stem,
                 freeze_feature, dtype, stem, bn_stats_dtype)
