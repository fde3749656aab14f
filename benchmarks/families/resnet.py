"""The ResNet family: uint8 image rows, a torchvision-layout weight tree, the
convolutions' FLOPs and the plain float32 forward.

Rows are made on the host in integer arithmetic, a chunk at a time on a few
threads (never a float array of the pool's size), into one uint8 array: the
in-memory pool that a warm round sees.  Weights are made once on the host in
the torchvision layout; the program loads them through its own
pretrained-checkpoint overlay every round, and the plain reference builds
its parameters from the same dictionary, so neither takes anything from the
other.

The work count is of what the algorithm needs, not of what a compiled
program happens to execute: padded scan steps, recomputation and layout
copies are not in it, so they show as a lower share of the roofline.  One
multiply-accumulate is two floating-point operations.

The forward is a straightforward ``jax.numpy`` ResNet (float32, matmul
precision ``highest``, BatchNorm on its stored statistics as the program
runs it under a pretrained checkpoint).  Only ``datasets`` imports the
program.  The contract: ``families/__init__.py``.
"""

from __future__ import annotations

import os
from typing import Dict, List, Tuple

import numpy as np

from lib.reference import q

CHUNK_ROWS = 256
GEN_THREADS = 8
_GRID = 8                       # class template: an 8x8 colour grid

IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)
BN_EPS = 1e-5


# -- rows ----------------------------------------------------------------------

def _fill_chunk(images: np.ndarray, labels: np.ndarray,
                templates: np.ndarray, seed: int, salt: int, start: int,
                rows: int) -> None:
    """Rows ``start:start+rows``: the class's colour grid, upsampled, plus
    pixel noise.  All uint8: templates lie in [64, 191] and the noise in
    [-32, 31], so nothing clips.  The grid is broadcast over its cells, not
    materialised."""
    size, ch = images.shape[1], images.shape[3]
    rep = size // _GRID
    n = rows * size * size * ch
    raw = np.random.PCG64([int(seed), salt, start]).random_raw(-(-n // 8))
    noise = raw.view(np.uint8)[:n]
    noise >>= 2
    cells = (rows, _GRID, rep, _GRID, rep, ch)
    base = templates[labels[start:start + rows]] - np.uint8(32)
    np.add(noise.reshape(cells), base[:, :, None, :, None, :],
           out=images[start:start + rows].reshape(cells))


def make_split(seed: int, salt: int, n: int, size: int, channels: int,
               num_classes: int, templates: np.ndarray
               ) -> Tuple[np.ndarray, np.ndarray]:
    """``n`` class-structured uint8 rows and their labels.  A row is its
    class's coarse colour grid plus uniform pixel noise, so a fit can learn
    the classes and no two rows tie.  Made on the host a chunk at a time
    (each chunk from its own generator, so the result does not depend on the
    thread that makes it), in integer arithmetic only."""
    from concurrent.futures import ThreadPoolExecutor
    assert size % _GRID == 0, "image size must be a multiple of the grid"
    rng = np.random.default_rng([int(seed), salt])
    labels = rng.integers(0, num_classes, size=n).astype(np.int64)
    images = np.empty((n, size, size, channels), dtype=np.uint8)
    starts = range(0, n, CHUNK_ROWS)
    with ThreadPoolExecutor(max_workers=GEN_THREADS) as pool:
        futures = [pool.submit(_fill_chunk, images, labels, templates, seed,
                               salt, s, min(CHUNK_ROWS, n - s))
                   for s in starts]
        for f in futures:
            f.result()
    return images, labels


def make_templates(seed: int, num_classes: int, channels: int) -> np.ndarray:
    rng = np.random.default_rng([int(seed), 11])
    return rng.integers(64, 192, size=(num_classes, _GRID, _GRID, channels),
                        dtype=np.uint8)


def make_data(seed: int, config: Dict, n_pool: int, n_test: int):
    """(pool images, pool labels, test images, test labels)."""
    size, ch = int(config["image_size"]), int(config["in_channels"])
    nc = int(config["num_classes"])
    templates = make_templates(seed, nc, ch)
    pool = make_split(seed, 21, n_pool, size, ch, nc, templates)
    test = make_split(seed, 22, n_test, size, ch, nc, templates)
    return pool + test


def datasets(config: Dict, pool, test):
    """The program's (train_set, test_set, al_set) over the host arrays."""
    from active_learning_tpu.data.core import (
        ArrayDataset, IMAGENET_NORM, ViewSpec)
    nc = int(config["num_classes"])
    # The 224 px train view: flip on the device (the random-resized crop
    # belongs to decode time, which a warm in-memory pool has behind it).
    train_set = ArrayDataset(pool[0], pool[1], nc,
                             ViewSpec(IMAGENET_NORM, augment=True, pad=0))
    val_view = ViewSpec(IMAGENET_NORM, augment=False)
    al_set = train_set.with_view(val_view)
    test_set = ArrayDataset(test[0], test[1], nc, val_view)
    return train_set, test_set, al_set


def experiment(config: Dict) -> Dict:
    return {"dataset": "imagenet", "model": config["model"]}


# -- weights ---------------------------------------------------------------

def block_keys(config: Dict) -> List[Tuple[str, List[Tuple[str, tuple]],
                                           int, bool]]:
    """(prefix, [(conv name, OIHW shape)], stride, has_downsample) for each
    residual block, in torchvision naming."""
    width = int(config["num_filters"])
    bottleneck = config["block"] == "bottleneck"
    exp = 4 if bottleneck else 1
    out = []
    c_in = width
    for stage, blocks in enumerate(config["stage_sizes"]):
        f = width * 2 ** stage
        for b in range(blocks):
            stride = 2 if stage > 0 and b == 0 else 1
            if bottleneck:
                convs = [("conv1", (f, c_in, 1, 1)), ("conv2", (f, f, 3, 3)),
                         ("conv3", (f * exp, f, 1, 1))]
            else:
                convs = [("conv1", (f, c_in, 3, 3)), ("conv2", (f, f, 3, 3))]
            down = stride != 1 or c_in != f * exp
            if down:
                convs.append(("downsample.0", (f * exp, c_in, 1, 1)))
            out.append((f"encoder.layer{stage + 1}.{b}", convs, stride, down))
            c_in = f * exp
    return out


# The residual branch's last BatchNorm scale.  BatchNorm runs on its stored
# statistics when a pretrained checkpoint is configured (the reference's
# rule), so a random network has nothing to normalise it; a small last scale
# keeps the activations of 16 stacked blocks of order one.
LAST_BN_SCALE = 0.3
HEAD_STD = 0.05


def make_weights(seed: int, config: Dict) -> Dict[str, np.ndarray]:
    """Every tensor of the model as float32, keyed like a torchvision
    ResNet wrapped by the reference's ``ResNetSimCLR`` (``encoder.*`` and
    ``linear.*``): He-normal (fan-out) convolutions, BatchNorm scale 1 (the
    branch's last: ``LAST_BN_SCALE``) and stored statistics mean 0 / var 1,
    a normal head."""
    rng = np.random.default_rng([int(seed), 31])
    w: Dict[str, np.ndarray] = {}

    def conv(name, shape):
        o, _, kh, kw = shape
        std = np.sqrt(2.0 / (kh * kw * o))
        w[name] = (rng.standard_normal(shape, dtype=np.float32)
                   * np.float32(std))

    def bn(name, c, scale=1.0):
        w[f"{name}.weight"] = np.full(c, scale, np.float32)
        w[f"{name}.bias"] = np.zeros(c, np.float32)
        w[f"{name}.running_mean"] = np.zeros(c, np.float32)
        w[f"{name}.running_var"] = np.ones(c, np.float32)

    width = int(config["num_filters"])
    conv("encoder.conv1.weight", (width, int(config["in_channels"]), 7, 7))
    bn("encoder.bn1", width)
    for prefix, convs, _, _ in block_keys(config):
        main = [c for c in convs if not c[0].startswith("downsample")]
        for i, (cname, shape) in enumerate(convs):
            conv(f"{prefix}.{cname}.weight", shape)
            if cname.startswith("downsample"):
                bn(f"{prefix}.downsample.1", shape[0])
            else:
                last = i == len(main) - 1
                bn(f"{prefix}.bn{cname[-1]}", shape[0],
                   LAST_BN_SCALE if last else 1.0)
    d, nc = embed_dim(config), int(config["num_classes"])
    w["linear.weight"] = (rng.standard_normal((nc, d), dtype=np.float32)
                          * np.float32(HEAD_STD))
    w["linear.bias"] = (rng.standard_normal(nc, dtype=np.float32)
                        * np.float32(HEAD_STD))
    return w


def save_checkpoint(weights: Dict[str, np.ndarray], directory: str) -> str:
    """The torch file the program's pretrained overlay reads."""
    import torch
    path = os.path.join(directory, "seed_weights.pth")
    torch.save({k: torch.from_numpy(v) for k, v in weights.items()}, path)
    return path


def flax_path(key: str) -> Tuple[str, ...]:
    """Where a tensor of ``make_weights`` sits in the program's parameter
    tree (the layout table of the program's overlay, restated here so the
    comparison can find each leaf; statistics are not parameters)."""
    parts = key.split(".")
    leaf = {"weight": "scale", "bias": "bias"}
    if parts[0] == "linear":
        return ("linear", "kernel" if parts[1] == "weight" else "bias")
    rest = parts[1:]
    if rest[0] == "conv1":
        return ("encoder", "conv_stem", "kernel")
    if rest[0] == "bn1":
        return ("encoder", "bn_stem", leaf[rest[1]])
    mod = f"stage{rest[0][5:]}_block{rest[1]}"
    sub = rest[2]
    if sub.startswith("conv"):
        return ("encoder", mod, f"Conv_{int(sub[4:]) - 1}", "kernel")
    if sub.startswith("bn"):
        return ("encoder", mod, f"BatchNorm_{int(sub[2:]) - 1}",
                leaf[rest[3]])
    if rest[3] == "0":
        return ("encoder", mod, "downsample_conv", "kernel")
    return ("encoder", mod, "downsample_bn", leaf[rest[4]])


def trainable_keys(weights: Dict[str, np.ndarray],
                   head_only: bool = False) -> List[str]:
    return [k for k in weights if "running_" not in k
            and (not head_only or k.startswith("linear."))]


def program_params(tree, weights: Dict[str, np.ndarray]
                   ) -> Dict[str, np.ndarray]:
    """The program's trainable leaves under ``make_weights``' keys, in the
    torchvision layout."""
    import jax
    flat = {"/".join(str(getattr(k, "key", k)) for k in path): np.asarray(v)
            for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]}
    params = {}
    for key in trainable_keys(weights):
        leaf = flat["/".join(flax_path(key))]
        if leaf.ndim == 4:
            leaf = leaf.transpose(3, 2, 0, 1)      # HWIO -> OIHW
        elif leaf.ndim == 2:
            leaf = leaf.T
        params[key] = leaf
    return params


# -- required operations and bytes ---------------------------------------

# (kernel, c_in, c_out, out_hw) per convolution, derived below.
Conv = Tuple[int, int, int, int]

_BLOCKS = {"basic": 1, "bottleneck": 4}


def resnet_convs(config: Dict) -> List[Conv]:
    """Every convolution of the encoder at ``image_size``, in order.  The
    first entry is the stem (its input needs no gradient)."""
    size = int(config["image_size"])
    width = int(config["num_filters"])
    expansion = _BLOCKS[config["block"]]
    hw = size // 2                      # 7x7 stride 2, pad 3
    convs: List[Conv] = [(7, int(config["in_channels"]), width, hw)]
    hw //= 2                            # 3x3 max-pool stride 2, pad 1
    c_in = width
    for stage, blocks in enumerate(config["stage_sizes"]):
        f = width * 2 ** stage
        for b in range(blocks):
            stride = 2 if stage > 0 and b == 0 else 1
            out_hw = hw // stride
            if config["block"] == "basic":
                convs += [(3, c_in, f, out_hw), (3, f, f, out_hw)]
            else:
                # v1.5: the stride sits on the 3x3.
                convs += [(1, c_in, f, hw), (3, f, f, out_hw),
                          (1, f, f * expansion, out_hw)]
            if stride != 1 or c_in != f * expansion:
                convs.append((1, c_in, f * expansion, out_hw))
            c_in, hw = f * expansion, out_hw
    return convs


def embed_dim(config: Dict) -> int:
    return (int(config["num_filters"])
            * 2 ** (len(config["stage_sizes"]) - 1)
            * _BLOCKS[config["block"]])


def _conv_macs(conv: Conv) -> int:
    k, c_in, c_out, hw = conv
    return k * k * c_in * c_out * hw * hw


def forward_macs(config: Dict) -> int:
    """Multiply-accumulates of one row's forward pass: convolutions and the
    linear head (the published 4.09 G for ResNet-50, 1.82 G for ResNet-18 at
    224 px and 1000 classes)."""
    head = embed_dim(config) * int(config["num_classes"])
    return sum(_conv_macs(c) for c in resnet_convs(config)) + head


def backward_macs(config: Dict, head_only: bool = False) -> int:
    """Multiply-accumulates of one row's backward pass.  Every convolution
    costs its forward twice (input gradient and weight gradient), the stem
    once (its input is data); under ``head_only`` (``freeze_feature``) only
    the head's weight gradient is needed."""
    head = embed_dim(config) * int(config["num_classes"])
    if head_only:
        return head
    convs = resnet_convs(config)
    return (2 * sum(_conv_macs(c) for c in convs) - _conv_macs(convs[0])
            + 2 * head)


def param_count(config: Dict) -> int:
    convs = sum(k * k * ci * co for k, ci, co, _ in resnet_convs(config))
    bn = 2 * sum(co for _, _, co, _ in resnet_convs(config))
    d = embed_dim(config)
    return convs + bn + d * int(config["num_classes"]) + int(
        config["num_classes"])


def row_bytes(config: Dict) -> int:
    return int(config["image_size"]) ** 2 * int(config["in_channels"])


def work(config: Dict, kind: str, rows: int, batches: int = 1,
         head_only: bool = False) -> Dict[str, float]:
    """Required FLOPs and least HBM bytes of ``rows`` rows of one kind of
    device work, done in ``batches`` program steps.

    ``forward``: score, embed, validate or test a row.  ``fit``: forward and
    backward of a fitted row-epoch, and per step the optimizer's pass over
    parameters and momentum.  Bytes are a lower bound: the uint8 rows read
    once, the parameters read once per step (f32 as stored), and for a fit
    step the parameters and momentum written back; activations are assumed
    to stay on chip."""
    fwd = forward_macs(config)
    p_bytes = 4 * param_count(config)
    if kind == "forward":
        flops = 2.0 * fwd * rows
        byts = rows * row_bytes(config) + batches * p_bytes
    elif kind == "fit":
        flops = 2.0 * (fwd + backward_macs(config, head_only)) * rows
        trained = (4 * (embed_dim(config) + 1) * int(config["num_classes"])
                   if head_only else p_bytes)
        byts = (rows * row_bytes(config)
                + batches * (p_bytes + 3 * trained))
    else:
        raise KeyError(f"unknown kind of work {kind!r}")
    return {"flops": flops, "bytes": float(byts)}


# -- the plain forward ----------------------------------------------------------

def _conv(x, w, stride: int, pad: int, quant):
    import jax
    return jax.lax.conv_general_dilated(
        q(x, quant), q(w, quant), (stride, stride),
        [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "OIHW", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def _bn(x, p: Dict, name: str):
    import jax
    mul = p[f"{name}.weight"] * jax.lax.rsqrt(
        p[f"{name}.running_var"] + BN_EPS)
    return x * mul + (p[f"{name}.bias"] - p[f"{name}.running_mean"] * mul)


def embed(p: Dict, x_u8, config: Dict, quant=None):
    """uint8 rows [B,H,W,C] -> float32 embedding [B,D]."""
    import jax
    import jax.numpy as jnp
    mean = jnp.asarray(IMAGENET_MEAN, jnp.float32) * 255.0
    std = jnp.asarray(IMAGENET_STD, jnp.float32) * 255.0
    x = (x_u8.astype(jnp.float32) - mean) / std
    x = _conv(x, p["encoder.conv1.weight"], 2, 3, quant)
    x = jax.nn.relu(_bn(x, p, "encoder.bn1"))
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        [(0, 0), (1, 1), (1, 1), (0, 0)])
    bottleneck = config["block"] == "bottleneck"
    for prefix, convs, stride, down in block_keys(config):
        res = x
        if bottleneck:
            y = _conv(x, p[f"{prefix}.conv1.weight"], 1, 0, quant)
            y = jax.nn.relu(_bn(y, p, f"{prefix}.bn1"))
            y = _conv(y, p[f"{prefix}.conv2.weight"], stride, 1, quant)
            y = jax.nn.relu(_bn(y, p, f"{prefix}.bn2"))
            y = _conv(y, p[f"{prefix}.conv3.weight"], 1, 0, quant)
            y = _bn(y, p, f"{prefix}.bn3")
        else:
            y = _conv(x, p[f"{prefix}.conv1.weight"], stride, 1, quant)
            y = jax.nn.relu(_bn(y, p, f"{prefix}.bn1"))
            y = _conv(y, p[f"{prefix}.conv2.weight"], 1, 1, quant)
            y = _bn(y, p, f"{prefix}.bn2")
        if down:
            res = _conv(x, p[f"{prefix}.downsample.0.weight"], stride, 0,
                        quant)
            res = _bn(res, p, f"{prefix}.downsample.1")
        x = jax.nn.relu(res + y)
    return jnp.mean(x, axis=(1, 2))


def head(p: Dict, emb, quant=None):
    import jax
    import jax.numpy as jnp
    return jnp.matmul(q(emb, quant), q(p["linear.weight"], quant).T,
                      precision=jax.lax.Precision.HIGHEST) + p["linear.bias"]


def train_view(x_u8, step_key, augment):
    """The fit's view of a batch: each row flipped or not, the flips drawn
    from the second half of the step key's own split (the first half is the
    crop's, unused at 224 px)."""
    import jax
    import jax.numpy as jnp
    _, key_flip = jax.random.split(step_key)
    flips = jax.random.bernoulli(key_flip, 0.5, (x_u8.shape[0],)) & augment
    return jnp.where(flips[:, None, None, None], x_u8[:, :, ::-1, :], x_u8)
