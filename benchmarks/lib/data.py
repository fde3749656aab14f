"""Inputs and weights, from the seed alone.

Rows are made on the host in integer arithmetic, a chunk at a time on a few
threads (never a float array of the pool's size), into one uint8 array: the
in-memory pool that a warm round sees.  Weights are made once on
the host in the torchvision layout; the program loads them through its own
pretrained-checkpoint overlay every round, and the plain reference builds
its parameters from the same dictionary, so neither takes anything from the
other.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from .flops import embed_dim

CHUNK_ROWS = 256
GEN_THREADS = 8
_GRID = 8                       # class template: an 8x8 colour grid


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


def save_torch_checkpoint(weights: Dict[str, np.ndarray], path: str) -> None:
    """The file the program's pretrained overlay reads."""
    import torch
    torch.save({k: torch.from_numpy(v) for k, v in weights.items()}, path)


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


def trainable_keys(weights: Dict[str, np.ndarray]) -> List[str]:
    return [k for k in weights if "running_" not in k]
