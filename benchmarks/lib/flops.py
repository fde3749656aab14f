"""Required operations and bytes, counted from shapes.

The count is of what the algorithm needs, not of what a compiled program
happens to execute: padded scan steps, recomputation and layout copies are
not in it, so they show as a lower share of the roofline.  One multiply-
accumulate is two floating-point operations.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

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


def least_seconds(w: Dict[str, float], peaks: Dict[str, float]
                  ) -> Tuple[float, str]:
    """The least time the chip could take for ``w`` and which peak bounds
    it."""
    t_c = w["flops"] / peaks["flops_bf16"]
    t_m = w["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
