"""Hand-written backward passes for the two hand-built forward kernels
(DESIGN.md §4, "The gradient path").

The backward pass is the step's largest cost, and it is whatever XLA
derives from the forward graph.  (Whether these hand-written forms win
on the chip has no ledger line yet: ROADMAP S1.)  For the two kernels
this repo hand-built — the space-to-depth
stem conv and the fused bf16 BN statistics — XLA's derivation loses the
very properties the forwards were built for:

  * ``FusedBatchNorm``'s forward reads bf16 activations with float32
    ACCUMULATION (the casts fuse into the reduce); autodiff of that
    graph materializes full-tensor float32 cotangents for the
    ``astype(float32)`` links in the stats path — the 2x-bytes
    materialization the forward exists to avoid, now on the backward.
  * the s2d stem's weight gradient is a contraction over batch x space
    (the worst-tiling conv on the MXU, DESIGN.md §4's weight-gradient
    row); derived from a bf16 forward it accumulates in bf16 and casts
    to f32 afterwards, instead of reading bf16 and accumulating f32
    like every forward reduction here does.

Both customs keep the PRIMAL bit-identical to the existing forward (the
checkpoint-tree and logits-parity contracts are untouched) and replace
only the cotangent computation:

  * ``stem_conv``: dx is the same transposed conv XLA derives (bf16 in,
    bf16 out — there is nothing to win); dW is ONE conv with
    ``preferred_element_type=float32`` — bf16 element reads, float32
    accumulation, f32 output landing directly in the f32 parameter
    cotangent (no bf16-round-then-cast).
  * ``fused_bn_train``: the per-channel reductions (dscale, dbias, the
    mean/variance chain) read bf16 and accumulate f32; dx is computed
    in one fused elementwise pass over bf16 reads with a single cast to
    the activation dtype at the end.  No full-size f32 tensor is ever
    materialized.

Gradient equivalence to the flax/XLA-derived backward is proven the
same way the s2d forward was (tests/test_backward.py): rounding-order
tolerance at bf16, ~1e-10 identity at f64.

Every ``jax.custom_vjp`` in the train path lives in THIS module and is
named in ``TRAIN_PATH_VJPS`` — scripts/trace_lint.py check 9 statically
verifies the registry is closed and that each name has a registered
parity test (``PARITY_TESTED_VJPS`` in tests/test_backward.py).
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# The CLOSED registry of train-path custom VJPs (trace_lint check 9):
# every jax.custom_vjp in the package must be defined here and named
# in this tuple, and every name must carry a registered parity test.
TRAIN_PATH_VJPS = ("stem_conv", "fused_bn_train")

_CONV_DN = ("NHWC", "HWIO", "NHWC")


@functools.lru_cache(maxsize=None)
def _stem_conv_fn(dtype: Any, padding: Tuple[Tuple[int, int], ...]):
    """custom_vjp'd stem conv for one (compute dtype, padding) pair —
    cached so repeated traces reuse one custom_vjp object (and one jit
    cache lineage)."""
    dtype = jnp.dtype(dtype)

    def _primal(x, kernel):
        # Exactly flax nn.Conv's forward (promote to the compute dtype,
        # stride-1 NHWC conv, default precision): the primal must stay
        # bit-identical to the nn.Conv it replaces.
        return lax.conv_general_dilated(
            x.astype(dtype), kernel.astype(dtype), (1, 1), padding,
            dimension_numbers=_CONV_DN)

    @jax.custom_vjp
    def conv(x, kernel):
        return _primal(x, kernel)

    def fwd(x, kernel):
        return _primal(x, kernel), (x, kernel)

    def bwd(res, g):
        x, kernel = res
        kd = kernel.astype(dtype)
        kh, kw = kd.shape[0], kd.shape[1]
        (pl0, pr0), (pl1, pr1) = padding
        # dx: the standard stride-1 transposed conv (flipped kernel,
        # in/out channels swapped, complementary padding) — the same
        # conv XLA's transpose rule emits, bf16 reads and writes.
        kt = jnp.flip(kd, (0, 1)).swapaxes(2, 3)
        dx = lax.conv_general_dilated(
            g, kt, (1, 1),
            ((kh - 1 - pl0, kh - 1 - pr0), (kw - 1 - pl1, kw - 1 - pr1)),
            dimension_numbers=_CONV_DN)
        # dW[h,w,c,f] = sum_{b,i,j} x[b, i+h-ph, j+w-pw, c] * g[b,i,j,f]
        # — the batch/space contraction, expressed as ONE conv whose
        # "batch" is the input channel and whose contraction runs over
        # the true batch: bf16 element reads, float32 ACCUMULATION
        # (preferred_element_type), f32 output landing directly in the
        # f32 parameter cotangent.
        dw = lax.conv_general_dilated(
            x.astype(dtype), g, (1, 1), padding,
            dimension_numbers=("CHWN", "IHWO", "HWNC"),
            # f32 accumulation over bf16/f32 reads; promoted to f64
            # under enable_x64 (preferred_element_type may not narrow).
            preferred_element_type=jnp.promote_types(dtype, jnp.float32))
        return dx.astype(x.dtype), dw.astype(kernel.dtype)

    conv.defvjp(fwd, bwd)
    return conv


def stem_conv(x: jnp.ndarray, kernel: jnp.ndarray, *, dtype: Any,
              padding=((2, 1), (2, 1))) -> jnp.ndarray:
    """The s2d stem's 4x4/stride-1 conv with the hand-written backward
    (see module docstring).  ``padding`` is the folded 7x7/pad-3 window
    in s2d coordinates (models/resnet.s2d_stem_kernel)."""
    padding = tuple(tuple(int(v) for v in p) for p in padding)
    return _stem_conv_fn(jnp.dtype(dtype), padding)(x, kernel)


def _balanced_relu_grad(a, g):
    """d/da of jnp.maximum(a, 0.0) applied to cotangent ``g``, matching
    jax's tie rule exactly (half the cotangent at a == 0) so the f64
    identity proof holds even on the clamp boundary."""
    return g * jnp.where(a > 0, 1.0, jnp.where(a == 0, 0.5, 0.0))


@functools.lru_cache(maxsize=None)
def _fused_bn_fn(dtype: Any, epsilon: float, ndim: int):
    """custom_vjp'd training-mode BN (batch statistics + normalize) for
    one (stats/compute dtype, epsilon, rank) triple.  Returns
    ``(y, mean, var)`` — the module updates its running statistics from
    mean/var outside (mutable collections carry no gradient; the bwd
    still honors their cotangents for correctness)."""
    dtype = jnp.dtype(dtype)
    axes = tuple(range(ndim - 1))
    # Accumulation dtype: float32 over bf16/f32 reads (the production
    # discipline); promoted to f64 under enable_x64 so the f64 identity
    # proof compares exact math to exact math.
    f32 = jnp.promote_types(dtype, jnp.float32)

    def _primal(x, scale, bias):
        # Bit-identical to the pre-custom-VJP FusedBatchNorm train
        # branch (models/resnet.py): bf16 element reads, f32-accumulated
        # statistics, fast-variance with the f32 square (see the
        # module's comment on cancellation), clamped at zero.  mean2 is
        # returned too: it is already an intermediate of var, and the
        # backward needs the PRE-clamp value's sign (var reads 0 both
        # at the clamp boundary and below it).
        x_stats = x.astype(dtype)
        mean = jnp.mean(x_stats, axes, dtype=f32)
        mean2 = jnp.mean(lax.square(x_stats.astype(f32)), axes)
        var = jnp.maximum(mean2 - lax.square(mean), 0.0)
        mul = (scale * lax.rsqrt(var + epsilon)).astype(dtype)
        sub = mean.astype(dtype) * mul - bias.astype(dtype)
        y = x.astype(dtype) * mul - sub
        return y, mean, var, mean2

    @jax.custom_vjp
    def bn(x, scale, bias):
        y, mean, var, _ = _primal(x, scale, bias)
        return y, mean, var

    def fwd(x, scale, bias):
        y, mean, var, mean2 = _primal(x, scale, bias)
        return (y, mean, var), (x, scale, mean, mean2)

    def bwd(res, cts):
        x, scale, mean, mean2 = res
        gy, gmean, gvar = cts
        n = float(np.prod([x.shape[a] for a in axes]))
        a_pre = mean2 - lax.square(mean)
        var = jnp.maximum(a_pre, 0.0)
        x_c = x.astype(dtype)
        r = lax.rsqrt(var + epsilon)                      # f32 [C]
        mulf = scale * r                                  # f32 [C]
        mul32 = mulf.astype(dtype).astype(f32)            # fwd's rounded mul
        # Per-channel reductions: bf16 element reads, f32 accumulation
        # (the casts fuse into the reduce's input computation — no f32
        # copy of the activation or cotangent is materialized).
        s1 = jnp.sum(gy, axes, dtype=f32)                 # Σ gy
        s2 = jnp.sum(gy.astype(f32) * x_c.astype(f32), axes)  # Σ gy·x
        dbias = s1                                        # y = ... + bias_c
        dmul = s2 - s1 * mean                             # Σ gy·(x − mean)
        dscale = dmul * r
        # var chain: r = (var+eps)^{-1/2}; var = max(mean2 − mean², 0).
        dvar = dmul * scale * (-0.5) * r * r * r + gvar
        da = _balanced_relu_grad(a_pre, dvar)
        dmean2 = da
        dmean = -s1 * mul32 + gmean - 2.0 * mean * da
        # dx, in ONE fused elementwise pass: bf16 reads of gy/x, f32
        # arithmetic against the per-channel f32 coefficients, a single
        # cast to the activation dtype on the way out.
        c2 = 2.0 * dmean2 / n                             # f32 [C]
        c1 = dmean / n                                    # f32 [C]
        dx = (gy.astype(f32) * mul32 + x_c.astype(f32) * c2 + c1)
        return dx.astype(x.dtype), dscale, dbias

    bn.defvjp(fwd, bwd)
    return bn


def fused_bn_train(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
                   *, dtype: Any, epsilon: float):
    """Training-mode fused-statistics BatchNorm with the hand-written
    backward: returns ``(y, mean, var)``; see the module docstring."""
    return _fused_bn_fn(jnp.dtype(dtype), float(epsilon), x.ndim)(
        x, scale, bias)
