"""Causal multi-head latent attention as one Pallas kernel: the scores of a
head never leave the chip, and no block above the diagonal is computed.

``causal_attention`` reads the attention's operands in the layouts the
projections write them, one row ``[T, heads x width]`` at a time, so
nothing is transposed or copied per head on the way in or out:

  * ``q`` ``[B, T, H x (d_nope + r)]``: each head's query, its rotary part
    zero-padded to ``r``, a whole number of 128 lanes;
  * ``kv`` ``[B, T, H x (d_nope + d_v)]``: each head's key (the part
    without rotary positions) then its value, as ``kv_b_proj`` makes them;
  * ``k_rope`` ``[B, T, r]``: the one rotary key every head shares,
    zero-padded as the queries' part.

It returns ``[B, T, H x d_v]``, the layout ``o_proj`` reads.  A grid step
holds ``heads_per_step`` heads of one row whole in VMEM.  For each block of
``BLOCK_Q`` queries it takes the scores against the keys up to the block's
last position (``[BLOCK_Q, (i + 1) x BLOCK_Q]``: the key part's product and
the rotary part's, summed), scales them in float32, masks the diagonal
block, and runs the softmax over the whole row at once: float32 max, exp,
sum and normalise, the probabilities cast to the operands' dtype for the
product with the values, which accumulates in float32.  Those are the
steps and precisions of the einsum form it replaces (models/mla_moe.py
``causal_core``); only the blocks above the diagonal are never formed,
and the sums may add in another order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# A block of queries; a sequence that is not a whole number of blocks is
# one block.  256 ran the cells' 16 x 512 rows 0.4-0.5 ms a call faster
# than 128 on a v5e (PERF.md section 6).
BLOCK_Q = 256
# What one grid step may hold in VMEM, of the 16 MiB a kernel is given by
# default on a v5e: the q, kv and output blocks of its heads and the shared
# rotary key, each double-buffered, and a block's float32 scores.
VMEM_BYTES = 12 * 2 ** 20

# Where this module compiles (al_lint recompile-hazard): the kernel's call,
# once a shape.
_STEP_BUILDERS = ("causal_attention",)


def lanes(width: int) -> int:
    """``width`` rounded up to whole 128-lane vregs."""
    return -(-width // LANES) * LANES


def _block_q(t: int) -> int:
    return BLOCK_Q if t % BLOCK_Q == 0 else t


def heads_per_step(heads: int, t: int, d_nope: int, d_rope: int, d_v: int,
                   itemsize: int) -> int:
    """The most heads (a divisor of ``heads``) one grid step holds whole in
    VMEM; 0 where the kernel cannot run: a key or value width that is not
    whole vregs (its slices would not be lane-aligned), or not one head
    that fits."""
    if d_nope % LANES or d_v % LANES:
        return 0
    r = lanes(d_rope)
    room = VMEM_BYTES - 3 * _block_q(t) * t * 4 - 2 * t * r * itemsize
    per_head = 2 * t * (2 * d_nope + r + 2 * d_v) * itemsize
    return max((h for h in range(1, heads + 1)
                if heads % h == 0 and h * per_head <= room), default=0)


def _kernel(q_ref, kv_ref, kr_ref, o_ref, *, scale: float, heads: int,
            d_nope: int, d_v: int):
    t = q_ref.shape[1]
    dq, dkv = q_ref.shape[2] // heads, kv_ref.shape[2] // heads
    block_q = _block_q(t)
    nt = (((1,), (1,)), ((), ()))
    for h in range(heads):
        key = slice(h * dkv, h * dkv + d_nope)
        value = slice(h * dkv + d_nope, (h + 1) * dkv)
        for i in range(t // block_q):
            lo, n = i * block_q, (i + 1) * block_q
            q = q_ref[0, lo:n, h * dq:(h + 1) * dq]
            s = (jax.lax.dot_general(q[:, :d_nope], kv_ref[0, :n, key], nt,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(q[:, d_nope:], kr_ref[0, :n, :], nt,
                                       preferred_element_type=jnp.float32)
                 ) * scale
            row = lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(col <= row, s, -jnp.inf)
            p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
            p = p / jnp.sum(p, axis=-1, keepdims=True)
            o_ref[0, lo:n, h * d_v:(h + 1) * d_v] = jax.lax.dot_general(
                p.astype(kv_ref.dtype), kv_ref[0, :n, value],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(o_ref.dtype)


# Jitted so that a forward's calls of one shape trace and lower the kernel
# once: unrolled over heads and blocks, it is a few thousand operations,
# and tracing it at every attention call of every program cost set-up
# more time than compiling them (PERF.md section 6).
@functools.partial(jax.jit, static_argnames=("heads", "d_nope", "d_v",
                                             "scale", "interpret"))
def causal_attention(q, kv, k_rope, *, heads: int, d_nope: int, d_v: int,
                     scale: float, interpret: bool = False):
    """The kernel over the operands the module docstring lays out
    (``heads_per_step`` of them not 0), on one device: the compiler cannot
    partition a Mosaic kernel over a mesh."""
    b, t, _ = q.shape
    r = k_rope.shape[-1]
    step = heads_per_step(heads, t, d_nope, r, d_v, q.dtype.itemsize)
    block_q = _block_q(t)
    pairs = (t // block_q) * (t // block_q + 1) // 2 * block_q * block_q
    rows = functools.partial(pl.BlockSpec, index_map=lambda i, j: (i, 0, j))
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, heads=step, d_nope=d_nope,
                          d_v=d_v),
        out_shape=jax.ShapeDtypeStruct((b, t, heads * d_v), q.dtype),
        grid=(b, heads // step),
        in_specs=[rows((1, t, step * (d_nope + r))),
                  rows((1, t, step * (d_nope + d_v))),
                  pl.BlockSpec((1, t, r), lambda i, j: (i, 0, 0))],
        out_specs=rows((1, t, step * d_v)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * heads * pairs * (d_nope + r + d_v),
            transcendentals=b * heads * pairs,
            bytes_accessed=(q.size + kv.size + k_rope.size
                            + b * t * heads * d_v) * q.dtype.itemsize),
        interpret=interpret,
    )(q, kv, k_rope)

