"""A frozen token encoder of the DeepSeek-V3 family (multi-head latent
attention, a sigmoid group-limited router over sparse experts) under a
linear head: the second implementer of the backbone contract
(models/backbone.py), after the ResNets.

The layer equations are those of arXiv:2412.19437 section 2.1.  Block l:
``u = h + MLA(RMSNorm(h))``, ``h' = u + F_l(RMSNorm(u))``; ``F_l`` is a
dense SwiGLU for the first ``first_k_dense`` layers and the expert layer
after them.  As an encoder: ``h_0`` the embedding table's rows (the model's
``input_stage``), the embedding of a row ``RMSNorm_final(h_L)`` at the
row's last token, the logits a float32 linear head over it (``linear``,
named as the ResNets name theirs).  No language-model head is held.

**The expert layer is told which experts it holds** (``held_first``,
``held_count``): it routes every token over ALL ``n_routed_experts`` and
computes the part of the result that its own experts give, for every token
routed to them — no token is dropped whatever the routing — plus the
shared expert, whole.  That is what expert parallelism asks of a chip; on
one chip the layer runs without the exchange, and what the absent experts
would have added is left out.  The held experts' work runs over the
(token, expert) pairs that were chosen: each held expert's tokens take
slots in tiles of ``expert_tile`` rows, a chunk of slots of every held
expert is gathered once and multiplied by all their kernels in one batched
matmul a projection, and the rows come back without a scatter of every
slot (``run_held_pairs``).  The chunk is sized from the layer's shapes for
the load an even routing gives; a loop whose trip count the program reads
off the routing takes a busier expert's tokens a chunk at a time, so no
token is dropped and the matmuls are shaped for the load, not the worst
case.

Precision: encoder leaves are stored bfloat16; contractions take
``dtype`` operands (bfloat16 on the TPU) and accumulate in float32;
RMSNorm, the softmax, the router (its matmul included) and the head are
float32.  The attention's causal core runs as one kernel where the program
is lowered for the TPU on one device (``fused_causal_core``,
ops/attention.py: the scores stay on chip and no block above the diagonal
is computed) and as einsums elsewhere (``causal_core``), in the same
precisions.  Kernels lie in the checkpoint's ``[out, in]`` layout, so a
file is uploaded as it is read.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import re
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..registry import MODELS
from .resnet import dense_kernel_init

_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    """One encoder.  Names follow the published ``config.json`` where it
    has one; ``held_first`` / ``held_count`` (this chip's experts),
    ``vocab_size`` (this chip's slice) and ``expert_tile`` (the rows of a
    held expert's tokens one tile takes: its slots are rounded up to
    tiles) are this program's."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    n_group: int
    topk_group: int
    routed_scaling_factor: float
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    held_first: int
    held_count: int
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 32.0
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    expert_tile: int = 512
    # Factors on the two low-rank paths of the attention (models/
    # shortcut_moe.py states them; 1 here, as published).
    mla_q_scale: float = 1.0
    mla_kv_scale: float = 1.0

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return self.qk_head_dim ** -0.5 * m * m


# A.X-K1 (https://huggingface.co/skt/A.X-K1/blob/main/config.json), cut to
# one chip's share of a 16-way expert-parallel deployment: 7 of 61 layers
# (the leading dense one and six expert layers), experts 0-11 of 192, ids
# 0-20479 of 163840.  Every width as published.
AXK1_EP16_L7 = MlaMoeConfig(
    vocab_size=20480, hidden_size=7168, num_hidden_layers=7,
    first_k_dense_replace=1, intermediate_size=18432,
    moe_intermediate_size=2048, n_routed_experts=192, n_shared_experts=1,
    num_experts_per_tok=8, n_group=8, topk_group=4,
    routed_scaling_factor=2.5, num_attention_heads=64, q_lora_rank=1536,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, held_first=0, held_count=12)

# The same block at toy widths, for the CPU: 16 experts in 4 groups, 4 held.
AXK1_TOY = MlaMoeConfig(
    vocab_size=256, hidden_size=64, num_hidden_layers=3,
    first_k_dense_replace=1, intermediate_size=160,
    moe_intermediate_size=32, n_routed_experts=16, n_shared_experts=1,
    num_experts_per_tok=4, n_group=4, topk_group=2,
    routed_scaling_factor=2.5, num_attention_heads=4, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, held_first=0, held_count=4, rope_factor=4.0,
    rope_original_max_position=16, expert_tile=8)


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg: MlaMoeConfig) -> np.ndarray:
    """The rotary frequencies under yarn scaling, one per pair."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    pos = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra, inter = 1.0 / base ** pos, 1.0 / (cfg.rope_factor * base ** pos)

    def correction_dim(rotations):
        return (dim * math.log(cfg.rope_original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rope_tables(cfg: MlaMoeConfig, length: int):
    """(cos, sin) ``[length, rope_dim / 2]`` float32."""
    ang = np.arange(length, dtype=np.float32)[:, None] * yarn_inv_freq(cfg)
    m = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
         / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    return jnp.asarray(np.cos(ang) * m), jnp.asarray(np.sin(ang) * m)


def apply_rope(x, cos, sin):
    """Rotate the adjacent pairs of the last axis; ``x`` is
    ``[B, T, ..., rope_dim]`` float32 and the tables ``[T, rope_dim/2]``."""
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (cos.shape[-1],)
    c, s = cos.reshape(shape), sin.reshape(shape)
    return jnp.stack([a * c - b * s, a * s + b * c], axis=-1).reshape(x.shape)


def rms_norm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def _fan_in_normal(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * shape[-1] ** -0.5).astype(dtype)


def _ones(key, shape, dtype):
    return jnp.ones(shape, dtype)


def _embed_normal(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def route(p, cfg: MlaMoeConfig):
    """``p`` [N, n_routed_experts] float32 scores -> (chosen expert ids
    [N, k], their gates [N, k]): the ``topk_group`` best groups by the sum
    of their two largest scores, the k largest scores inside them, gates
    renormalised over the chosen and scaled."""
    n = p.shape[0]
    groups = p.reshape(n, cfg.n_group, -1)
    group_score = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)
    _, best = jax.lax.top_k(group_score, cfg.topk_group)
    keep = jnp.zeros((n, cfg.n_group), bool).at[
        jnp.arange(n)[:, None], best].set(True)
    masked = jnp.where(jnp.repeat(keep, groups.shape[-1], axis=1), p, 0.0)
    _, idx = jax.lax.top_k(masked, cfg.num_experts_per_tok)
    w = jnp.take_along_axis(p, idx, axis=1)
    return idx, cfg.routed_scaling_factor * w / jnp.sum(w, axis=1,
                                                        keepdims=True)


def _dot(x, w, dtype, spec="...i,oi->...o"):
    """``x [..., in] @ w[out, in]^T`` (or ``spec``'s contraction):
    ``dtype`` operands, float32 accumulation."""
    return jnp.einsum(spec, x.astype(dtype), w.astype(dtype),
                      precision=_HI, preferred_element_type=jnp.float32)


def _swiglu(x, gate, up, down, dtype):
    h = jax.nn.silu(_dot(x, gate, dtype)) * _dot(x, up, dtype)
    return _dot(h, down, dtype)


class _Part(nn.Module):
    """A part of a block: kernels in the checkpoint's ``[out, in]`` layout
    (or a stack of them) and norm scales, stored bfloat16 under the
    checkpoint's own names."""

    cfg: MlaMoeConfig
    dtype: Any

    def weight(self, name: str, *shape: int):
        return self.param(name, _fan_in_normal, shape, jnp.bfloat16)

    def scale(self, name: str, width: int):
        return self.param(name, _ones, (width,), jnp.bfloat16)


def causal_core(q, kv, k_r, *, scale: float, dtype):
    """Causal attention over ``q`` ``[B, T, H, d_nope + r]`` (rotary part
    last), ``kv`` ``[B, T, H, d_nope + d_v]`` (key part, then value) and
    the one rotary key ``k_r`` ``[B, T, r]`` every head shares, float32:
    the scores as one ``[B, H, T, T]`` float32 array, scaled, masked and
    normalised in float32, the probabilities cast to ``dtype`` for the
    product with the values.  Returns (``[B, T, H, d_v]`` in ``dtype``, 0 a
    row: no call ran the fused kernel)."""
    b, t, heads = q.shape[:3]
    rope = k_r.shape[-1]
    nope = q.shape[-1] - rope
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_r[:, :, None, :], (b, t, heads, rope))], axis=-1)
    scores = jnp.einsum("bthd,bshd->bhts", q.astype(dtype),
                        k.astype(dtype), precision=_HI,
                        preferred_element_type=jnp.float32)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(
        jnp.where(causal, scores * scale, -jnp.inf), axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", probs.astype(dtype),
                     kv[..., nope:].astype(dtype), precision=_HI,
                     preferred_element_type=jnp.float32)
    return out.astype(dtype), jnp.zeros((b,), jnp.int32)


def fused_causal_core(q, kv, k_r, *, scale: float, dtype,
                      interpret: bool = False):
    """``causal_core`` as one kernel (ops/attention.py): the same steps in
    the same precisions, the scores kept on chip and no block above the
    diagonal computed, over the projections' own layouts.  Returns 1 a row: the
    call ran it."""
    from ..ops import attention  # Pallas: imported by the programs that run it
    b, t, heads = q.shape[:3]
    rope = k_r.shape[-1]
    nope = q.shape[-1] - rope
    d_v = kv.shape[-1] - nope

    def padded(a):  # the rotary part, zero-padded to whole vregs
        return jnp.pad(a, [(0, 0)] * (a.ndim - 1)
                       + [(0, attention.lanes(rope) - rope)])
    q = jnp.concatenate([q[..., :nope], padded(q[..., nope:])], axis=-1)
    out = attention.causal_attention(
        q.astype(dtype).reshape(b, t, -1), kv.astype(dtype).reshape(b, t, -1),
        padded(k_r).astype(dtype), heads=heads, d_nope=nope, d_v=d_v,
        scale=scale, interpret=interpret)
    return out.reshape(b, t, heads, d_v), jnp.ones((b,), jnp.int32)


class _Attention(_Part):
    """Multi-head latent attention over normalised rows ``x`` [B, T, d]."""

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg, dtype = self.cfg, self.dtype
        d, heads = cfg.hidden_size, cfg.num_attention_heads
        nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        b, t = x.shape[:2]
        c_q = rms_norm(
            _dot(x, self.weight("q_a_proj", cfg.q_lora_rank, d), dtype),
            self.scale("q_a_layernorm", cfg.q_lora_rank), cfg.rms_norm_eps)
        q = _dot(c_q, self.weight("q_b_proj", heads * cfg.qk_head_dim,
                                  cfg.q_lora_rank), dtype
                 ).reshape(b, t, heads, cfg.qk_head_dim)
        if cfg.mla_q_scale != 1.0:
            q = q * cfg.mla_q_scale
        kv_a = _dot(x, self.weight("kv_a_proj_with_mqa",
                                   cfg.kv_lora_rank + rope, d), dtype)
        c_kv = rms_norm(kv_a[..., :cfg.kv_lora_rank],
                        self.scale("kv_a_layernorm", cfg.kv_lora_rank),
                        cfg.rms_norm_eps)
        if cfg.mla_kv_scale != 1.0:
            c_kv = c_kv * cfg.mla_kv_scale
        kv = _dot(c_kv, self.weight("kv_b_proj", heads * (nope + vd),
                                    cfg.kv_lora_rank), dtype
                  ).reshape(b, t, heads, nope + vd)
        # RoPE on the decoupled parts only; the key's is one vector that
        # every head shares.
        q_r = apply_rope(q[..., nope:], cos, sin)
        k_r = apply_rope(kv_a[..., cfg.kv_lora_rank:], cos, sin)
        q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
        # The causal core: one fused kernel where the program is lowered
        # for the TPU on a process of one device and a grid step holds a
        # head; the einsum form elsewhere, which the compiler partitions
        # over a mesh where it cannot partition the kernel.  The count of
        # calls that ran the kernel, a row each, is a counter.
        how = dict(scale=cfg.softmax_scale, dtype=dtype)
        cores = {"default": functools.partial(causal_core, **how)}
        if jax.device_count() == 1:
            from ..ops import attention
            if attention.heads_per_step(heads, t, nope, rope, vd,
                                        jnp.dtype(dtype).itemsize):
                cores["tpu"] = functools.partial(fused_causal_core, **how)
        out, fused = jax.lax.platform_dependent(q, kv, k_r, **cores)
        self.sow("counters", "attn_fused", fused)
        return _dot(out.reshape(b, t, heads * vd),
                    self.weight("o_proj", d, heads * vd), dtype)


class _DenseFFN(_Part):
    @nn.compact
    def __call__(self, x):
        d, f = self.cfg.hidden_size, self.cfg.intermediate_size
        return _swiglu(x, self.weight("gate_proj", f, d),
                       self.weight("up_proj", f, d),
                       self.weight("down_proj", d, f), self.dtype)


def held_gates(idx, gate, held_first: int, held: int):
    """This chip's part of a routing: ``idx`` / ``gate`` [N, k] (chosen
    expert ids, their gates) -> gate[n, e] of held expert e, 0 where token n
    did not choose it."""
    n = idx.shape[0]
    local = idx - held_first
    mine = (local >= 0) & (local < held)
    return jnp.zeros((n, held), jnp.float32).at[
        jnp.arange(n)[:, None], jnp.where(mine, local, held)
    ].add(jnp.where(mine, gate, 0.0), mode="drop")


def chunk_rows(n: int, picks: int, outputs: int, tile: int) -> int:
    """The rows each held expert takes in one chunk, from the layer's
    shapes: the tokens an even routing sends an expert (``n x picks /
    outputs``) and three standard deviations of a count that random, in
    whole tiles, and never more tiles than ``n`` tokens fill."""
    tile = min(tile, n)
    even = n * picks / outputs
    want = -(-math.ceil(even + 3 * math.sqrt(even)) // tile)
    return min(want, -(-n // tile)) * tile


def run_held_pairs(flat, gates, stacks, tile: int, chunk: int, dtype):
    """The held experts' work over the (token, expert) pairs that were
    chosen: ``flat`` [N, d] tokens, ``gates`` [N, held] (``held_gates``),
    ``stacks`` the experts' (gate, up, down) kernels stacked ``[held, ...]``.
    Each expert's tokens take slots rounded up to tiles of ``tile`` rows.
    A chunk is ``chunk`` slots of every held expert (``chunk_rows``): its
    tokens are gathered once and multiplied by all the held experts'
    kernels at once, one batched matmul a projection.  A token's first held
    pick is taken back by a gather over the tokens, its later picks (a
    token may choose several held experts) are added a tile of rows at a
    time.  A loop whose trip count is read off the routing runs the
    chunks, so no token is dropped whatever the routing.  Returns (the
    gated sum [N, d] float32, the slots the tiles were shaped for, the
    chunks run)."""
    n, d = flat.shape
    held = gates.shape[1]
    tile = min(tile, n)
    with jax.named_scope("moe_route"):
        chosen = gates > 0
        count = jnp.sum(chosen, axis=0).astype(jnp.int32)
        # Per expert its tokens first, in row order; a token's place among
        # an expert's tokens; its picks numbered in expert order.
        order = jnp.argsort(jnp.where(chosen.T, 0, 1), axis=1,
                            stable=True).astype(jnp.int32)
        place = jnp.cumsum(chosen, axis=0, dtype=jnp.int32) - 1
        rank = jnp.cumsum(chosen, axis=1, dtype=jnp.int32) - 1
        picked = jnp.any(chosen, axis=1)
        first = jnp.argmax(chosen, axis=1).astype(jnp.int32)
        first_place = jnp.take_along_axis(place, first[:, None], axis=1)[:, 0]
        slots = jnp.sum(-(-count // tile) * tile)
        trips = -(-jnp.max(count) // chunk)
    with jax.named_scope("moe_experts"):
        xs = flat.astype(dtype)

        def one_chunk(c, acc):
            k = c * chunk + jnp.arange(chunk, dtype=jnp.int32)
            real = k[None, :] < count[:, None]                 # [held, chunk]
            # A slot no token fills reads row n: the gather fills it with
            # zeros, the add drops it.
            rows = jnp.where(real, order[:, jnp.minimum(k, n - 1)], n)
            xt = jnp.take(xs, rows, axis=0, mode="fill", fill_value=0)
            h = (jax.nn.silu(_dot(xt, stacks[0], dtype, "ecd,efd->ecf"))
                 * _dot(xt, stacks[1], dtype, "ecd,efd->ecf"))
            y = _dot(h, stacks[2], dtype, "ecf,edf->ecd")
            g = jnp.where(real, jnp.take_along_axis(
                gates.T, jnp.minimum(rows, n - 1), axis=1), 0.0)
            z = (y * g[..., None]).reshape(held * chunk, d)
            # First picks: each token reads its row back, no scatter.
            at = first_place - c * chunk
            mine = picked & (at >= 0) & (at < chunk)
            acc = acc + jnp.where(mine[:, None], jnp.take(
                z, first * chunk + jnp.clip(at, 0, chunk - 1), axis=0), 0.0)
            # Later picks, packed to the front and added a tile at a time.
            later = (real & (jnp.take_along_axis(
                rank.T, jnp.minimum(rows, n - 1), axis=1) > 0)).reshape(-1)
            packed = jnp.argsort(~later, stable=True).astype(jnp.int32)
            n_later = jnp.sum(later)
            flat_rows = rows.reshape(-1)

            def one_tile(i, acc):
                j = i * tile + jnp.arange(tile, dtype=jnp.int32)
                at = jnp.take(packed, jnp.minimum(j, held * chunk - 1))
                to = jnp.where(j < n_later, flat_rows[at], n)
                return acc.at[to].add(jnp.take(z, at, axis=0), mode="drop")

            return jax.lax.fori_loop(0, -(-n_later // tile), one_tile, acc)

        routed = jax.lax.fori_loop(0, trips, one_chunk,
                                   jnp.zeros((n, d), jnp.float32))
    return routed, slots.astype(jnp.int32), trips.astype(jnp.int32)


class _Experts(_Part):
    """The expert layer of one chip: routes ``x`` [B, T, d] over all the
    experts, computes the held ones' part and the shared expert."""

    @nn.compact
    def __call__(self, x):
        cfg, dtype = self.cfg, self.dtype
        d, f, held = cfg.hidden_size, cfg.moe_intermediate_size, cfg.held_count
        b, t = x.shape[:2]
        n = b * t
        flat = x.reshape(n, d)
        router = self.weight("gate", cfg.n_routed_experts, d)
        stacks = (self.weight("experts_gate_proj", held, f, d),
                  self.weight("experts_up_proj", held, f, d),
                  self.weight("experts_down_proj", held, d, f))
        fs = f * cfg.n_shared_experts
        with jax.named_scope("moe_route"):
            p = jax.nn.sigmoid(jnp.einsum(
                "ni,oi->no", flat, router.astype(jnp.float32),
                precision=_HI))
            idx, gate = route(p, cfg)
            gates = held_gates(idx, gate, cfg.held_first, held)
        routed, pairs_run, trips = run_held_pairs(
            flat, gates, stacks, cfg.expert_tile,
            chunk_rows(n, cfg.num_experts_per_tok, cfg.n_routed_experts,
                       cfg.expert_tile), dtype)
        with jax.named_scope("moe_experts"):
            shared = _swiglu(
                flat, self.weight("shared_experts_gate_proj", fs, d),
                self.weight("shared_experts_up_proj", fs, d),
                self.weight("shared_experts_down_proj", d, fs), dtype)
        # What the device did, as counters: the chosen-and-held pairs of
        # each row, the slots the tiles were shaped for, the chunks run.
        self.sow("counters", "pairs_real", jnp.sum(
            (gates > 0).reshape(b, t * held), axis=1).astype(jnp.int32))
        self.sow("counters", "pairs_run", pairs_run)
        self.sow("counters", "expert_trips", trips)
        return (routed + shared).reshape(b, t, d)


class _Block(_Part):
    layer: int = 0

    @nn.compact
    def __call__(self, h, cos, sin):
        cfg = self.cfg
        with jax.named_scope("mla"):
            u = h + _Attention(cfg, self.dtype, name="self_attn")(
                rms_norm(h, self.scale("input_layernorm", cfg.hidden_size),
                         cfg.rms_norm_eps), cos, sin)
        x = rms_norm(u, self.scale("post_attention_layernorm",
                                   cfg.hidden_size), cfg.rms_norm_eps)
        if self.layer < cfg.first_k_dense_replace:
            with jax.named_scope("dense_ffn"):
                return u + _DenseFFN(cfg, self.dtype, name="mlp")(x)
        return u + _Experts(cfg, self.dtype, name="mlp")(x)


class MlaMoeEncoder(nn.Module):
    cfg: MlaMoeConfig
    dtype: Any = jnp.float32

    block_cls = _Block

    def setup(self):
        cfg = self.cfg
        self.embed_tokens = self.param(
            "embed_tokens", _embed_normal,
            (cfg.vocab_size, cfg.hidden_size), jnp.bfloat16)
        self.layers = [self.block_cls(cfg, self.dtype, layer)
                       for layer in range(cfg.num_hidden_layers)]
        self.norm = self.param("norm", _ones, (cfg.hidden_size,),
                               jnp.bfloat16)

    def embed(self, ids):
        """Rows of token ids ``[B, T]`` -> ``h_0`` ``[B, T, d]`` float32."""
        return jnp.take(self.embed_tokens, ids.astype(jnp.int32),
                        axis=0).astype(jnp.float32)

    def rope(self, length: int):
        return rope_tables(self.cfg, length)

    def __call__(self, h):
        cos, sin = self.rope(h.shape[1])
        for block in self.layers:
            h = block(h, cos, sin)
        return rms_norm(h[:, -1], self.norm, self.cfg.rms_norm_eps)


class MlaMoeClassifier(nn.Module):
    """Encoder + a float32 linear head, with the ResNets' forward modes:
    ``apply(vars, x)`` -> logits; ``return_features=True`` -> (logits,
    embedding); ``method="head"`` -> logits from an embedding.  ``x`` is a
    batch of token rows ``[B, T]`` or what ``input_stage`` made of it."""

    cfg: MlaMoeConfig
    num_classes: int
    dtype: Any = jnp.float32

    encoder_cls = MlaMoeEncoder

    # The backbone contract's optional parts (models/backbone.py).
    row_counters = ("pairs_real", "pairs_run", "expert_trips", "attn_fused")

    # A forward-only encoder: linear evaluation is the one protocol it runs
    # (the registry's factory refuses to build it without
    # ``freeze_feature``), so the frozen set is a constant of the class.
    freeze_feature = True
    frozen_prefixes = ("encoder",)

    def setup(self):
        self.encoder = self.encoder_cls(self.cfg, self.dtype,
                                        name="encoder")
        self.linear = nn.Dense(
            self.num_classes, kernel_init=dense_kernel_init,
            bias_init=nn.initializers.zeros, name="linear")

    def input_stage(self, ids):
        return self.encoder.embed(ids)

    def __call__(self, x, train: bool = True, return_features: bool = False):
        if x.ndim == 2:
            x = self.input_stage(x)
        embedding = jax.lax.stop_gradient(self.encoder(x))
        logits = self.linear(embedding)
        if return_features:
            return logits, embedding
        return logits

    def head(self, embedding):
        return self.linear(embedding)

    @property
    def embed_dim(self) -> int:
        return self.cfg.hidden_size

    def torch_key_to_flax(self, key: str) -> Optional[Tuple]:
        """The checkpoint layout: the published names (``model.layers.N.
        self_attn.q_a_proj.weight`` ...) and this repo's head
        (``linear.weight``, ``linear.bias``).  An expert another chip
        holds maps to nothing; a held one to its slot of the stack."""
        if key == "linear.weight":
            return (("params", "linear", "kernel"), "dense")
        if key == "linear.bias":
            return (("params", "linear", "bias"), None)
        enc = ("params", "encoder")
        if key == "model.embed_tokens.weight":
            return (enc + ("embed_tokens",), None)
        if key == "model.norm.weight":
            return (enc + ("norm",), None)
        m = re.fullmatch(r"model\.layers\.(\d+)\.(.+)\.weight", key)
        if not m:
            raise KeyError(f"No Flax mapping for torch key '{key}'")
        block, rest = enc + (f"layers_{int(m.group(1))}",), m.group(2)
        e = re.fullmatch(r"mlp\.experts\.(\d+)\.(\w+)", rest)
        if e:
            slot = int(e.group(1)) - self.cfg.held_first
            if not 0 <= slot < self.cfg.held_count:
                return None
            return (block + ("mlp", f"experts_{e.group(2)}"), ("slot", slot))
        # Every other tensor sits where the checkpoint names it.
        part, _, name = rest.partition(".")
        return (block + ((part, name.replace(".", "_")) if name
                         else (part,)), None)


def forward_only_factory(name: str, cfg, classifier=MlaMoeClassifier):
    def make(num_classes: int, freeze_feature: bool = False,
             dtype: Any = jnp.float32, **_image_model_options):
        if not freeze_feature:
            # No fit moves this encoder: its leaves are stored bfloat16, the
            # expert layer's loops read their trip counts off the routing
            # (no reverse mode), and 12 bytes a parameter of float32
            # weight, gradient and momentum do not exist for billions of
            # leaves.
            raise ValueError(
                f"model {name} is a forward-only encoder: it runs linear "
                f"evaluation only; pass --freeze_feature")
        return classifier(cfg, num_classes, dtype=dtype)
    return make


MODELS.register("AXK1_EP16_L7",
                forward_only_factory("AXK1_EP16_L7", AXK1_EP16_L7))
MODELS.register("AXK1_TOY", forward_only_factory("AXK1_TOY", AXK1_TOY))
