"""A frozen token encoder of the LongCat-Flash family (arXiv:2509.01322):
shortcut-connected mixture of experts, zero-compute experts, multi-head
latent attention with rescaled low-rank paths.  The third implementer of the
backbone contract (models/backbone.py) and the second token encoder: what it
shares with models/mla_moe.py it imports from there (the attention, the dense
SwiGLU, the held experts' batched matmuls, the classifier's head and freeze
contract).

One "layer" of the published config is a **double layer**: two attention
sub-layers and two dense feed-forwards in series, and ONE expert layer that
reads the first sub-layer's post-attention norm and joins the residual
stream only at the END of the second sub-layer (the shortcut).  Input h:

    a0 = h  + MLA_0(RMSNorm_in0(h))
    x0 = RMSNorm_post0(a0)
    m  = MoE(x0)
    b0 = a0 + FFN_0(x0)
    a1 = b0 + MLA_1(RMSNorm_in1(b0))
    h' = a1 + FFN_1(RMSNorm_post1(a1)) + m

so the expert branch and the chain FFN_0 -> MLA_1 -> FFN_1 do not depend on
each other.  ``MLA_i`` is models/mla_moe.py's attention with the query
scaled by (d / q_lora_rank)^0.5 behind ``q_b_proj`` and the normalised key /
value latent by (d / kv_lora_rank)^0.5 in front of ``kv_b_proj``; plain
RoPE (no yarn), softmax scale qk_head_dim^-0.5.

**The expert layer.**  The router has ``n_routed_experts + zero_expert_num``
outputs: ``p = softmax(W_r x)`` over all of them, the picks are the
``moe_topk`` largest of ``p + bias`` (a per-expert correction that enters the
choice only), a pick's gate is ``routed_scaling_factor * p`` (not
renormalised).  A pick among the first ``n_routed_experts`` is a SwiGLU
expert; a pick among the last ``zero_expert_num`` is an identity
(``gate * x``): it holds no weight, lives on no chip and is computed where
the token is.  As in models/mla_moe.py the layer is told which experts it
holds (``held_first``, ``held_count``), routes over ALL the outputs, runs its
own (token, expert) pairs through ``mla_moe.run_held_pairs`` — no token
dropped whatever the routing — adds the zero-expert term for every token,
and leaves out what the absent experts would have added.

Precision as models/mla_moe.py states it; the router (matmul, softmax, bias,
top-k) and the zero-expert term are float32, the correction bias is a
float32 leaf among the frozen bfloat16 ones.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..registry import MODELS
from . import mla_moe
from .mla_moe import _HI, _Attention, _DenseFFN, _Part, rms_norm


@dataclasses.dataclass(frozen=True)
class ShortcutMoeConfig:
    """One encoder.  Names follow the published ``config.json``;
    ``held_first`` / ``held_count`` (this chip's experts), ``vocab_size``
    (this chip's slice) and ``expert_tile`` (the rows of a held expert's
    tokens one tile takes) are this program's."""

    vocab_size: int
    hidden_size: int
    ffn_hidden_size: int
    expert_ffn_hidden_size: int
    num_layers: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed_experts: int
    zero_expert_num: int
    moe_topk: int
    routed_scaling_factor: float
    held_first: int
    held_count: int
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    expert_tile: int = 128

    # What the shared parts of models/mla_moe.py read, under their names.
    @property
    def intermediate_size(self) -> int:
        return self.ffn_hidden_size

    @property
    def num_hidden_layers(self) -> int:
        return self.num_layers

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5

    @property
    def mla_q_scale(self) -> float:
        return ((self.hidden_size / self.q_lora_rank) ** 0.5
                if self.mla_scale_q_lora else 1.0)

    @property
    def mla_kv_scale(self) -> float:
        return ((self.hidden_size / self.kv_lora_rank) ** 0.5
                if self.mla_scale_kv_lora else 1.0)

    @property
    def router_outputs(self) -> int:
        return self.n_routed_experts + self.zero_expert_num


# LongCat-Flash-Chat (https://huggingface.co/meituan-longcat/LongCat-Flash-
# Chat/blob/main/config.json), cut to one chip's share of a 32-way
# expert-parallel deployment: 4 of 28 double layers, experts 0-15 of 512
# (the router keeps its 768 outputs), ids 0-16383 of 131072.  Every width
# as published.
LONGCAT_FLASH_EP32_L4 = ShortcutMoeConfig(
    vocab_size=16384, hidden_size=6144, ffn_hidden_size=12288,
    expert_ffn_hidden_size=2048, num_layers=4, num_attention_heads=64,
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, n_routed_experts=512,
    zero_expert_num=256, moe_topk=12, routed_scaling_factor=6.0,
    held_first=0, held_count=16)

# The same block at toy widths, for the CPU: 16 routed + 8 zero experts, 4
# held, 4 a token.
LONGCAT_FLASH_TOY = ShortcutMoeConfig(
    vocab_size=256, hidden_size=64, ffn_hidden_size=160,
    expert_ffn_hidden_size=32, num_layers=3, num_attention_heads=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16,
    zero_expert_num=8, moe_topk=4, routed_scaling_factor=6.0,
    held_first=0, held_count=4, expert_tile=8)


def rope_tables(cfg: ShortcutMoeConfig, length: int):
    """(cos, sin) ``[length, rope_dim / 2]`` float32 of plain RoPE."""
    dim = cfg.qk_rope_head_dim
    inv_freq = (1.0 / cfg.rope_theta ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)).astype(np.float32)
    ang = np.arange(length, dtype=np.float32)[:, None] * inv_freq
    return jnp.asarray(np.cos(ang)), jnp.asarray(np.sin(ang))


def route(p, bias, cfg: ShortcutMoeConfig):
    """``p`` [N, router_outputs] float32 softmax scores -> (chosen ids
    [N, k], their gates [N, k]): the k largest of ``p + bias``, each gated
    by its own ``p`` times the scale, not renormalised."""
    _, idx = jax.lax.top_k(p + bias, cfg.moe_topk)
    return idx, cfg.routed_scaling_factor * jnp.take_along_axis(p, idx,
                                                                axis=1)


def _zeros_f32(key, shape, dtype):
    return jnp.zeros(shape, jnp.float32)


class _ShortcutExperts(_Part):
    """The expert layer of one chip: routes ``x`` [B, T, d] over the routed
    and the zero experts, computes the held ones' part and the zero-expert
    term."""

    @nn.compact
    def __call__(self, x):
        cfg, dtype = self.cfg, self.dtype
        d, f, held = (cfg.hidden_size, cfg.expert_ffn_hidden_size,
                      cfg.held_count)
        b, t = x.shape[:2]
        n = b * t
        flat = x.reshape(n, d)
        router = self.weight("router_classifier", cfg.router_outputs, d)
        bias = self.param("router_e_score_correction_bias", _zeros_f32,
                          (cfg.router_outputs,), jnp.float32)
        stacks = (self.weight("experts_gate_proj", held, f, d),
                  self.weight("experts_up_proj", held, f, d),
                  self.weight("experts_down_proj", held, d, f))
        with jax.named_scope("moe_route"):
            p = jax.nn.softmax(jnp.einsum(
                "ni,oi->no", flat, router.astype(jnp.float32),
                precision=_HI), axis=-1)
            idx, gate = route(p, bias, cfg)
            gates = mla_moe.held_gates(idx, gate, cfg.held_first, held)
            zero_pick = idx >= cfg.n_routed_experts
            zero_gate = jnp.sum(jnp.where(zero_pick, gate, 0.0), axis=1)
        routed, pairs_run, trips = mla_moe.run_held_pairs(
            flat, gates, stacks, cfg.expert_tile,
            mla_moe.chunk_rows(n, cfg.moe_topk, cfg.router_outputs,
                               cfg.expert_tile), dtype)
        with jax.named_scope("moe_zero"):
            zero = zero_gate[:, None] * flat
        # Counters, a row each but the slots the tiles were shaped for and
        # the chunks run: held pairs, picks that fell on a zero expert, all
        # picks.
        self.sow("counters", "pairs_real", jnp.sum(
            (gates > 0).reshape(b, t * held), axis=1).astype(jnp.int32))
        self.sow("counters", "pairs_run", pairs_run)
        self.sow("counters", "expert_trips", trips)
        self.sow("counters", "pairs_zero", jnp.sum(
            zero_pick.reshape(b, t * cfg.moe_topk), axis=1
        ).astype(jnp.int32))
        self.sow("counters", "pairs_routed",
                 jnp.full((b,), t * cfg.moe_topk, jnp.int32))
        return (routed + zero).reshape(b, t, d)


class _DoubleBlock(_Part):
    layer: int = 0

    @nn.compact
    def __call__(self, h, cos, sin):
        cfg, d = self.cfg, self.cfg.hidden_size

        def norm(name, x):
            return rms_norm(x, self.scale(name, d), cfg.rms_norm_eps)

        with jax.named_scope("mla"):
            a0 = h + _Attention(cfg, self.dtype, name="self_attn_0")(
                norm("input_layernorm_0", h), cos, sin)
        x0 = norm("post_attention_layernorm_0", a0)
        # The shortcut: the experts read x0 and join only at the end.
        m = _ShortcutExperts(cfg, self.dtype, name="mlp")(x0)
        with jax.named_scope("dense_ffn"):
            b0 = a0 + _DenseFFN(cfg, self.dtype, name="mlps_0")(x0)
        with jax.named_scope("mla"):
            a1 = b0 + _Attention(cfg, self.dtype, name="self_attn_1")(
                norm("input_layernorm_1", b0), cos, sin)
        with jax.named_scope("dense_ffn"):
            return a1 + _DenseFFN(cfg, self.dtype, name="mlps_1")(
                norm("post_attention_layernorm_1", a1)) + m


class ShortcutMoeEncoder(mla_moe.MlaMoeEncoder):
    block_cls = _DoubleBlock

    def rope(self, length: int):
        return rope_tables(self.cfg, length)


class ShortcutMoeClassifier(mla_moe.MlaMoeClassifier):
    """``MlaMoeClassifier`` over the double-layer encoder."""

    encoder_cls = ShortcutMoeEncoder
    row_counters = ("pairs_real", "pairs_run", "expert_trips",
                    "pairs_zero", "pairs_routed", "attn_fused")

    def torch_key_to_flax(self, key: str) -> Optional[Tuple]:
        """The published names: the list-valued sub-modules of a double
        layer (``self_attn.0``, ``mlps.1``, ``input_layernorm.0`` ...) take
        their index into the name, the router's two tensors sit in the
        expert layer, and everything else is laid out as A.X-K1's."""
        m = re.fullmatch(r"model\.layers\.(\d+)\.mlp\.router\."
                         r"(classifier\.weight|e_score_correction_bias)", key)
        if m:
            leaf = "router_" + m.group(2).replace(".weight", "")
            return (("params", "encoder", f"layers_{int(m.group(1))}", "mlp",
                     leaf), None)
        return super().torch_key_to_flax(re.sub(
            r"^(model\.layers\.\d+\.(?:self_attn|mlps|input_layernorm|"
            r"post_attention_layernorm))\.([01])\.", r"\1_\2.", key))


MODELS.register("LONGCAT_FLASH_EP32_L4", mla_moe.forward_only_factory(
    "LONGCAT_FLASH_EP32_L4", LONGCAT_FLASH_EP32_L4, ShortcutMoeClassifier))
MODELS.register("LONGCAT_FLASH_TOY", mla_moe.forward_only_factory(
    "LONGCAT_FLASH_TOY", LONGCAT_FLASH_TOY, ShortcutMoeClassifier))
