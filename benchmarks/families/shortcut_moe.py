"""The shortcut-connected MoE token-encoder family (LongCat-Flash,
arXiv:2509.01322): rows of token ids, a frozen encoder of double layers under
a trained linear head, its FLOPs and bytes and its plain float32 forward.
The contract: ``families/__init__.py``.  The rows, the seeded draw, the head
and the forward-only refusal are ``families/mla_moe.py``'s, imported.

**The plain reference** (``embed``, ``head``): float32, matmul precision
``highest``, no kernels, no cache, no batching tricks, following the HF
``modeling_longcat_flash.py`` as the configuration's keys bear it out.  One
double layer, input h (d = hidden_size):

    a0 = h  + MLA_0(RMSNorm_in0(h))
    x0 = RMSNorm_post0(a0)
    m  = MoE(x0)                         # the shortcut: joins only at the end
    b0 = a0 + FFN_0(x0)                  # SwiGLU, width ffn_hidden_size
    a1 = b0 + MLA_1(RMSNorm_in1(b0))
    h' = a1 + FFN_1(RMSNorm_post1(a1)) + m

``MLA_i(x)``: ``q = alpha_q * W_qb RMSNorm(W_qa x)`` per head [nope | rope];
``[c | kr] = W_kva x``; ``[k_nope | v] = W_kvb (alpha_kv * RMSNorm(c))``;
``alpha_q = (d / q_lora_rank)^0.5``, ``alpha_kv = (d / kv_lora_rank)^0.5``;
q_rope and kr rotated (adjacent pairs, plain RoPE at ``rope_theta``), kr
shared by the heads; causal softmax of ``q . k / qk_head_dim^0.5``; ``W_o``.
``MoE(x)``: ``p = softmax(W_r x)`` over all ``experts_routed_over +
zero_expert_num`` outputs; the picks are the ``moe_topk`` largest of ``p +
bias``; a pick's gate is ``routed_scaling_factor * p`` (not renormalised);
the result is the gated sum of the picked SwiGLU experts plus (the picked
zero experts' gates, summed) times x.  Departures, all in the configuration
file too: the encoder's output is ``RMSNorm_final(h_L)`` at a row's LAST
token under a trained linear head (no language-model head held, no MTP
module); the correction bias, zero in a fresh model and learnt in training,
is drawn from the seed so that it moves picks.

**The share.**  One chip of an expert-parallel deployment: the router scores
every output, and the layer adds up the picked experts this chip HOLDS
(``experts_held_first`` .. ``+ n_routed_experts``) and the zero-expert term,
whole (it is computed where the token is).  What the absent experts would
have added is left out, here as in the program.  ``moe_parts`` gives the
routed and the zero part apart, so a test can add the shares up to the uncut
layer; because the shortcut joins last, the same holds for a whole double
layer.

**Weights are a function of the seed, drawn where they are used**, as
``families/mla_moe.py`` sets out: ``save_checkpoint`` writes the file the
program loads (published names, bfloat16; the correction bias float32) and
``embed`` draws a layer's tensors when it reaches the layer.

Only ``datasets`` imports the program.
"""

from __future__ import annotations

import os
import zlib
from typing import Dict, List, Tuple

import numpy as np

from families import mla_moe as base

draw, _draw = base.draw, base._draw
make_data = base.make_data
make_weights = base.make_weights     # it remembers the key's configuration
experiment = base.experiment
trainable_keys = base.trainable_keys
program_params = base.program_params
head = base.head
train_view = base.train_view
held_experts = base.held_experts

BIAS = "e_score_correction_bias"


def datasets(config: Dict, pool, test):
    """The program's (train_set, test_set, al_set) over the host arrays.  A
    program that has no such encoder (the parent of the PR that brought it)
    is told so here, before a ten-gigabyte file is drawn for it."""
    from active_learning_tpu.models import factory  # noqa: F401 (registers)
    from active_learning_tpu.registry import MODELS
    if config["model"] not in MODELS.names():
        raise SystemExit(f"this program has no model {config['model']!r}: "
                         f"it cannot run this configuration")
    return base.datasets(config, pool, test)


# -- the tensors of the share ------------------------------------------------

def tensor_specs(config: Dict) -> List[Tuple[str, tuple, float]]:
    """(published name, shape [out, in], standard deviation) of every
    encoder tensor of this chip's share, in the checkpoint's order.  Every
    name ends in ``.weight`` but the router's correction bias, a buffer."""
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    nope, rope = int(config["qk_nope_head_dim"]), int(
        config["qk_rope_head_dim"])
    vd, ql, kvl = (int(config["v_head_dim"]), int(config["q_lora_rank"]),
                   int(config["kv_lora_rank"]))
    f, fe = int(config["ffn_hidden_size"]), int(
        config["expert_ffn_hidden_size"])
    outputs = int(config["experts_routed_over"]) + int(
        config["zero_expert_num"])
    specs = [("model.embed_tokens.weight", (int(config["vocab_size"]), d),
              1.0)]

    def lin(name, out, inn, fan=None):
        specs.append((name + ".weight", (out, inn), (fan or inn) ** -0.5))

    def scale(name, width):
        specs.append((name + ".weight", (width,), 0.0))     # std 0: ones

    for layer in range(int(config["num_layers"])):
        pre = f"model.layers.{layer}"
        for i in (0, 1):
            scale(f"{pre}.input_layernorm.{i}", d)
            attn = f"{pre}.self_attn.{i}"
            lin(f"{attn}.q_a_proj", ql, d)
            scale(f"{attn}.q_a_layernorm", ql)
            # The two up-projections are drawn at the model width's scale:
            # the variance the factors alpha_q, alpha_kv bring back to 1.
            lin(f"{attn}.q_b_proj", heads * (nope + rope), ql,
                d if config["mla_scale_q_lora"] else ql)
            lin(f"{attn}.kv_a_proj_with_mqa", kvl + rope, d)
            scale(f"{attn}.kv_a_layernorm", kvl)
            lin(f"{attn}.kv_b_proj", heads * (nope + vd), kvl,
                d if config["mla_scale_kv_lora"] else kvl)
            lin(f"{attn}.o_proj", d, heads * vd)
            scale(f"{pre}.post_attention_layernorm.{i}", d)
            lin(f"{pre}.mlps.{i}.gate_proj", f, d)
            lin(f"{pre}.mlps.{i}.up_proj", f, d)
            lin(f"{pre}.mlps.{i}.down_proj", d, f)
        lin(f"{pre}.mlp.router.classifier", outputs, d)
        specs.append((f"{pre}.mlp.router.{BIAS}", (outputs,),
                      float(config["router_bias_std"])))
        for e in held_experts(config):
            lin(f"{pre}.mlp.experts.{e}.gate_proj", fe, d)
            lin(f"{pre}.mlp.experts.{e}.up_proj", fe, d)
            lin(f"{pre}.mlp.experts.{e}.down_proj", d, fe)
    scale("model.norm", d)
    return specs


def param_count(config: Dict) -> int:
    """Encoder parameters of the share, and the head's."""
    enc = sum(int(np.prod(shape)) for _, shape, _ in tensor_specs(config))
    return enc + (int(config["hidden_size"]) + 1) * int(config["num_classes"])


def save_checkpoint(weights: Dict[str, np.ndarray], directory: str) -> str:
    """The torch file the program's pretrained overlay reads: the share's
    tensors under their published names, bfloat16 as a deployment stores
    them; the correction bias (a float32 buffer) and the head float32."""
    import jax
    import jax.numpy as jnp
    import torch
    import warnings
    key = np.asarray(weights["encoder.key"])
    config = base._CONFIG_OF_KEY[(int(key[0]), int(key[1]))]
    drawn = jax.jit(
        lambda k, crc, shape, std, dtype: _draw(k, crc, shape, std).astype(
            dtype), static_argnums=(2, 3, 4))
    warnings.filterwarnings("ignore", message="The given NumPy array is not "
                            "writable")       # the file only reads them
    state = {}
    for name, shape, std in tensor_specs(config):
        crc = np.uint32(zlib.crc32(name.encode()))
        if name.endswith(BIAS):
            state[name] = torch.from_numpy(np.asarray(
                drawn(jnp.asarray(key), crc, shape, std, jnp.float32)))
            continue
        host = np.asarray(drawn(jnp.asarray(key), crc, shape, std,
                                jnp.bfloat16))
        state[name] = torch.from_numpy(host.view(np.int16)).view(
            torch.bfloat16)
    for name in ("linear.weight", "linear.bias"):
        state[name] = torch.from_numpy(np.asarray(weights[name]))
    path = os.path.join(directory, "seed_weights.pth")
    torch.save(state, path)
    return path


# -- required operations and bytes ---------------------------------------

def layer_macs(config: Dict) -> Dict[str, float]:
    """Multiply-accumulates of ONE token in the parts of one double layer
    (``mla`` and ``dense_ffn`` are one of the two; attention cores apart:
    they depend on the row's length)."""
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    nope, rope = int(config["qk_nope_head_dim"]), int(
        config["qk_rope_head_dim"])
    vd, ql, kvl = (int(config["v_head_dim"]), int(config["q_lora_rank"]),
                   int(config["kv_lora_rank"]))
    expert = 3 * d * int(config["expert_ffn_hidden_size"])
    outputs = int(config["experts_routed_over"]) + int(
        config["zero_expert_num"])
    # The held experts' share of a token's picks, in expectation over a
    # router that favours none of its outputs; a zero expert's pick costs
    # no multiplication worth counting (one scale of the token).
    picked_here = (int(config["moe_topk"]) * int(config["n_routed_experts"])
                   / outputs)
    return {"mla": float(d * ql + ql * heads * (nope + rope)
                         + d * (kvl + rope) + kvl * heads * (nope + vd)
                         + heads * vd * d),
            "dense_ffn": float(3 * d * int(config["ffn_hidden_size"])),
            "router": float(d * outputs),
            "held_experts": picked_here * expert}


def forward_flops_per_token(config: Dict) -> float:
    """FLOPs of one token's forward at ``row_len`` (causal attention: a
    token attends to itself and what is before it)."""
    m = layer_macs(config)
    per_key = int(config["num_attention_heads"]) * (
        int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"])
        + int(config["v_head_dim"]))
    keys = (int(config["row_len"]) + 1) / 2.0
    macs = int(config["num_layers"]) * (
        2 * (m["mla"] + per_key * keys) + 2 * m["dense_ffn"] + m["router"]
        + m["held_experts"])
    return 2.0 * macs


def work(config: Dict, kind: str, rows: int, batches: int = 1,
         head_only: bool = False) -> Dict[str, float]:
    """Required FLOPs and least HBM bytes of ``rows`` rows in ``batches``
    program steps, by ``families/mla_moe.py``'s convention: ``forward``
    every token through the encoder and the last through the head; ``fit``
    under ``head_only`` the same and the head's weight gradient.  Bytes: the
    int32 rows once; per step every parameter once as stored (bfloat16; the
    correction biases and the head float32); for a fit step the head written
    back with its momentum."""
    t, d = int(config["row_len"]), int(config["hidden_size"])
    nc = int(config["num_classes"])
    fwd = forward_flops_per_token(config) * t + 2.0 * d * nc
    head_bytes = 4 * (d + 1) * nc
    p_bytes = head_bytes + sum(
        int(np.prod(shape)) * (4 if name.endswith(BIAS) else 2)
        for name, shape, _ in tensor_specs(config))
    if kind == "forward":
        flops = fwd * rows
        byts = rows * t * 4 + batches * p_bytes
    elif kind == "fit":
        if not head_only:
            raise NotImplementedError(base.FORWARD_ONLY)
        flops = (fwd + 2.0 * d * nc) * rows
        byts = rows * t * 4 + batches * (p_bytes + 3 * head_bytes)
    else:
        raise KeyError(f"unknown kind of work {kind!r}")
    return {"flops": float(flops), "bytes": float(byts)}


# -- the plain forward ----------------------------------------------------------

_mm, _rms, _swiglu, _rope = base._mm, base._rms, base._swiglu, base._rope
q = base.q


def rope_angles(config: Dict, length: int) -> np.ndarray:
    """[length, rope_dim / 2] rotation angles of plain RoPE."""
    dim, base_ = int(config["qk_rope_head_dim"]), float(config["rope_theta"])
    inv_freq = base_ ** -(np.arange(0, dim, 2, dtype=np.float64) / dim)
    return (np.arange(length, dtype=np.float32)[:, None]
            * inv_freq.astype(np.float32))


def mla(x, w: Dict, config: Dict, quant=None):
    """Multi-head latent attention with scaled low-rank paths over rows
    ``x`` [B, T, d] (already normalised); ``w`` maps one attention's short
    names (``q_a_proj`` ...) to tensors."""
    import jax
    import jax.numpy as jnp
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    nope, rope = int(config["qk_nope_head_dim"]), int(
        config["qk_rope_head_dim"])
    vd, kvl = int(config["v_head_dim"]), int(config["kv_lora_rank"])
    eps = float(config["rms_norm_eps"])
    alpha_q = ((d / int(config["q_lora_rank"])) ** 0.5
               if config["mla_scale_q_lora"] else 1.0)
    alpha_kv = (d / kvl) ** 0.5 if config["mla_scale_kv_lora"] else 1.0
    b, t = x.shape[:2]
    c_q = _rms(_mm(x, w["q_a_proj"], quant), w["q_a_layernorm"], eps)
    qh = alpha_q * _mm(c_q, w["q_b_proj"], quant).reshape(
        b, t, heads, nope + rope)
    kv_a = _mm(x, w["kv_a_proj_with_mqa"], quant)
    c_kv = alpha_kv * _rms(kv_a[..., :kvl], w["kv_a_layernorm"], eps)
    kv = _mm(c_kv, w["kv_b_proj"], quant).reshape(b, t, heads, nope + vd)
    angles = jnp.asarray(rope_angles(config, t))
    q_r = _rope(qh[..., nope:], angles, 1.0)
    k_r = _rope(kv_a[..., kvl:], angles, 1.0)              # one for all heads
    hi = jax.lax.Precision.HIGHEST
    scores = (jnp.einsum("bthd,bshd->bhts", q(qh[..., :nope], quant),
                         q(kv[..., :nope], quant), precision=hi)
              + jnp.einsum("bthd,bsd->bhts", q(q_r, quant), q(k_r, quant),
                           precision=hi)) * (nope + rope) ** -0.5
    scores = jnp.where(jnp.arange(t)[:, None] >= jnp.arange(t)[None, :],
                       scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", q(probs, quant),
                     q(kv[..., nope:], quant), precision=hi)
    return _mm(out.reshape(b, t, heads * vd), w["o_proj"], quant)


def router_gates(x, w_router, bias, config: Dict):
    """[N, experts_routed_over + zero_expert_num] gates of tokens ``x``
    [N, d]: 0 for an output the token did not pick.  Softmax scores over
    all the outputs; the ``moe_topk`` largest of score + bias are picked;
    a gate is the picked score itself times ``routed_scaling_factor``."""
    import jax
    import jax.numpy as jnp
    p = jax.nn.softmax(jnp.einsum("ni,oi->no", x, w_router,
                                  precision=jax.lax.Precision.HIGHEST),
                       axis=-1)
    choice = p + bias
    kth = jnp.sort(choice, axis=-1)[:, -int(config["moe_topk"])][:, None]
    return jnp.where(choice >= kth,
                     float(config["routed_scaling_factor"]) * p, 0.0)


def moe_parts(x, w: Dict, config: Dict, quant=None):
    """(routed, zero) of the expert layer over tokens ``x`` [N, d]: the
    picked experts THIS share holds, each over every token and weighted by
    its gate, and the picked zero experts' identity term."""
    import jax.numpy as jnp
    gates = router_gates(x, w["mlp.router.classifier"],
                         w[f"mlp.router.{BIAS}"], config)
    routed = jnp.zeros_like(x)
    for e in held_experts(config):
        routed = routed + gates[:, e:e + 1] * _swiglu(
            x, w[f"mlp.experts.{e}.gate_proj"], w[f"mlp.experts.{e}.up_proj"],
            w[f"mlp.experts.{e}.down_proj"], quant)
    zero_gate = jnp.sum(gates[:, int(config["experts_routed_over"]):],
                        axis=1, keepdims=True)
    return routed, zero_gate * x


def block(h, w: Dict, layer: int, config: Dict, quant=None):
    """One double layer over rows ``h`` [B, T, d]."""
    eps = float(config["rms_norm_eps"])

    def sub(prefix):
        return {k[len(prefix):]: v for k, v in w.items()
                if k.startswith(prefix)}

    def ffn(x, i):
        return _swiglu(x, w[f"mlps.{i}.gate_proj"], w[f"mlps.{i}.up_proj"],
                       w[f"mlps.{i}.down_proj"], quant)

    a0 = h + mla(_rms(h, w["input_layernorm.0"], eps), sub("self_attn.0."),
                 config, quant)
    x0 = _rms(a0, w["post_attention_layernorm.0"], eps)
    routed, zero = moe_parts(x0.reshape(-1, x0.shape[-1]), w, config, quant)
    m = (routed + zero).reshape(x0.shape)
    b0 = a0 + ffn(x0, 0)
    a1 = b0 + mla(_rms(b0, w["input_layernorm.1"], eps), sub("self_attn.1."),
                  config, quant)
    return a1 + ffn(_rms(a1, w["post_attention_layernorm.1"], eps), 1) + m


def layer_tensors(key, layer: int, config: Dict) -> Dict:
    """The layer's tensors under their short names, drawn from the key."""
    pre = f"model.layers.{layer}."
    return {name[len(pre):].removesuffix(".weight"):
            draw(key, name, shape, std)
            for name, shape, std in tensor_specs(config)
            if name.startswith(pre)}


def embed(p: Dict, rows, config: Dict, quant=None):
    """int32 rows [B, T] -> float32 embedding [B, d]: layer-major over all
    the rows, in blocks of ``ref_block_rows`` rows inside a layer."""
    import jax
    key = p["encoder.key"]
    specs = {name: (shape, std) for name, shape, std in tensor_specs(config)}
    table = draw(key, "model.embed_tokens.weight",
                 *specs["model.embed_tokens.weight"])
    h = table[rows - int(config.get("vocab_first", 0))]
    b = h.shape[0]
    per = max(1, min(int(config.get("ref_block_rows", 8)), b))
    while b % per:
        per -= 1
    for layer in range(int(config["num_layers"])):
        # A layer's 5 GB of float32 tensors are drawn when the layer before
        # it is done, not ahead of it: the key waits for h.
        key, h = jax.lax.optimization_barrier((key, h))
        w = layer_tensors(key, layer, config)
        h = jax.lax.map(lambda part: block(part, w, layer, config, quant),
                        h.reshape((b // per, per) + h.shape[1:])
                        ).reshape(h.shape)
    norm = draw(key, "model.norm.weight", *specs["model.norm.weight"])
    return _rms(h[:, -1], norm, float(config["rms_norm_eps"]))
