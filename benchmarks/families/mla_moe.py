"""The MLA + sparse-experts token-encoder family: rows of token ids, a frozen
DeepSeek-V3-style encoder under a trained linear head, its FLOPs and bytes
and its plain float32 forward.  The contract: ``families/__init__.py``.

**The plain reference** (``embed``, ``head``) follows arXiv:2412.19437
section 2.1 with the configuration's numbers, in float32 with matmul
precision ``highest``, no kernels, no cache, no batching tricks.  Block l:
``u = h + MLA(RMSNorm(h))``, ``h' = u + F_l(RMSNorm(u))``, ``F_0`` the dense
SwiGLU, ``F_l`` the expert layer behind it.  Departures from the published
model, all stated in the configuration file too: the encoder's output is
``RMSNorm_final(h_L)`` at a row's LAST token and the logits are a trained
linear head over it, so the language-model head is neither used nor held;
the config lists no multi-token-prediction module and none is built;
``topk_method: "none"`` is read as "no correction bias enters the choice of
experts", with the group limit kept.

**The share.**  The configuration is one chip's share of an expert-parallel
deployment: the router scores ALL ``experts_routed_over`` experts and picks
``num_experts_per_tok`` of them under the group limit, and the layer adds
up the picked experts that this chip HOLDS (``experts_held_first`` ..
``+ n_routed_experts``) and the shared expert, whole.  What the absent
experts would have added is left out, here as in the program, and that
partial result goes on to the next layer.  ``moe_parts`` gives the routed
and the shared part apart, so a test can add the shares up to the uncut
layer.

**Weights are a function of the seed, drawn where they are used.**  A tensor
is named as the published checkpoint names it
(``model.layers.3.mlp.experts.5.gate_proj.weight``) and its values are
``draw(key, name, shape, std)``: threefry bits folded with the name's CRC,
the sum of each word's four bytes (Irwin-Hall, excess kurtosis -0.3:
normal to the eye, bounded at 3.46 standard deviations), centred, scaled by
one float32 multiplication and rounded once to bfloat16 — integer
arithmetic up to two IEEE operations, so the same bits on the CPU, on the
TPU, inside any ``jit`` and in any fusion.  ``make_weights`` therefore
returns the head's tensors and the encoder's KEY; ``save_checkpoint`` draws
every tensor of the share once and writes the file the program loads
(bfloat16, published names); ``embed`` draws each layer's tensors as it
reaches the layer, so 4.7 billion float32 parameters never sit anywhere at
once (``lib/reference.py`` keeps two copies of what ``make_weights``
returns on the device) and both sides hold the same numbers.  The
reference walks layer-major over all the rows it is given, and inside a
layer in blocks of ``ref_block_rows`` rows, so that one layer's float32
tensors and one block's attention scores are what it needs beside ``h``.

Only ``datasets`` imports the program.
"""

from __future__ import annotations

import os
import zlib
from typing import Dict, List, Tuple

import numpy as np

from lib.reference import q

# make_weights remembers which configuration a key belongs to:
# save_checkpoint is handed the weights alone.
_CONFIG_OF_KEY: Dict[Tuple[int, int], Dict] = {}

_BYTE_SUM_MEAN = 510.0                      # four bytes of mean 127.5
_BYTE_SUM_STD = (4 * (256 ** 2 - 1) / 12.0) ** 0.5


# -- rows ----------------------------------------------------------------------

def make_data(seed: int, config: Dict, n_pool: int, n_test: int):
    """(pool rows int32 [n, T], pool labels, test rows, test labels): half
    of a row's tokens lean towards its class's share of the vocabulary
    slice, the rest fall anywhere in it."""
    vocab, length = int(config["vocab_size"]), int(config["row_len"])
    nc = int(config["num_classes"])
    out = []
    for salt, n in ((21, n_pool), (22, n_test)):
        rng = np.random.default_rng([int(seed), salt])
        labels = rng.integers(0, nc, size=n).astype(np.int64)
        anywhere = rng.integers(0, vocab, size=(n, length))
        own = labels[:, None] * (vocab // nc) + rng.integers(
            0, vocab // nc, size=(n, length))
        rows = np.where(rng.random((n, length)) < 0.5, own, anywhere)
        out += [rows.astype(np.int32) + int(config.get("vocab_first", 0)),
                labels]
    return tuple(out)


def datasets(config: Dict, pool, test):
    """The program's (train_set, test_set, al_set) over the host arrays."""
    from active_learning_tpu.data.tokens import token_datasets
    return token_datasets(pool, test, int(config["num_classes"]))


def experiment(config: Dict) -> Dict:
    return {"dataset": "synthetic_tokens", "model": config["model"]}


# -- the tensors of the share ------------------------------------------------

def held_experts(config: Dict) -> range:
    first = int(config.get("experts_held_first", 0))
    return range(first, first + int(config["n_routed_experts"]))


def tensor_specs(config: Dict) -> List[Tuple[str, tuple, float]]:
    """(published name, shape [out, in], standard deviation) of every
    encoder tensor of this chip's share, in the checkpoint's order."""
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    nope, rope = int(config["qk_nope_head_dim"]), int(
        config["qk_rope_head_dim"])
    vd, ql, kvl = (int(config["v_head_dim"]), int(config["q_lora_rank"]),
                   int(config["kv_lora_rank"]))
    f, fe = int(config["intermediate_size"]), int(
        config["moe_intermediate_size"])
    fs = fe * int(config["n_shared_experts"])
    specs = [("model.embed_tokens.weight", (int(config["vocab_size"]), d),
              1.0)]

    def lin(name, out, inn):
        specs.append((name + ".weight", (out, inn), inn ** -0.5))

    def scale(name, width):
        specs.append((name + ".weight", (width,), 0.0))     # std 0: ones

    for layer in range(int(config["num_hidden_layers"])):
        pre = f"model.layers.{layer}"
        scale(f"{pre}.input_layernorm", d)
        lin(f"{pre}.self_attn.q_a_proj", ql, d)
        scale(f"{pre}.self_attn.q_a_layernorm", ql)
        lin(f"{pre}.self_attn.q_b_proj", heads * (nope + rope), ql)
        lin(f"{pre}.self_attn.kv_a_proj_with_mqa", kvl + rope, d)
        scale(f"{pre}.self_attn.kv_a_layernorm", kvl)
        lin(f"{pre}.self_attn.kv_b_proj", heads * (nope + vd), kvl)
        lin(f"{pre}.self_attn.o_proj", d, heads * vd)
        scale(f"{pre}.post_attention_layernorm", d)
        if layer < int(config["first_k_dense_replace"]):
            lin(f"{pre}.mlp.gate_proj", f, d)
            lin(f"{pre}.mlp.up_proj", f, d)
            lin(f"{pre}.mlp.down_proj", d, f)
            continue
        lin(f"{pre}.mlp.gate", int(config["experts_routed_over"]), d)
        for e in held_experts(config):
            lin(f"{pre}.mlp.experts.{e}.gate_proj", fe, d)
            lin(f"{pre}.mlp.experts.{e}.up_proj", fe, d)
            lin(f"{pre}.mlp.experts.{e}.down_proj", d, fe)
        lin(f"{pre}.mlp.shared_experts.gate_proj", fs, d)
        lin(f"{pre}.mlp.shared_experts.up_proj", fs, d)
        lin(f"{pre}.mlp.shared_experts.down_proj", d, fs)
    scale("model.norm", d)
    return specs


def param_count(config: Dict) -> int:
    """Encoder parameters of the share, and the head's."""
    enc = sum(int(np.prod(shape)) for _, shape, _ in tensor_specs(config))
    return enc + (int(config["hidden_size"]) + 1) * int(config["num_classes"])


def draw(key, name: str, shape: tuple, std: float):
    """The tensor ``name`` as bfloat16-representable float32 values (see the
    module docstring); traceable, and the same bits wherever it runs."""
    return _draw(key, np.uint32(zlib.crc32(name.encode())), shape, std)


def _draw(key, name_crc, shape: tuple, std: float):
    """``draw`` with the name as a number, so that one compiled program
    serves every tensor of a shape."""
    import jax
    import jax.numpy as jnp
    if std == 0.0:
        return jnp.ones(shape, jnp.float32)
    bits = jax.random.bits(jax.random.fold_in(key, name_crc), shape,
                           jnp.uint32)
    total = ((bits & 255) + ((bits >> 8) & 255) + ((bits >> 16) & 255)
             + (bits >> 24)).astype(jnp.int32)
    centred = (total - int(_BYTE_SUM_MEAN)).astype(jnp.float32)
    return (centred * np.float32(std / _BYTE_SUM_STD)).astype(
        jnp.bfloat16).astype(jnp.float32)


def make_weights(seed: int, config: Dict) -> Dict[str, np.ndarray]:
    """The head's tensors and the encoder's key (two uint32 words): every
    encoder tensor is ``draw(key, name, ...)`` of ``tensor_specs``."""
    rng = np.random.default_rng([int(seed), 31])
    d, nc = int(config["hidden_size"]), int(config["num_classes"])
    key = rng.integers(0, 2 ** 32, size=2, dtype=np.uint32)
    _CONFIG_OF_KEY[(int(key[0]), int(key[1]))] = config
    return {
        "encoder.key": key,
        "linear.weight": (rng.standard_normal((nc, d), dtype=np.float32)
                          * np.float32(d ** -0.5)),
        "linear.bias": (rng.standard_normal(nc, dtype=np.float32)
                        * np.float32(0.01))}


def save_checkpoint(weights: Dict[str, np.ndarray], directory: str) -> str:
    """The torch file the program's pretrained overlay reads: the share's
    tensors under their published names, bfloat16 as a deployment stores
    them, and the head float32."""
    import jax
    import jax.numpy as jnp
    import torch
    key = np.asarray(weights["encoder.key"])
    config = _CONFIG_OF_KEY[(int(key[0]), int(key[1]))]
    drawn = jax.jit(
        lambda k, crc, shape, std: _draw(k, crc, shape, std).astype(
            jnp.bfloat16), static_argnums=(2, 3))
    import warnings
    warnings.filterwarnings("ignore", message="The given NumPy array is not "
                            "writable")       # the file only reads them
    state = {}
    for name, shape, std in tensor_specs(config):
        host = np.asarray(drawn(jnp.asarray(key),
                                np.uint32(zlib.crc32(name.encode())),
                                shape, std))
        state[name] = torch.from_numpy(host.view(np.int16)).view(
            torch.bfloat16)
    for name in ("linear.weight", "linear.bias"):
        state[name] = torch.from_numpy(np.asarray(weights[name]))
    path = os.path.join(directory, "seed_weights.pth")
    torch.save(state, path)
    return path


FORWARD_ONLY = ("the mla_moe family has no reference for a fit of the whole "
                "encoder (its tensors are a function of a key and its "
                "program is forward-only): its cells say freeze_feature true")


def trainable_keys(weights: Dict[str, np.ndarray],
                   head_only: bool = False) -> List[str]:
    """Under ``head_only`` (``freeze_feature``) the head's.  Without it
    nothing: the family refuses, as the program's factory does."""
    if not head_only:
        raise NotImplementedError(FORWARD_ONLY)
    return [k for k in weights if k.startswith("linear.")]


def program_params(tree, weights: Dict[str, np.ndarray]
                   ) -> Dict[str, np.ndarray]:
    """The program's trainable leaves (``state.params``: the head, nothing
    frozen is among them) under ``make_weights``' keys and layout."""
    return {"linear.weight": np.asarray(tree["linear"]["kernel"]).T,
            "linear.bias": np.asarray(tree["linear"]["bias"])}


# -- required operations and bytes ---------------------------------------

def layer_macs(config: Dict) -> Dict[str, float]:
    """Multiply-accumulates of ONE token in one layer's parts (attention
    cores apart: they depend on the row's length)."""
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    nope, rope = int(config["qk_nope_head_dim"]), int(
        config["qk_rope_head_dim"])
    vd, ql, kvl = (int(config["v_head_dim"]), int(config["q_lora_rank"]),
                   int(config["kv_lora_rank"]))
    fe = int(config["moe_intermediate_size"])
    mla = (d * ql + ql * heads * (nope + rope) + d * (kvl + rope)
           + kvl * heads * (nope + vd) + heads * vd * d)
    expert = 3 * d * fe
    # The held experts' share of a token's picks, in expectation over a
    # router that favours none: what the algorithm needs from this chip.
    picked_here = (int(config["num_experts_per_tok"])
                   * int(config["n_routed_experts"])
                   / int(config["experts_routed_over"]))
    return {"mla": float(mla),
            "dense_ffn": float(3 * d * int(config["intermediate_size"])),
            "router": float(d * int(config["experts_routed_over"])),
            "shared": float(expert * int(config["n_shared_experts"])),
            "held_experts": picked_here * expert}


def forward_flops_per_token(config: Dict) -> float:
    """FLOPs of one token's forward at ``row_len`` (causal attention: a
    token attends to itself and what is before it)."""
    m = layer_macs(config)
    layers = int(config["num_hidden_layers"])
    dense = int(config["first_k_dense_replace"])
    heads = int(config["num_attention_heads"])
    per_key = heads * (int(config["qk_nope_head_dim"])
                       + int(config["qk_rope_head_dim"])
                       + int(config["v_head_dim"]))
    keys = (int(config["row_len"]) + 1) / 2.0
    macs = (layers * (m["mla"] + per_key * keys) + dense * m["dense_ffn"]
            + (layers - dense) * (m["router"] + m["shared"]
                                  + m["held_experts"]))
    return 2.0 * macs


def work(config: Dict, kind: str, rows: int, batches: int = 1,
         head_only: bool = False) -> Dict[str, float]:
    """Required FLOPs and least HBM bytes of ``rows`` rows in ``batches``
    program steps.  ``forward``: every token through the encoder, the last
    one through the head.  ``fit`` under ``head_only``: the same forward
    and the head's weight gradient, no encoder backward; a fit without
    ``head_only`` is refused (``trainable_keys``).  Bytes:
    the int32 rows once; per step every parameter once, bfloat16 as stored
    (the head float32); for a fit step the head written back with its
    momentum."""
    t, d = int(config["row_len"]), int(config["hidden_size"])
    nc = int(config["num_classes"])
    fwd = forward_flops_per_token(config) * t + 2.0 * d * nc
    head_bytes = 4 * (d + 1) * nc
    p_bytes = 2 * (param_count(config) - (d + 1) * nc) + head_bytes
    if kind == "forward":
        flops = fwd * rows
        byts = rows * t * 4 + batches * p_bytes
    elif kind == "fit":
        if not head_only:
            raise NotImplementedError(FORWARD_ONLY)
        flops = (fwd + 2.0 * d * nc) * rows
        byts = rows * t * 4 + batches * (p_bytes + 3 * head_bytes)
    else:
        raise KeyError(f"unknown kind of work {kind!r}")
    return {"flops": float(flops), "bytes": float(byts)}


# -- the plain forward ----------------------------------------------------------

def _mm(x, w, quant):
    """``x [..., in] @ w[out, in]^T``, float32, precision ``highest``."""
    import jax
    import jax.numpy as jnp
    return jnp.einsum("...i,oi->...o", q(x, quant), q(w, quant),
                      precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _swiglu(x, w_gate, w_up, w_down, quant):
    import jax
    return _mm(jax.nn.silu(_mm(x, w_gate, quant)) * _mm(x, w_up, quant),
               w_down, quant)


def rope_angles(config: Dict, length: int) -> np.ndarray:
    """[length, rope_dim / 2] rotation angles under yarn scaling."""
    rs = config["rope_scaling"]
    dim, base = int(config["qk_rope_head_dim"]), float(config["rope_theta"])
    factor, orig = float(rs["factor"]), float(
        rs["original_max_position_embeddings"])
    i = np.arange(0, dim, 2, dtype=np.float64) / dim
    plain, stretched = base ** -i, base ** -i / factor

    def dim_of(rotations):
        return dim * np.log(orig / (rotations * 2 * np.pi)) / (
            2 * np.log(base))
    low = max(np.floor(dim_of(float(rs["beta_fast"]))), 0)
    high = min(np.ceil(dim_of(float(rs["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv_freq = stretched * ramp + plain * (1 - ramp)
    return (np.arange(length, dtype=np.float32)[:, None]
            * inv_freq.astype(np.float32))


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * float(np.log(factor)) + 1.0


def _rope(x, angles, scale):
    """Rotate the pairs (x[2i], x[2i+1]) by ``angles[t, i]``; ``x`` is
    [B, T, rope_dim] or [B, T, H, rope_dim]."""
    import jax.numpy as jnp
    cos, sin = jnp.cos(angles) * scale, jnp.sin(angles) * scale
    if x.ndim == 4:
        cos, sin = cos[:, None, :], sin[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1)
    return out.reshape(x.shape)


def mla(x, w: Dict, config: Dict, quant=None):
    """Multi-head latent attention over rows ``x`` [B, T, d] (already
    normalised); ``w`` maps the layer's short names to tensors."""
    import jax
    import jax.numpy as jnp
    heads = int(config["num_attention_heads"])
    nope, rope = int(config["qk_nope_head_dim"]), int(
        config["qk_rope_head_dim"])
    vd, kvl = int(config["v_head_dim"]), int(config["kv_lora_rank"])
    eps, rs = float(config["rms_norm_eps"]), config["rope_scaling"]
    b, t = x.shape[:2]
    c_q = _rms(_mm(x, w["self_attn.q_a_proj"], quant),
               w["self_attn.q_a_layernorm"], eps)
    qh = _mm(c_q, w["self_attn.q_b_proj"], quant).reshape(
        b, t, heads, nope + rope)
    kv_a = _mm(x, w["self_attn.kv_a_proj_with_mqa"], quant)
    c_kv = _rms(kv_a[..., :kvl], w["self_attn.kv_a_layernorm"], eps)
    kv = _mm(c_kv, w["self_attn.kv_b_proj"], quant).reshape(
        b, t, heads, nope + vd)
    angles = jnp.asarray(rope_angles(config, t))
    factor = float(rs["factor"])
    table_scale = (_mscale(factor, float(rs["mscale"]))
                   / _mscale(factor, float(rs["mscale_all_dim"])))
    q_r = _rope(qh[..., nope:], angles, table_scale)
    k_r = _rope(kv_a[..., kvl:], angles, table_scale)      # one for all heads
    hi = jax.lax.Precision.HIGHEST
    m = _mscale(factor, float(rs["mscale_all_dim"]))
    scale = (nope + rope) ** -0.5 * m * m
    scores = (jnp.einsum("bthd,bshd->bhts", q(qh[..., :nope], quant),
                         q(kv[..., :nope], quant), precision=hi)
              + jnp.einsum("bthd,bsd->bhts", q(q_r, quant), q(k_r, quant),
                           precision=hi)) * scale
    scores = jnp.where(jnp.arange(t)[:, None] >= jnp.arange(t)[None, :],
                       scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", q(probs, quant),
                     q(kv[..., nope:], quant), precision=hi)
    return _mm(out.reshape(b, t, heads * vd), w["self_attn.o_proj"], quant)


def router_gates(x, w_router, config: Dict):
    """[N, experts_routed_over] gates of tokens ``x`` [N, d]: 0 for an
    expert the token did not pick.  Sigmoid scores; a group's score is the
    sum of its two largest; the ``topk_group`` best groups are kept; the
    ``num_experts_per_tok`` largest scores inside them are picked; gates are
    the picked scores over their sum, times ``routed_scaling_factor``."""
    import jax
    import jax.numpy as jnp
    n_group, k_group = int(config["n_group"]), int(config["topk_group"])
    k = int(config["num_experts_per_tok"])
    p = jax.nn.sigmoid(jnp.einsum("ni,oi->no", x, w_router,
                                  precision=jax.lax.Precision.HIGHEST))
    groups = p.reshape(p.shape[0], n_group, -1)
    group_score = jnp.sum(jnp.sort(groups, axis=-1)[..., -2:], axis=-1)
    kth_group = jnp.sort(group_score, axis=-1)[:, -k_group][:, None]
    kept = jnp.repeat(group_score >= kth_group, groups.shape[-1], axis=1)
    inside = jnp.where(kept, p, 0.0)
    kth = jnp.sort(inside, axis=-1)[:, -k][:, None]
    picked = jnp.where(inside >= kth, p, 0.0)
    return (float(config["routed_scaling_factor"]) * picked
            / jnp.sum(picked, axis=-1, keepdims=True))


def moe_parts(x, w: Dict, config: Dict, quant=None):
    """(routed, shared) of the expert layer over tokens ``x`` [N, d]: the
    picked experts THIS share holds, each over every token and weighted by
    its gate, and the shared expert."""
    import jax.numpy as jnp
    gates = router_gates(x, w["mlp.gate"], config)
    routed = jnp.zeros_like(x)
    for e in held_experts(config):
        routed = routed + gates[:, e:e + 1] * _swiglu(
            x, w[f"mlp.experts.{e}.gate_proj"], w[f"mlp.experts.{e}.up_proj"],
            w[f"mlp.experts.{e}.down_proj"], quant)
    shared = _swiglu(x, w["mlp.shared_experts.gate_proj"],
                     w["mlp.shared_experts.up_proj"],
                     w["mlp.shared_experts.down_proj"], quant)
    return routed, shared


def block(h, w: Dict, layer: int, config: Dict, quant=None):
    """One layer over rows ``h`` [B, T, d]."""
    eps = float(config["rms_norm_eps"])
    u = h + mla(_rms(h, w["input_layernorm"], eps), w, config, quant)
    x = _rms(u, w["post_attention_layernorm"], eps)
    if layer < int(config["first_k_dense_replace"]):
        return u + _swiglu(x, w["mlp.gate_proj"], w["mlp.up_proj"],
                           w["mlp.down_proj"], quant)
    routed, shared = moe_parts(x.reshape(-1, x.shape[-1]), w, config, quant)
    return u + (routed + shared).reshape(x.shape)


def layer_tensors(key, layer: int, config: Dict) -> Dict:
    """The layer's tensors under their short names, drawn from the key."""
    pre = f"model.layers.{layer}."
    return {name[len(pre):-len(".weight")]: draw(key, name, shape, std)
            for name, shape, std in tensor_specs(config)
            if name.startswith(pre)}


def embed(p: Dict, rows, config: Dict, quant=None):
    """int32 rows [B, T] -> float32 embedding [B, d]: layer-major over all
    the rows, in blocks of ``ref_block_rows`` rows inside a layer."""
    import jax
    import jax.numpy as jnp
    key = p["encoder.key"]
    specs = {name: (shape, std) for name, shape, std in tensor_specs(config)}
    table = draw(key, "model.embed_tokens.weight",
                 *specs["model.embed_tokens.weight"])
    h = table[rows - int(config.get("vocab_first", 0))]
    b = h.shape[0]
    per = max(1, min(int(config.get("ref_block_rows", 8)), b))
    while b % per:
        per -= 1
    for layer in range(int(config["num_hidden_layers"])):
        w = layer_tensors(key, layer, config)
        h = jax.lax.map(lambda part: block(part, w, layer, config, quant),
                        h.reshape((b // per, per) + h.shape[1:])
                        ).reshape(h.shape)
    norm = draw(key, "model.norm.weight", *specs["model.norm.weight"])
    return _rms(h[:, -1], norm, float(config["rms_norm_eps"]))


def head(p: Dict, emb, quant=None):
    import jax
    import jax.numpy as jnp
    return jnp.matmul(q(emb, quant), q(p["linear.weight"], quant).T,
                      precision=jax.lax.Precision.HIGHEST) + p["linear.bias"]


def train_view(rows, step_key, augment):
    """A row of token ids has no augmentation."""
    return rows
