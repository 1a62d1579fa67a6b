"""Qwen3-Next family (``model_type`` ``qwen3_next``; Qwen3-Next-80B-A3B):
layers of two KINDS in a repeating pattern (three ``linear_attention`` layers
to every ``full_attention`` layer: ``full_attention_interval`` 4), EVERY one
followed by routed experts. Pre-norm, sequential residual; RMSNorm with a
zero-centred scale; a final norm; an untied head.

- A linear-attention layer is a Gated DeltaNet mixer (``ops/gdn.py`` has the
  recurrence) with GROUPED value heads: ``linear_num_key_heads`` query and
  key heads under ``linear_num_value_heads`` value heads, ``beta`` in (0, 1),
  a plain-scaled RMSNorm over a head's values times ``SiLU(z)``.
- A full-attention layer is gated: the query projection gives a query and a
  gate a head, RMSNorm over each HEAD of the queries and of the keys, rotary
  on the first ``partial_rotary_factor`` of a head (rotate-half), grouped
  queries, and the heads' output times ``sigmoid(gate)`` before the output
  projection.
- The expert layer: a float32 softmax over ``num_experts``, the top
  ``num_experts_per_tok``, renormalised; beside one shared expert times
  ``sigmoid(x . w)`` (``ops/moe.py``).

The two kinds are two stacks of the parameter tree and two pools of the
paged cache walked by period, as ``olmo_hybrid``'s are; the experts of all
layers are one stack (``params["experts"]``). What each serving feature does
with the state, and what one chip's SHARE of the experts is
(``expert_parallel``), are in ``docs/recurrent-state.md``. Forms of the
family that are not implemented are refused by name here; the published
model's multi-token-prediction module is not loaded and not served.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from llmss_tpu.models.common import (
    DecoderConfig, LinearAttnConfig, MoEConfig, experts_held,
)
from llmss_tpu.models.decoder import Params, param_specs
from llmss_tpu.ops.layers import LinearParams, NormParams, load_lm_head
from llmss_tpu.parallel.mesh import AXIS_TP
from llmss_tpu.weights.loader import CheckpointShards

KINDS = ("linear_attention", "full_attention")


def config_from_hf(hf, dtype: str = "bfloat16") -> DecoderConfig:
    def refuse(what):
        raise ValueError(f"qwen3_next: {what} is not implemented")

    n = hf.num_hidden_layers
    interval = getattr(hf, "full_attention_interval", 4)
    types = tuple(getattr(hf, "layer_types", None) or (
        KINDS[(i + 1) % interval == 0] for i in range(n)
    ))
    if len(types) != n or set(types) != set(KINDS):
        raise ValueError(
            f"qwen3_next: {n} layers must be of both kinds {KINDS} "
            f"(full_attention_interval {interval}), got {len(types)}: "
            f"{sorted(set(types))}"
        )
    if getattr(hf, "mlp_only_layers", None):
        refuse(f"mlp_only_layers {hf.mlp_only_layers} (a dense MLP in some layers)")
    if getattr(hf, "decoder_sparse_step", 1) != 1:
        refuse(f"decoder_sparse_step {hf.decoder_sparse_step}")
    if getattr(hf, "rope_scaling", None):
        refuse("rope_scaling")
    if getattr(hf, "use_sliding_window", False):
        refuse("use_sliding_window")
    if getattr(hf, "attention_bias", False):
        refuse("attention_bias")
    if not getattr(hf, "shared_expert_intermediate_size", 0):
        refuse("an expert layer without a shared expert")
    head_dim = getattr(hf, "head_dim", None) or (
        hf.hidden_size // hf.num_attention_heads
    )
    rotary = int(head_dim * getattr(hf, "partial_rotary_factor", 1.0))
    n_experts, first, count = experts_held(hf, "qwen3_next")
    return DecoderConfig(
        model_type="qwen3_next",
        vocab_size=hf.vocab_size,
        hidden_size=hf.hidden_size,
        n_layers=n,
        n_heads=hf.num_attention_heads,
        n_kv_heads=hf.num_key_value_heads,
        head_dim=head_dim,
        intermediate_size=hf.intermediate_size,
        max_position_embeddings=hf.max_position_embeddings,
        activation=hf.hidden_act,
        norm="rmsnorm",
        norm_eps=hf.rms_norm_eps,
        mlp="swiglu",
        positions="rotary",
        rope_style="half",
        rotary_dim=rotary,
        rope_theta=float(hf.rope_theta),
        attn_bias=False,
        mlp_bias=False,
        tie_word_embeddings=getattr(hf, "tie_word_embeddings", False),
        layer_types=types,
        linear_attn=LinearAttnConfig(
            n_heads=hf.linear_num_key_heads,
            key_head_dim=hf.linear_key_head_dim,
            value_head_dim=hf.linear_value_head_dim,
            d_conv=hf.linear_conv_kernel_dim,
            allow_neg_eigval=False,
            n_value_heads=hf.linear_num_value_heads,
        ),
        moe=MoEConfig(
            n_experts=n_experts,
            top_k=hf.num_experts_per_tok,
            expert_size=hf.moe_intermediate_size,
            shared_size=hf.shared_expert_intermediate_size,
            n_dense_layers=0,
            norm_topk_prob=bool(hf.norm_topk_prob),
            scoring="softmax",
            shared_gate=True,
            first=first,
            count=count,
        ),
        qk_norm_per_head=True,
        attn_gate=True,
        dtype=dtype,
    )


def load_params(ckpt: CheckpointShards, cfg: DecoderConfig, mesh: Mesh) -> Params:
    """Every leaf under the name the published implementation gives it, as
    remembered (no network here, and no checkpoint to read; the round trip
    through a checkpoint written under these names is in
    tests/test_qwen3_next.py). A name that is not in the file raises in the
    loader. Three things are not as stored:

    - the zero-centred norm scales (``x / rms(x) * (1 + w)``: the block
      norms, the QK-norms, the final norm) are loaded as ``1 + w``, added in
      float32 and rounded to the compute dtype, so the program's one
      ``rms_norm`` serves them; the gated norm inside the linear mixer is
      plain and loads as it is;
    - ``linear_attn.in_proj_qkvz`` and ``in_proj_ba`` interleave their parts
      a KEY head (``[q, k, v of its value heads, z of its value heads]``,
      ``[b, a]``); they are taken apart into the program's ``gdn_qkv`` (q, k,
      v side by side, as the one convolution reads them), ``gdn_g`` (z) and
      ``gdn_ab`` (a beside b);
    - of ``mlp.experts.{j}`` only the experts held here are read
      (``MoEConfig.first`` / ``count``)."""
    specs = param_specs(cfg, mesh.shape[AXIS_TP])
    rep = P(None, None, None)
    m, x = cfg.linear_attn, cfg.moe

    def centred(scale):
        return (scale.astype(jnp.float32) + 1.0).astype(scale.dtype)

    def stack_of(kind, sp):
        """The loaders of one kind's layers, stacked in the model's order,
        and what both kinds hold: the two norms, the router, the shared
        expert and its gate."""
        ids = [i for i, t in enumerate(cfg.layer_types) if t == kind]

        def names(attr):
            return [f"model.layers.{i}.{attr}" for i in ids]

        def mat(attr, spec=rep, transpose=True):
            # torch Linear stores [out, in]: every matrix is [in, out] here
            # but the attention layers' q and k, and the router
            return ckpt.get_stacked_array(
                names(f"{attr}.weight"), mesh, spec, transpose=transpose
            )

        def norm(attr, zero_centred=True):
            scale = ckpt.get_stacked_array(
                names(f"{attr}.weight"), mesh, P(None, None)
            )
            return NormParams(centred(scale) if zero_centred else scale, None)

        shared = {
            "ln1": norm("input_layernorm"),
            "ln2": norm("post_attention_layernorm"),
            "router": LinearParams(mat("mlp.gate", transpose=False), None),
            **{f"shared_{key}": LinearParams(
                mat(f"mlp.shared_expert.{key}_proj", sp[f"shared_{key}"].w),
                None,
            ) for key in ("gate", "up", "down")},
            "shared_sig": LinearParams(mat("mlp.shared_expert_gate"), None),
        }
        return shared, names, mat, norm

    blocks, _, mat, norm = stack_of(KINDS[1], specs["blocks"])
    for key in ("q", "k", "v", "o"):
        blocks[key] = LinearParams(mat(
            f"self_attn.{key}_proj", specs["blocks"][key].w,
            transpose=key not in ("q", "k"),
        ), None)
    blocks["q_norm"] = norm("self_attn.q_norm")
    blocks["k_norm"] = norm("self_attn.k_norm")

    linear, names, mat, norm = stack_of(KINDS[0], specs["linear"])
    Hk, r = m.n_heads, m.n_v_heads // m.n_heads
    Dk, Dv = m.key_head_dim, m.value_head_dim
    # [L, out, E] as stored, a key head's parts side by side on ``out``
    qkvz = mat("linear_attn.in_proj_qkvz", transpose=False)
    L, _, E = qkvz.shape
    qkvz = qkvz.reshape(L, Hk, 2 * Dk + 2 * r * Dv, E)
    ba = mat("linear_attn.in_proj_ba", transpose=False).reshape(L, Hk, 2 * r, E)

    def part(a, lo, hi):  # a part of every key head -> [L, E, heads x width]
        return jnp.swapaxes(a[:, :, lo:hi].reshape(L, -1, E), 1, 2)

    conv = ckpt.get_stacked_array(  # [L, C, 1, K] as published -> [L, K, C]
        names("linear_attn.conv1d.weight"), mesh, P(None, None, None, None)
    )

    def vec(attr):
        return ckpt.get_stacked_array(
            names(f"linear_attn.{attr}"), mesh, P(None, None)
        )

    v0 = 2 * Dk
    linear.update({
        "gdn_qkv": LinearParams(jnp.concatenate(
            [part(qkvz, 0, Dk), part(qkvz, Dk, v0),
             part(qkvz, v0, v0 + r * Dv)], -1
        ), None),
        "gdn_g": LinearParams(part(qkvz, v0 + r * Dv, v0 + 2 * r * Dv), None),
        "gdn_ab": LinearParams(jnp.concatenate(
            [part(ba, r, 2 * r), part(ba, 0, r)], -1
        ), None),
        "gdn_o": LinearParams(mat("linear_attn.out_proj"), None),
        "gdn_conv": LinearParams(
            jnp.transpose(conv[:, :, 0, :], (0, 2, 1)), None
        ),
        "gdn_A_log": vec("A_log"),
        "gdn_dt_bias": vec("dt_bias"),
        "gdn_norm": norm("linear_attn.norm", zero_centred=False),
    })

    def experts(which):
        flat = ckpt.get_stacked_array(
            [f"model.layers.{i}.mlp.experts.{j}.{which}_proj.weight"
             for i in range(cfg.n_layers)
             for j in range(x.first, x.first + x.n_held)],
            mesh, P(None, None, None), transpose=True,
        )
        return flat.reshape((cfg.n_layers, x.n_held) + flat.shape[1:])

    params: Params = {
        "wte": ckpt.get_array("model.embed_tokens.weight", mesh, specs["wte"]),
        "blocks": blocks,
        "linear": linear,
        "experts": {f"experts_{k}": experts(k) for k in ("gate", "up", "down")},
        "ln_f": NormParams(
            scale=centred(
                ckpt.get_array("model.norm.weight", mesh, specs["ln_f"].scale)
            ),
            bias=None,
        ),
    }
    if not cfg.tie_word_embeddings:
        params["head"] = load_lm_head(
            ckpt, "lm_head.weight", mesh, transpose=True, bias=False
        )
    return params
