"""Olmo-Hybrid family: layers of two KINDS in a repeating pattern
(``layer_types``: three ``linear_attention`` layers to every
``full_attention`` layer in Olmo-Hybrid-7B), each followed by a SwiGLU MLP.

- A linear-attention layer is a Gated DeltaNet mixer (Yang, Kautz,
  Hatamizadeh 2024, as flash-linear-attention's ``GatedDeltaNet`` layer
  parameterises it; ``ops/gdn.py`` has the recurrence): it holds a float32
  state of ``[key_head_dim, value_head_dim]`` a head and a convolution window,
  and NO keys and values.
- A full-attention layer is Olmo 2's: RMSNorm over the WHOLE query and key
  projection, causal softmax, and no rotary embedding (``rope_theta`` null:
  the recurrent layers carry position).
- The block, both kinds, is Olmo 2's "reordered norm": ``h = h +
  RMSNorm(mixer(h))``, ``h = h + RMSNorm(MLP(h))``; a final RMSNorm and an
  untied head.

The two kinds are two stacks of the parameter tree (``params["blocks"]``: the
attention layers, ``params["linear"]``: the linear-attention layers) that
``models/decoder.py: _layer_scan`` walks by period, and two pools of the paged
cache with layer axes of their own (``engine/cache.py``); what each serving
feature does with the state is in ``docs/recurrent-state.md``.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from llmss_tpu.models._loading import stacked_norm
from llmss_tpu.models.common import DecoderConfig, LinearAttnConfig
from llmss_tpu.models.decoder import Params, param_specs
from llmss_tpu.ops.layers import LinearParams, NormParams, load_lm_head
from llmss_tpu.parallel.mesh import AXIS_TP
from llmss_tpu.weights.loader import CheckpointShards

KINDS = ("linear_attention", "full_attention")


def config_from_hf(hf, dtype: str = "bfloat16") -> DecoderConfig:
    types = tuple(hf.layer_types)
    if len(types) != hf.num_hidden_layers or set(types) - set(KINDS):
        raise ValueError(
            f"olmo_hybrid: layer_types must name {hf.num_hidden_layers} "
            f"layers of kinds {KINDS}, got {len(types)}: {sorted(set(types))}"
        )
    if not set(KINDS) <= set(types):
        raise ValueError(
            "olmo_hybrid: a stack of one kind of layer is another family's "
            "(both kinds must appear in layer_types)"
        )
    if getattr(hf, "attention_bias", False):
        raise ValueError("olmo_hybrid: projection biases are not implemented")
    rope = getattr(hf, "rope_parameters", None) or {}
    if rope.get("rope_theta") is not None:
        raise ValueError(
            "olmo_hybrid: a rotary embedding in the attention layers is not "
            f"implemented (rope_parameters {rope}); the published model has "
            "none"
        )
    if hf.linear_num_value_heads % hf.linear_num_key_heads:
        raise ValueError(
            "olmo_hybrid: linear_num_value_heads "
            f"{hf.linear_num_value_heads} must be a multiple of "
            f"linear_num_key_heads {hf.linear_num_key_heads} (each key head "
            "serves a whole group of value heads)"
        )
    n_heads = hf.num_attention_heads
    return DecoderConfig(
        model_type="olmo_hybrid",
        vocab_size=hf.vocab_size,
        hidden_size=hf.hidden_size,
        n_layers=hf.num_hidden_layers,
        n_heads=n_heads,
        n_kv_heads=getattr(hf, "num_key_value_heads", None) or n_heads,
        head_dim=getattr(hf, "head_dim", None) or hf.hidden_size // n_heads,
        intermediate_size=hf.intermediate_size,
        max_position_embeddings=hf.max_position_embeddings,
        activation=hf.hidden_act,
        norm="rmsnorm",
        norm_eps=hf.rms_norm_eps,
        mlp="swiglu",
        positions="none",
        attn_bias=False,
        mlp_bias=False,
        tie_word_embeddings=getattr(hf, "tie_word_embeddings", False),
        layer_types=types,
        linear_attn=LinearAttnConfig(
            n_heads=hf.linear_num_key_heads,
            key_head_dim=hf.linear_key_head_dim,
            value_head_dim=hf.linear_value_head_dim,
            d_conv=hf.linear_conv_kernel_dim,
            allow_neg_eigval=bool(hf.linear_allow_neg_eigval),
            n_value_heads=hf.linear_num_value_heads,
        ),
        post_norm=True,
        qk_norm=True,
        dtype=dtype,
    )


def load_params(ckpt: CheckpointShards, cfg: DecoderConfig, mesh: Mesh) -> Params:
    """Every leaf under the name the published implementation gives it, as
    remembered (no network here, and no checkpoint to read; the round trip
    through a checkpoint written under these names is in
    tests/test_olmo_hybrid.py): Olmo 2's names for the attention layers, the
    MLP and the norms, flash-linear-attention's for the mixer under
    ``linear_attn``. The three projections q, k, v and their three
    convolutions are concatenated into one leaf each, a and b likewise. A
    name that is not in the file raises in the loader."""
    specs = param_specs(cfg, mesh.shape[AXIS_TP])
    rep = P(None, None, None)

    def stack_of(kind, sp):
        """The loaders of one kind's layers, stacked in the model's order,
        and what both kinds hold: the two norms and the MLP."""
        ids = [i for i, t in enumerate(cfg.layer_types) if t == kind]

        def names(attr):
            return [f"model.layers.{i}.{attr}" for i in ids]

        def mat(attr, spec=rep, transpose=True):
            # torch Linear stores [out, in]: every matrix is [in, out] here
            # but the attention layers' q and k (decoder.param_specs)
            return ckpt.get_stacked_array(
                names(f"{attr}.weight"), mesh, spec, transpose=transpose
            )

        def norm(attr):
            return stacked_norm(
                ckpt, lambda j: f"model.layers.{ids[j]}.{attr}", len(ids),
                mesh, bias=False,
            )

        shared = {
            "ln1": norm("post_attention_layernorm"),
            "ln2": norm("post_feedforward_layernorm"),
            **{key: LinearParams(mat(f"mlp.{key}_proj", sp[key].w), None)
               for key in ("gate", "up", "down")},
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

    def cat(attrs):
        return jnp.concatenate([mat(f"linear_attn.{a}") for a in attrs], -1)

    def conv(attr):  # [L, C, 1, K] as published -> [L, K, C]
        w = ckpt.get_stacked_array(
            names(f"linear_attn.{attr}.weight"), mesh, P(None, None, None, None)
        )
        return jnp.transpose(w[:, :, 0, :], (0, 2, 1))

    def vec(attr):
        return ckpt.get_stacked_array(
            names(f"linear_attn.{attr}"), mesh, P(None, None)
        )

    linear.update({
        "gdn_qkv": LinearParams(cat(["q_proj", "k_proj", "v_proj"]), None),
        "gdn_ab": LinearParams(cat(["a_proj", "b_proj"]), None),
        "gdn_g": LinearParams(mat("linear_attn.g_proj"), None),
        "gdn_o": LinearParams(mat("linear_attn.o_proj"), None),
        "gdn_conv": LinearParams(jnp.concatenate(
            [conv(a) for a in ("q_conv1d", "k_conv1d", "v_conv1d")], -1
        ), None),
        "gdn_A_log": vec("A_log"),
        "gdn_dt_bias": vec("dt_bias"),
        "gdn_norm": norm("linear_attn.o_norm"),
    })
    params: Params = {
        "wte": ckpt.get_array("model.embed_tokens.weight", mesh, specs["wte"]),
        "blocks": blocks,
        "linear": linear,
        "ln_f": NormParams(
            scale=ckpt.get_array("model.norm.weight", mesh, specs["ln_f"].scale),
            bias=None,
        ),
    }
    if not cfg.tie_word_embeddings:
        params["head"] = load_lm_head(
            ckpt, "lm_head.weight", mesh, transpose=True, bias=False
        )
    return params
