"""Llama family: GQA, rotary (half style), RMSNorm, SwiGLU.

Not in the reference's registry. Covers Llama 1/2/3-style checkpoints (GQA via
``num_key_value_heads``; ``rope_theta``; optional tied embeddings for the
small Llama-3.2 variants).
"""

from __future__ import annotations

from jax.sharding import Mesh

from llmss_tpu.models._loading import stacked_linear, stacked_norm
from llmss_tpu.models.common import DecoderConfig
from llmss_tpu.models.decoder import Params, param_specs
from llmss_tpu.ops.layers import NormParams, load_lm_head
from llmss_tpu.parallel.mesh import AXIS_TP
from llmss_tpu.weights.loader import CheckpointShards


def config_from_hf(hf, dtype: str = "bfloat16") -> DecoderConfig:
    n_heads = hf.num_attention_heads
    head_dim = getattr(hf, "head_dim", None) or hf.hidden_size // n_heads
    return DecoderConfig(
        model_type="llama",
        vocab_size=hf.vocab_size,
        hidden_size=hf.hidden_size,
        n_layers=hf.num_hidden_layers,
        n_heads=n_heads,
        n_kv_heads=getattr(hf, "num_key_value_heads", None) or n_heads,
        head_dim=head_dim,
        intermediate_size=hf.intermediate_size,
        max_position_embeddings=hf.max_position_embeddings,
        activation=hf.hidden_act,
        norm="rmsnorm",
        norm_eps=hf.rms_norm_eps,
        parallel_residual=False,
        mlp="swiglu",
        positions="rotary",
        rope_style="half",
        rotary_dim=head_dim,
        rope_theta=getattr(hf, "rope_theta", 10000.0),
        # Llama-architecture conversions may carry attention biases
        # (LlamaConfig.attention_bias, e.g. InternLM/Yi-style exports);
        # the spec must agree with what the loader's bias auto-detect
        # finds on disk.
        attn_bias=bool(getattr(hf, "attention_bias", False)),
        mlp_bias=False,
        tie_word_embeddings=getattr(hf, "tie_word_embeddings", False),
        dtype=dtype,
    )


def load_params(
    ckpt: CheckpointShards, cfg: DecoderConfig, mesh: Mesh,
    overrides=None,
) -> Params:
    """``overrides`` maps a block key ("q", "gate", …) to a
    ``(ckpt, cfg, mesh, specs) -> LinearParams`` factory — how families
    with Llama-identical structure but fused checkpoint tensors (Phi-3)
    reuse this loader instead of copying it."""
    specs = param_specs(cfg, mesh.shape[AXIS_TP])
    L = cfg.n_layers
    layers = "model.layers"

    def lin(attr, key):
        # q/k store [L, out, in] (decoder.param_specs) — the torch Linear
        # disk layout is already [out, in], so they load untransposed.
        # bias=True auto-detects: Llama checkpoints carry none; Qwen2
        # (which delegates here) has q/k/v biases but no o/mlp biases.
        return stacked_linear(
            ckpt, lambda i: f"{layers}.{i}.{attr}", L, mesh,
            specs["blocks"][key].w, specs["blocks"][key].b,
            transpose=key not in ("q", "k"), bias=True,
        )

    def entry(attr, key):
        if overrides and key in overrides:
            return overrides[key](ckpt, cfg, mesh, specs)
        return lin(attr, key)

    blocks: Params = {
        "ln1": stacked_norm(
            ckpt, lambda i: f"{layers}.{i}.input_layernorm", L, mesh,
            bias=False,
        ),
        "ln2": stacked_norm(
            ckpt, lambda i: f"{layers}.{i}.post_attention_layernorm", L, mesh,
            bias=False,
        ),
        "q": entry("self_attn.q_proj", "q"),
        "k": entry("self_attn.k_proj", "k"),
        "v": entry("self_attn.v_proj", "v"),
        "o": entry("self_attn.o_proj", "o"),
        "gate": entry("mlp.gate_proj", "gate"),
        "up": entry("mlp.up_proj", "up"),
        "down": entry("mlp.down_proj", "down"),
    }
    params: Params = {
        "wte": ckpt.get_array(
            "model.embed_tokens.weight", mesh, specs["wte"]
        ),
        "blocks": blocks,
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
