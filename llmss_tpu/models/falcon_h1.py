"""Falcon-H1 family: every block runs a Mamba-2 mixer AND grouped-query
attention on the same normed input, adds both to the residual, then a SwiGLU
MLP (Zuo et al., "Falcon-H1", 2025; ``FalconH1Config``).

Attention is Llama's (GQA, half-style RoPE, RMSNorm, no bias) with a
``head_dim`` that is a key of the config (20 heads of 128 on a hidden size of
5120 at 34B: ``q_size`` differs from ``hidden_size``). Twelve kinds of fixed
scalar (muP multipliers) scale the embedding, the keys, each branch's input
and output, the five segments of the mixer's input projection, the MLP's gate
and output, and the logits; they are fields of the configuration and are
applied in the forward (``models/decoder.py``), none folded into a weight, so a
checkpoint's leaves load as published.

The mixer's state lives beside the paged keys and values
(``engine/cache.py: PagedKVCache.ssm`` / ``.conv``); what each serving feature
does with it is in ``docs/recurrent-state.md``.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from llmss_tpu.models._loading import stacked_norm
from llmss_tpu.models.common import DecoderConfig, SSMConfig
from llmss_tpu.models.decoder import Params, param_specs
from llmss_tpu.ops.layers import LinearParams, NormParams, load_lm_head
from llmss_tpu.parallel.mesh import AXIS_TP
from llmss_tpu.weights.loader import CheckpointShards


def config_from_hf(hf, dtype: str = "bfloat16") -> DecoderConfig:
    if not getattr(hf, "mamba_rms_norm", True) or getattr(
        hf, "mamba_norm_before_gate", False
    ):
        raise ValueError(
            "falcon_h1: only the published form of the mixer's output norm "
            "is implemented (mamba_rms_norm true, mamba_norm_before_gate "
            "false: gate, then RMSNorm a group)"
        )
    if not getattr(hf, "mamba_use_mlp", True):
        raise ValueError("falcon_h1: a block without its MLP is not implemented")
    if any(getattr(hf, k, False) for k in (
        "attention_bias", "mlp_bias", "mamba_proj_bias", "projectors_bias",
    )):
        raise ValueError("falcon_h1: projection biases are not implemented")
    if getattr(hf, "rope_scaling", None):
        raise ValueError("falcon_h1: rope_scaling is not implemented")
    n_heads = hf.num_attention_heads
    head_dim = getattr(hf, "head_dim", None) or hf.hidden_size // n_heads
    d_ssm = getattr(hf, "mamba_d_ssm", None) or (
        hf.mamba_expand * hf.hidden_size
    )
    ssm = SSMConfig(
        d_ssm=d_ssm,
        n_heads=hf.mamba_n_heads,
        head_dim=hf.mamba_d_head,
        n_groups=hf.mamba_n_groups,
        d_state=hf.mamba_d_state,
        d_conv=hf.mamba_d_conv,
        chunk_size=hf.mamba_chunk_size,
        in_multiplier=float(hf.ssm_in_multiplier),
        out_multiplier=float(hf.ssm_out_multiplier),
        multipliers=tuple(float(m) for m in hf.ssm_multipliers),
    )
    if ssm.n_heads * ssm.head_dim != d_ssm or len(ssm.multipliers) != 5:
        raise ValueError(
            f"falcon_h1: mamba_n_heads x mamba_d_head != mamba_d_ssm "
            f"({ssm.n_heads} x {ssm.head_dim} vs {d_ssm}), or ssm_multipliers "
            "is not five numbers"
        )
    if not getattr(hf, "mamba_conv_bias", True):
        raise ValueError("falcon_h1: a convolution without bias is not implemented")
    return DecoderConfig(
        model_type="falcon_h1",
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
        mlp="swiglu",
        positions="rotary",
        rope_style="half",
        rotary_dim=head_dim,
        rope_theta=float(getattr(hf, "rope_theta", 10000.0)),
        attn_bias=False,
        mlp_bias=False,
        tie_word_embeddings=getattr(hf, "tie_word_embeddings", False),
        embed_multiplier=float(hf.embedding_multiplier),
        ssm=ssm,
        attn_in_multiplier=float(hf.attention_in_multiplier),
        key_multiplier=float(hf.key_multiplier),
        attn_out_multiplier=float(hf.attention_out_multiplier),
        mlp_multipliers=tuple(float(m) for m in hf.mlp_multipliers),
        lm_head_multiplier=float(hf.lm_head_multiplier),
        dtype=dtype,
    )


def load_params(ckpt: CheckpointShards, cfg: DecoderConfig, mesh: Mesh) -> Params:
    """Every leaf under the name the published implementation gives it, as
    remembered (no network here, and no checkpoint to read; the round trip
    through a checkpoint written under these names is in
    tests/test_falcon_h1.py). A name that is not in the file raises in the
    loader: nothing is skipped, nothing is folded."""
    specs = param_specs(cfg, mesh.shape[AXIS_TP])
    L = cfg.n_layers
    bspecs = specs["blocks"]

    def names(attr):
        return [f"model.layers.{i}.{attr}" for i in range(L)]

    def lin(attr, key):
        # torch Linear stores [out, in]; q and k are kept that way
        # (decoder.param_specs), every other matrix is [in, out] here
        return LinearParams(ckpt.get_stacked_array(
            names(f"{attr}.weight"), mesh, bspecs[key].w,
            transpose=key not in ("q", "k"),
        ), None)

    def norm(attr):
        return stacked_norm(
            ckpt, lambda i: f"model.layers.{i}.{attr}", L, mesh, bias=False
        )

    def vec(attr):
        return ckpt.get_stacked_array(names(attr), mesh, P(None, None))

    conv_w = ckpt.get_stacked_array(  # [L, C, 1, K] as published
        names("mamba.conv1d.weight"), mesh, P(None, None, None, None)
    )
    blocks: Params = {
        "ln1": norm("input_layernorm"),
        "ln2": norm("pre_ff_layernorm"),
        "q": lin("self_attn.q_proj", "q"),
        "k": lin("self_attn.k_proj", "k"),
        "v": lin("self_attn.v_proj", "v"),
        "o": lin("self_attn.o_proj", "o"),
        "gate": lin("feed_forward.gate_proj", "gate"),
        "up": lin("feed_forward.up_proj", "up"),
        "down": lin("feed_forward.down_proj", "down"),
        "ssm_in": lin("mamba.in_proj", "ssm_in"),
        "ssm_conv": LinearParams(
            jnp.transpose(conv_w[:, :, 0, :], (0, 2, 1)),  # [L, K, C]
            vec("mamba.conv1d.bias"),
        ),
        "ssm_dt_bias": vec("mamba.dt_bias"),
        "ssm_A_log": vec("mamba.A_log"),
        "ssm_D": vec("mamba.D"),
        "ssm_norm": norm("mamba.norm"),
        "ssm_out": lin("mamba.out_proj", "ssm_out"),
    }
    params: Params = {
        "wte": ckpt.get_array("model.embed_tokens.weight", mesh, specs["wte"]),
        "blocks": blocks,
        "ln_f": NormParams(
            scale=ckpt.get_array(
                "model.final_layernorm.weight", mesh, specs["ln_f"].scale
            ),
            bias=None,
        ),
    }
    if not cfg.tie_word_embeddings:
        params["head"] = load_lm_head(
            ckpt, "lm_head.weight", mesh, transpose=True, bias=False
        )
    return params
