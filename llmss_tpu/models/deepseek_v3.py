"""DeepSeek-V3 family (``model_type`` ``deepseek_v3``; DeepSeek-AI,
"DeepSeek-V3 Technical Report", 2024) as ``kanana-2-30b-a3b`` configures it:
multi-head latent attention WITHOUT a query low-rank, a leading stack of
dense SwiGLU layers, then layers of sigmoid-routed experts (top-k of
``n_routed_experts`` by score plus a selection-only bias, renormalised,
times ``routed_scaling_factor``) beside one shared SwiGLU.

The cached quantity is the 576-wide latent (``kv_lora_rank`` normed numbers
beside the one shared rotary key) in ONE paged pool; the program attends in
the absorbed form (``models/decoder.py: _latent_attention``,
``_forward_latent``). What each serving feature does with that pool is in
``docs/latent-cache.md``. Forms of the family that are not implemented are
refused by name here.
"""

from __future__ import annotations

from jax.sharding import Mesh, PartitionSpec as P

from llmss_tpu.models._loading import stacked_norm
from llmss_tpu.models.common import DecoderConfig, MLAConfig, MoEConfig
from llmss_tpu.models.decoder import Params, param_specs
from llmss_tpu.ops.layers import LinearParams, NormParams, load_lm_head
from llmss_tpu.parallel.mesh import AXIS_TP
from llmss_tpu.weights.loader import CheckpointShards


def config_from_hf(hf, dtype: str = "bfloat16") -> DecoderConfig:
    def refuse(what):
        raise ValueError(f"deepseek_v3: {what} is not implemented")

    if getattr(hf, "q_lora_rank", None) is not None:
        refuse("a query low-rank projection (q_lora_rank not null)")
    if getattr(hf, "n_group", 1) != 1 or getattr(hf, "topk_group", 1) != 1:
        refuse("group-limited routing (n_group / topk_group above 1)")
    if getattr(hf, "rope_scaling", None):
        refuse("rope_scaling")
    if getattr(hf, "scoring_func", "sigmoid") != "sigmoid":
        refuse(f"scoring_func {hf.scoring_func!r}")
    if getattr(hf, "topk_method", "noaux_tc") != "noaux_tc":
        refuse(f"topk_method {hf.topk_method!r}")
    if getattr(hf, "attention_bias", False):
        refuse("attention_bias")
    if getattr(hf, "moe_layer_freq", 1) != 1:
        refuse("moe_layer_freq other than 1")
    if not getattr(hf, "rope_interleave", True):
        refuse("rope_interleave false (the half-rotation layout)")
    mla = MLAConfig(
        kv_lora_rank=hf.kv_lora_rank,
        qk_nope_head_dim=hf.qk_nope_head_dim,
        qk_rope_head_dim=hf.qk_rope_head_dim,
        v_head_dim=hf.v_head_dim,
    )
    n_dense = hf.first_k_dense_replace
    if not 0 <= n_dense < hf.num_hidden_layers:
        refuse(
            f"first_k_dense_replace {n_dense} of {hf.num_hidden_layers} "
            "layers (at least one expert layer is needed)"
        )
    moe = MoEConfig(
        n_experts=hf.n_routed_experts,
        top_k=hf.num_experts_per_tok,
        expert_size=hf.moe_intermediate_size,
        shared_size=hf.n_shared_experts * hf.moe_intermediate_size,
        n_dense_layers=n_dense,
        routed_scaling_factor=float(hf.routed_scaling_factor),
        norm_topk_prob=bool(hf.norm_topk_prob),
    )
    return DecoderConfig(
        model_type="deepseek_v3",
        vocab_size=hf.vocab_size,
        hidden_size=hf.hidden_size,
        n_layers=hf.num_hidden_layers,
        n_heads=hf.num_attention_heads,
        # what is cached has ONE head, the latent (``cache_row``); the
        # published num_key_value_heads counts the heads keys and values
        # are rebuilt into, which the absorbed form never does
        n_kv_heads=1,
        head_dim=mla.qk_head_dim,
        intermediate_size=hf.intermediate_size,
        max_position_embeddings=hf.max_position_embeddings,
        activation=hf.hidden_act,
        norm="rmsnorm",
        norm_eps=hf.rms_norm_eps,
        mlp="swiglu",
        positions="rotary",
        rope_style="interleaved",
        rotary_dim=mla.qk_rope_head_dim,
        rope_theta=float(getattr(hf, "rope_theta", 10000.0)),
        attn_bias=False,
        mlp_bias=False,
        tie_word_embeddings=getattr(hf, "tie_word_embeddings", False),
        attn_scale=mla.qk_head_dim ** -0.5,
        mla=mla,
        moe=moe,
        dtype=dtype,
    )


def load_params(
    ckpt: CheckpointShards, cfg: DecoderConfig, mesh: Mesh,
) -> Params:
    """Every leaf under the name the published implementation gives it, as
    remembered (no network here and no checkpoint to read; the round trip
    through a checkpoint written under these names is in
    tests/test_deepseek_v3.py). A name that is not in the file raises in
    the loader: nothing is skipped, nothing is folded."""
    specs = param_specs(cfg, mesh.shape[AXIS_TP])
    n_lead, N = cfg.n_lead_layers, cfg.moe.n_experts

    def stack(layers, bspecs, mlp):
        def names(attr):
            return [f"model.layers.{i}.{attr}" for i in layers]

        def lin(attr, key, transpose=True):
            # torch Linear stores [out, in]; q is kept that way
            return LinearParams(ckpt.get_stacked_array(
                names(f"{attr}.weight"), mesh, bspecs[key].w,
                transpose=transpose,
            ), None)

        def norm(attr):
            return stacked_norm(
                ckpt, lambda i: f"model.layers.{layers[i]}.{attr}",
                len(layers), mesh, bias=False,
            )

        return {
            "ln1": norm("input_layernorm"),
            "ln2": norm("post_attention_layernorm"),
            "q": lin("self_attn.q_proj", "q", transpose=False),
            "kv_a": lin("self_attn.kv_a_proj_with_mqa", "kv_a"),
            "kv_norm": norm("self_attn.kv_a_layernorm"),
            "kv_b": lin("self_attn.kv_b_proj", "kv_b"),
            "o": lin("self_attn.o_proj", "o"),
            **mlp(names, lin),
        }

    def dense_mlp(names, lin):
        return {k: lin(f"mlp.{k}_proj", k) for k in ("gate", "up", "down")}

    def expert_mlp(names, lin):
        def experts(which):
            flat = ckpt.get_stacked_array(
                [n for base in names("mlp.experts") for n in (
                    f"{base}.{j}.{which}_proj.weight" for j in range(N)
                )], mesh, P(None, None, None), transpose=True,
            )
            return flat.reshape((-1, N) + flat.shape[1:])

        return {
            "router": LinearParams(
                ckpt.get_stacked_array(
                    names("mlp.gate.weight"), mesh, P(None, None, None)
                ),
                ckpt.get_stacked_array(
                    names("mlp.gate.e_score_correction_bias"), mesh,
                    P(None, None),
                ),
            ),
            **{f"experts_{k}": experts(k) for k in ("gate", "up", "down")},
            **{f"shared_{k}": lin(
                f"mlp.shared_experts.{k}_proj", f"shared_{k}"
            ) for k in ("gate", "up", "down")},
        }

    params: Params = {
        "wte": ckpt.get_array("model.embed_tokens.weight", mesh, specs["wte"]),
        "blocks": stack(
            range(n_lead, cfg.n_layers), specs["blocks"], expert_mlp
        ),
        "ln_f": NormParams(
            scale=ckpt.get_array(
                "model.norm.weight", mesh, specs["ln_f"].scale
            ),
            bias=None,
        ),
    }
    if n_lead:
        params["lead"] = stack(range(n_lead), specs["lead"], dense_mlp)
    if not cfg.tie_word_embeddings:
        params["head"] = load_lm_head(
            ckpt, "lm_head.weight", mesh, transpose=True, bias=False
        )
    return params
