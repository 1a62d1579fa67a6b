"""Keye-VL-2.0's language model (``model_type`` ``KeyeVL2``;
Keye-VL-2.0-30B-A3B): one kind of layer, pre-norm with a sequential
residual, ``h += attn(norm(h)); h += experts(norm(h))``; RMSNorm, a final
norm, an untied head.

- Attention is grouped-query (32 heads on 4 KV heads of 128) with an RMSNorm
  over each head of the queries and of the keys and rotary over the whole
  head (rotate-half), over a LEARNED SELECTION of the context: every layer
  holds a DeepSeek-Sparse-Attention indexer (``sa_config``: 16 heads of 64,
  one key head, ``topk`` 2048) that scores each earlier position for a query
  and keeps the ``topk`` best; every head of the layer attends over those
  alone (``IndexerConfig``, ops/sparse_attention.py,
  docs/sparse-attention.md).
- The expert layer after every attention layer: a float32 softmax over
  ``num_experts``, the top ``num_experts_per_tok``, renormalised; NO shared
  expert (``ops/moe.py``). ``intermediate_size`` is not used
  (``mlp_only_layers`` [] and ``decoder_sparse_step`` 1 make every layer
  sparse).

The vision tower is not part of this family here: the catalog's config is
the language model's, traffic is token ids, and with text alone the three
position components of ``mrope_section`` are equal, so the rotary is the
ordinary one. Forms of the family that are not implemented are refused by
name; what one chip's SHARE of the experts is (``expert_parallel``) is in
docs/recurrent-state.md.
"""

from __future__ import annotations

from jax.sharding import Mesh, PartitionSpec as P

from llmss_tpu.models.common import (
    DecoderConfig, IndexerConfig, MoEConfig, experts_held,
)
from llmss_tpu.models.decoder import Params, param_specs
from llmss_tpu.ops.layers import LinearParams, NormParams, load_lm_head
from llmss_tpu.parallel.mesh import AXIS_TP
from llmss_tpu.weights.loader import CheckpointShards


def config_from_hf(hf, dtype: str = "bfloat16") -> DecoderConfig:
    def refuse(what):
        raise ValueError(f"KeyeVL2: {what} is not implemented")

    if getattr(hf, "mlp_only_layers", None):
        refuse(f"mlp_only_layers {hf.mlp_only_layers} (a dense MLP in some layers)")
    if getattr(hf, "decoder_sparse_step", 1) != 1:
        refuse(f"decoder_sparse_step {hf.decoder_sparse_step}")
    if getattr(hf, "use_sliding_window", False):
        refuse("use_sliding_window")
    if getattr(hf, "attention_bias", False):
        refuse("attention_bias")
    scaling = getattr(hf, "rope_scaling", None) or {}
    kind = scaling.get("rope_type", scaling.get("type", "default"))
    if kind != "default":
        refuse(f"rope_scaling of rope_type {kind!r}")
    sa = getattr(hf, "sa_config", None)
    if not sa:
        refuse("a model without sa_config (no indexer: dense attention)")
    if sa.get("indexer_num_kv_heads", 1) != 1:
        refuse(f"indexer_num_kv_heads {sa['indexer_num_kv_heads']}")
    head_dim = getattr(hf, "head_dim", None) or (
        hf.hidden_size // hf.num_attention_heads
    )
    n_experts, first, count = experts_held(hf, "KeyeVL2")
    return DecoderConfig(
        model_type="KeyeVL2",
        vocab_size=hf.vocab_size,
        hidden_size=hf.hidden_size,
        n_layers=hf.num_hidden_layers,
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
        rope_theta=float(hf.rope_theta),
        attn_bias=False,
        mlp_bias=False,
        tie_word_embeddings=getattr(hf, "tie_word_embeddings", False),
        moe=MoEConfig(
            n_experts=n_experts,
            top_k=hf.num_experts_per_tok,
            expert_size=hf.moe_intermediate_size,
            shared_size=0,
            n_dense_layers=0,
            norm_topk_prob=bool(hf.norm_topk_prob),
            scoring="softmax",
            first=first,
            count=count,
        ),
        qk_norm_per_head=True,
        indexer=IndexerConfig(
            n_heads=sa["indexer_num_heads"],
            head_dim=sa["indexer_head_dim"],
            topk=sa["topk"],
        ),
        dtype=dtype,
    )


def load_params(ckpt: CheckpointShards, cfg: DecoderConfig, mesh: Mesh) -> Params:
    """Every leaf under the name the published implementation gives it, as
    remembered (no network here, and no checkpoint to read: the backbone's
    names are Qwen3-MoE's, the indexer's DeepSeek-V3.2's ``self_attn.indexer.
    {wq, wk, k_norm, weights_proj}``; the round trip through a checkpoint
    written under these names is in tests/test_keye_vl2.py). A name that is
    not in the file raises in the loader. Of ``mlp.experts.{j}`` only the
    experts held here are read (``MoEConfig.first`` / ``count``)."""
    specs = param_specs(cfg, mesh.shape[AXIS_TP])
    rep = P(None, None, None)
    x = cfg.moe

    def names(attr):
        return [f"model.layers.{i}.{attr}" for i in range(cfg.n_layers)]

    def mat(attr, spec=rep, transpose=True):
        # torch Linear stores [out, in]: every matrix is [in, out] here but
        # attention's q and k, and the router
        return ckpt.get_stacked_array(
            names(f"{attr}.weight"), mesh, spec, transpose=transpose
        )

    def vec(attr):
        return ckpt.get_stacked_array(names(attr), mesh, P(None, None))

    blocks = {
        "ln1": NormParams(vec("input_layernorm.weight"), None),
        "ln2": NormParams(vec("post_attention_layernorm.weight"), None),
        "q_norm": NormParams(vec("self_attn.q_norm.weight"), None),
        "k_norm": NormParams(vec("self_attn.k_norm.weight"), None),
        "router": LinearParams(mat("mlp.gate", transpose=False), None),
        "idx_q": LinearParams(mat("self_attn.indexer.wq"), None),
        "idx_k": LinearParams(mat("self_attn.indexer.wk"), None),
        "idx_w": LinearParams(mat("self_attn.indexer.weights_proj"), None),
        "idx_k_norm": NormParams(
            vec("self_attn.indexer.k_norm.weight"),
            vec("self_attn.indexer.k_norm.bias"),
        ),
    }
    for key in ("q", "k", "v", "o"):
        blocks[key] = LinearParams(mat(
            f"self_attn.{key}_proj", specs["blocks"][key].w,
            transpose=key not in ("q", "k"),
        ), None)

    def experts(which):
        flat = ckpt.get_stacked_array(
            [f"model.layers.{i}.mlp.experts.{j}.{which}_proj.weight"
             for i in range(cfg.n_layers)
             for j in range(x.first, x.first + x.n_held)],
            mesh, rep, transpose=True,
        )
        return flat.reshape((cfg.n_layers, x.n_held) + flat.shape[1:])

    params: Params = {
        "wte": ckpt.get_array("model.embed_tokens.weight", mesh, specs["wte"]),
        "blocks": blocks,
        "experts": {f"experts_{k}": experts(k) for k in ("gate", "up", "down")},
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
