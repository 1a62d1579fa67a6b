"""GPT-2: MHA, learned positions, Conv1D checkpoints, tied head.

Not in the reference's registry. Structurally GPT-BigCode minus MQA, with HF
Conv1D weight layout — already [in, out], so no transpose on load — and a
fused ``c_attn`` of 3×E split by sub-range reads.
"""

from __future__ import annotations

from jax.sharding import Mesh

from llmss_tpu.models._loading import stacked_linear, stacked_norm
from llmss_tpu.models.common import DecoderConfig
from llmss_tpu.models.decoder import Params, param_specs
from llmss_tpu.ops.layers import load_norm
from llmss_tpu.parallel.mesh import AXIS_TP
from llmss_tpu.weights.loader import CheckpointShards

def config_from_hf(hf, dtype: str = "bfloat16") -> DecoderConfig:
    return DecoderConfig(
        model_type="gpt2",
        vocab_size=hf.vocab_size,
        hidden_size=hf.n_embd,
        n_layers=hf.n_layer,
        n_heads=hf.n_head,
        n_kv_heads=hf.n_head,
        head_dim=hf.n_embd // hf.n_head,
        intermediate_size=hf.n_inner or 4 * hf.n_embd,
        max_position_embeddings=hf.n_positions,
        activation=hf.activation_function,
        norm="layernorm",
        norm_eps=hf.layer_norm_epsilon,
        parallel_residual=False,
        mlp="mlp",
        positions="learned",
        attn_bias=True,
        mlp_bias=True,
        tie_word_embeddings=True,
        dtype=dtype,
    )


def load_params(
    ckpt: CheckpointShards, cfg: DecoderConfig, mesh: Mesh
) -> Params:
    specs = param_specs(cfg, mesh.shape[AXIS_TP])
    L, E = cfg.n_layers, cfg.hidden_size

    def name(i, attr):
        n = f"h.{i}.{attr}"
        return n if n in ckpt else f"transformer.{n}"

    def split_attn(key, lo, hi):
        # Conv1D c_attn is already [E, 3E]: Q|K|V along the output axis.
        # q/k store [L, out, in] (decoder.param_specs), so they read the
        # transposed view with the split range on axis 0; v keeps [in, out].
        t = key in ("q", "k")
        return stacked_linear(
            ckpt, lambda i: name(i, "attn.c_attn"), L, mesh,
            specs["blocks"][key].w, specs["blocks"][key].b,
            transpose=t, sub=(0 if t else 1, lo, hi),
        )

    def lin(attr, key):
        return stacked_linear(
            ckpt, lambda i: name(i, attr), L, mesh,
            specs["blocks"][key].w, specs["blocks"][key].b, transpose=False,
        )

    def top(n):
        return n if n in ckpt else f"transformer.{n}"

    blocks: Params = {
        "ln1": stacked_norm(ckpt, lambda i: name(i, "ln_1"), L, mesh),
        "ln2": stacked_norm(ckpt, lambda i: name(i, "ln_2"), L, mesh),
        "q": split_attn("q", 0, E),
        "k": split_attn("k", E, 2 * E),
        "v": split_attn("v", 2 * E, 3 * E),
        "o": lin("attn.c_proj", "o"),
        "fc_in": lin("mlp.c_fc", "fc_in"),
        "fc_out": lin("mlp.c_proj", "fc_out"),
    }
    return {
        "wte": ckpt.get_array(top("wte.weight"), mesh, specs["wte"]),
        "wpe": ckpt.get_array(top("wpe.weight"), mesh, specs["wpe"]),
        "blocks": blocks,
        "ln_f": load_norm(ckpt, top("ln_f"), mesh),
    }
