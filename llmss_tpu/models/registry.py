"""Model registry keyed by HF ``config.model_type``.

≙ reference ``custom_modeling/__init__.py:4-7`` (``MODEL_REGISTRY``), plus
the one-stop ``load_model`` that replaces the construction path
``MODEL_REGISTRY[model_type](config, weights)`` (``generate.py:64-67``,
``consumer_server.py:57-60``).
"""

from __future__ import annotations

from pathlib import Path

from jax.sharding import Mesh

from llmss_tpu.models import (
    deepseek_v3, falcon_h1, gemma, gpt2, gpt_bigcode, gpt_neox, gptj, keye_vl2,
    llama, mistral, olmo_hybrid, phi3, qwen2, qwen3_next,
)
from llmss_tpu.models.common import DecoderConfig
from llmss_tpu.models.decoder import Params
from llmss_tpu.utils import devtel
from llmss_tpu.weights import CheckpointShards, weight_files

MODEL_REGISTRY = {
    "gptj": gptj,
    "gpt_bigcode": gpt_bigcode,
    "gpt2": gpt2,
    "llama": llama,
    "mistral": mistral,
    "qwen2": qwen2,
    "gpt_neox": gpt_neox,
    "phi3": phi3,
    "gemma": gemma,
    "falcon_h1": falcon_h1,
    "deepseek_v3": deepseek_v3,
    "olmo_hybrid": olmo_hybrid,
    "qwen3_next": qwen3_next,
    "KeyeVL2": keye_vl2,
}


def config_from_hf(hf_config, dtype: str = "bfloat16") -> DecoderConfig:
    mt = hf_config.model_type
    if mt not in MODEL_REGISTRY:
        raise KeyError(
            f"model_type {mt!r} not supported; have {sorted(MODEL_REGISTRY)}"
        )
    return MODEL_REGISTRY[mt].config_from_hf(hf_config, dtype=dtype)


def load_model(
    model_path: str | Path,
    mesh: Mesh,
    dtype: str = "bfloat16",
    revision: str | None = None,
) -> tuple[DecoderConfig, Params]:
    """Resolve config + weights and build sharded params on the mesh."""
    from transformers import AutoConfig

    hf_config = AutoConfig.from_pretrained(model_path, revision=revision)
    cfg = config_from_hf(hf_config, dtype=dtype)
    # The span times the host's part (reading the shards, placing them): the
    # device may still be receiving the arrays when it closes.
    with devtel.setup_span("setup.weights") as sp:
        files = weight_files(str(model_path), revision=revision)
        ckpt = CheckpointShards(files, dtype=cfg.compute_dtype)
        params = MODEL_REGISTRY[cfg.model_type].load_params(ckpt, cfg, mesh)
        sp.set(bytes=devtel.tree_bytes(params))
    return cfg, params
