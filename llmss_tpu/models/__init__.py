"""Model zoo: pure-function decoders over parameter pytrees.

TPU-native replacement for the reference's ``custom_modeling/`` (GPT-J,
GPT-BigCode), extended with GPT-2 and Llama, Mistral (sliding-window
attention), Qwen2 (split q/kv vs out bias granularity), and
GPT-NeoX/Pythia (fused head-interleaved QKV, partial rotary, NeoX
parallel residual), Phi-3 (contiguous fused
qkv/gate_up splits via sliced reads), and Gemma ((1+w) RMSNorm, scaled
embeddings, tied head).
All models share one unified decoder (``decoder.py``) driven by a
``DecoderConfig``; per-model modules translate HF configs and checkpoint
name layouts.
"""

from llmss_tpu.models.common import DecoderConfig
from llmss_tpu.models.registry import MODEL_REGISTRY, config_from_hf, load_model

__all__ = ["DecoderConfig", "MODEL_REGISTRY", "config_from_hf", "load_model"]
