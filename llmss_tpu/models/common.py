"""Unified decoder configuration.

One frozen config drives the shared decoder for every supported family; the
flags cover exactly the structural axes on which the reference's two models
(and the BASELINE extensions) differ:

==================  =========  ============  =======  ========
axis                GPT-J      GPT-BigCode   GPT-2    Llama
==================  =========  ============  =======  ========
attention           MHA        MQA (1 kv)    MHA      GQA
positions           rotary     learned       learned  rotary
rope style          interleav  —             —        half
residual            parallel   sequential    seq.     seq.
norm                LN         LN            LN       RMSNorm
mlp                 fc/fc      fc/fc         fc/fc    SwiGLU
tied head           no         yes           yes      no
==================  =========  ============  =======  ========

(Reference structure: GPT-J parallel residual ``gptj_modeling.py:295-310``;
BigCode MQA ``gpt_bigcode_modeling.py:84-85,120-155``, two vocab-parallel
embeddings wte+wpe ``:564-565``, tied head ``:792-797``.)
"""

from __future__ import annotations

import dataclasses

import jax


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """A Mamba-2 mixer that runs BESIDE attention in every block (Falcon-H1):
    both branches read the same normed input and add to the residual. The
    sizes are the published config's; the multipliers are fixed scalars of
    the architecture (muP), applied in the forward and never folded into a
    weight, so a checkpoint's leaves load as published."""

    d_ssm: int  # n_heads * head_dim
    n_heads: int
    head_dim: int
    n_groups: int  # B and C are shared by n_heads / n_groups heads
    d_state: int
    d_conv: int
    chunk_size: int = 128  # prefill scan chunk
    in_multiplier: float = 1.0
    out_multiplier: float = 1.0
    # on the five segments of the input projection, in order z, x, B, C, dt
    multipliers: tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)

    @property
    def bc_dim(self) -> int:
        return self.n_groups * self.d_state

    @property
    def conv_dim(self) -> int:
        """Channels the causal convolution runs over: x, B and C."""
        return self.d_ssm + 2 * self.bc_dim

    @property
    def proj_dim(self) -> int:
        """Width of the input projection: z, x, B, C, dt."""
        return 2 * self.d_ssm + 2 * self.bc_dim + self.n_heads


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    model_type: str
    vocab_size: int
    hidden_size: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    intermediate_size: int
    max_position_embeddings: int

    activation: str = "gelu_new"  # ACT2FN key (gptj_modeling.py:266)
    norm: str = "layernorm"  # "layernorm" | "rmsnorm"
    norm_eps: float = 1e-5
    # Gemma parameterizes RMSNorm as (1 + weight) and scales embeddings by
    # sqrt(hidden_size) before the first block.
    norm_scale_offset: float = 0.0
    embed_multiplier: float | None = None
    parallel_residual: bool = False  # GPT-J block form
    # GPT-NeoX variant of the parallel block: the MLP branch gets its own
    # pre-norm (h + attn(ln1(h)) + mlp(ln2(h))) instead of sharing GPT-J's
    # single norm. Only meaningful with parallel_residual=True.
    parallel_residual_ln2: bool = False
    mlp: str = "mlp"  # "mlp" | "swiglu"

    positions: str = "learned"  # "learned" | "rotary" | "none"
    rope_style: str = "interleaved"  # "interleaved" | "half"
    rotary_dim: int | None = None  # partial rotary (config.rotary_dim, GPT-J)
    rope_theta: float = 10000.0
    # LongRoPE (Phi-3 long-context): per-frequency divisors of length
    # rotary_dim/2 — inv_freq_i = 1 / (factor_i * theta^(2i/d)) — and a
    # scalar multiplier on sin/cos (the paper's attention factor). Chosen
    # STATICALLY at config time (models/phi3.py) rather than by runtime
    # sequence length as HF does: a basis switch mid-decode would poison
    # the incremental KV cache.
    rope_freq_factors: tuple[float, ...] | None = None
    rope_attn_factor: float = 1.0
    # Both LongRoPE bases + the original (pre-extension) window, so the
    # ENGINE can pick the basis matching its actual configured context
    # (DecodeEngine.__init__): a 4k-context engine on a 128k checkpoint
    # uses the short factors exactly as HF does for <=4k forwards.
    rope_freq_factors_short: tuple[float, ...] | None = None
    rope_freq_factors_long: tuple[float, ...] | None = None
    rope_original_max_positions: int | None = None

    # Sliding-window attention (Mistral): each token attends only the last
    # ``sliding_window`` positions. None = full causal. The ring-buffer
    # cache (engine/cache.py) makes this natural: a cache of window size
    # wraps and the mask drops the overwritten tail.
    sliding_window: int | None = None

    attn_bias: bool = True
    # Qwen2 puts biases on q/k/v but not o_proj; None = follow attn_bias.
    attn_out_bias: bool | None = None
    mlp_bias: bool = True
    head_bias: bool = False
    tie_word_embeddings: bool = False
    # GPT-2/BigCode scale attention by 1/sqrt(D); GPT-J divides by
    # sqrt(head_dim) too but computes it as `scale_attn` applied post-mask
    # (gptj_modeling.py:153) — numerically the same scaled softmax.
    attn_scale: float | None = None

    # Falcon-H1: a Mamba-2 mixer in parallel with attention in every block
    # (None = the block is attention -> MLP), and the fixed scalars on the
    # attention branch's input, keys and output, the MLP's gate and output,
    # and the logits. 1.0 leaves the forward as every other family has it.
    ssm: SSMConfig | None = None
    attn_in_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attn_out_multiplier: float = 1.0
    mlp_multipliers: tuple[float, float] = (1.0, 1.0)
    lm_head_multiplier: float = 1.0

    # compute dtype for activations; params are loaded in this dtype too
    dtype: str = "bfloat16"

    @property
    def compute_dtype(self):
        import jax.numpy as jnp

        return jnp.dtype(self.dtype)

    @property
    def has_ln2(self) -> bool:
        """Whether blocks carry a second norm: sequential blocks always do;
        parallel-residual blocks only in the NeoX form. The single source
        of truth for param specs/shapes and the forward pass."""
        return not self.parallel_residual or self.parallel_residual_ln2

    @property
    def o_bias(self) -> bool:
        return (
            self.attn_bias if self.attn_out_bias is None
            else self.attn_out_bias
        )

    @property
    def q_size(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_size(self) -> int:
        return self.n_kv_heads * self.head_dim


def act_fn(name: str):
    """ACT2FN equivalent (reference uses HF's table, gptj_modeling.py:266)."""
    import jax.numpy as jnp

    table = {
        "gelu": lambda x: jax.nn.gelu(x, approximate=False),
        "gelu_new": lambda x: jax.nn.gelu(x, approximate=True),
        "gelu_fast": lambda x: jax.nn.gelu(x, approximate=True),
        "gelu_pytorch_tanh": lambda x: jax.nn.gelu(x, approximate=True),
        "silu": jax.nn.silu,
        "swish": jax.nn.silu,
        "relu": jax.nn.relu,
        "tanh": jnp.tanh,
    }
    if name not in table:
        raise KeyError(f"unsupported activation {name!r}")
    return table[name]
