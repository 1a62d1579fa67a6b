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
class LinearAttnConfig:
    """Gated DeltaNet (Yang, Kautz, Hatamizadeh 2024) as the mixer of the
    ``linear_attention`` layers of a model whose layers ALTERNATE kinds
    (``DecoderConfig.layer_types``; Olmo-Hybrid, Qwen3-Next): per VALUE head
    a float32 state ``S`` of ``[key_head_dim, value_head_dim]`` that a token
    decays, corrects by the delta rule and reads (ops/gdn.py), behind one
    depthwise causal convolution over the concatenated q, k and v channels.
    Such a layer holds no keys and values.

    ``n_heads`` counts the KEY heads; ``n_value_heads`` (None: as many) may
    be a multiple of it (Qwen3-Next: 16 under 32): value head ``j`` reads
    key head ``j // (n_v_heads // n_heads)``, and the decay, ``beta``, the
    state and the output gate are a value head's."""

    n_heads: int
    key_head_dim: int
    value_head_dim: int
    d_conv: int
    # beta = 2 * sigmoid(.) in (0, 2): the transition I - beta k k^T may
    # have a negative eigenvalue (linear_allow_neg_eigval)
    allow_neg_eigval: bool = True
    n_value_heads: int | None = None

    @property
    def n_v_heads(self) -> int:
        return self.n_value_heads or self.n_heads

    @property
    def key_dim(self) -> int:
        return self.n_heads * self.key_head_dim

    @property
    def value_dim(self) -> int:
        return self.n_v_heads * self.value_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the causal convolution runs over: q, k and v."""
        return 2 * self.key_dim + self.value_dim


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2/V3) without a query
    low-rank: keys and values of all heads are rebuilt from ONE normed
    latent of ``kv_lora_rank`` numbers a token, and one rotary key of
    ``qk_rope_head_dim`` numbers is shared by all heads. What is cached is
    the latent beside the rotated key (``latent_dim`` numbers a token and
    layer, one pool: docs/latent-cache.md); the program attends over it in
    the absorbed form and never rebuilds a head of the context."""

    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def pool_dim(self) -> int:
        """Width of a token's row in the latent pool: ``latent_dim`` rounded
        up to whole 128-lane tiles, the tail zero. A minor dimension that
        is not a multiple of 128 (576 is 4.5 tiles) gets a default device
        layout with ANOTHER axis minor, and every step program then
        transposes the pool whole on its way in, through and out (compiled
        for a described v5e); 640 keeps it row-major and costs a ninth more
        pool."""
        return -(-self.latent_dim // 128) * 128


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Routed experts in every layer after the first ``n_dense_layers``
    (which keep the dense MLP of ``DecoderConfig.intermediate_size``): a
    router in float32 over ALL ``n_experts``, the ``top_k`` of them a token,
    beside ONE shared SwiGLU of ``shared_size`` (ops/moe.py), or beside none
    (``shared_size`` 0, keye_vl2: the tree then has no leaf for it and the
    layer no matmul).

    ``scoring`` ``"sigmoid"`` (deepseek_v3): sigmoid scores, chosen by score
    plus a selection-only bias, weights renormalised over the chosen
    (``norm_topk_prob``) and times ``routed_scaling_factor``. ``"softmax"``
    (qwen3_next, keye_vl2): a softmax over all the experts, the largest ``top_k``,
    renormalised over the chosen; no bias, no factor. ``shared_gate``: the
    shared expert's output is times ``sigmoid(x . w)`` a token.

    What is HELD here may be one chip's share of the experts, ``count`` of
    them from ``first`` (None: all): the stacked experts then have ``count``
    on their expert axis, the router still scores all ``n_experts``, and a
    pair routed to an expert held elsewhere adds nothing here."""

    n_experts: int
    top_k: int
    expert_size: int  # moe_intermediate_size
    shared_size: int  # n_shared_experts * moe_intermediate_size
    n_dense_layers: int  # first_k_dense_replace
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    scoring: str = "sigmoid"  # "sigmoid" | "softmax"
    shared_gate: bool = False
    first: int = 0
    count: int | None = None

    @property
    def n_held(self) -> int:
        return self.n_experts if self.count is None else self.count


@dataclasses.dataclass(frozen=True)
class IndexerConfig:
    """A learned selection of what attention reads (DeepSeek Sparse
    Attention's lightning indexer, at Keye-VL-2.0's shapes): every layer
    scores a query's whole visible context with ``n_heads`` small heads of
    ``head_dim`` against ONE cached key of ``head_dim`` a token, ``I[t, s] =
    sum_j w[t, j] relu(qI[t, j] . kI[s])``, and every head of the layer then
    attends over the ``topk`` best positions and nothing else (all of them
    while the context is at most ``topk``). The key is cached beside the
    token's keys and values, in a pool of its own (``PagedKVCache.idx``);
    projections, scores and selection are float32 whatever the compute
    dtype (ops/sparse_attention.py, docs/sparse-attention.md)."""

    n_heads: int
    head_dim: int
    topk: int

    @property
    def pool_dim(self) -> int:
        """Width of a token's row in the pool of indexer keys: ``head_dim``
        rounded up to whole 128-lane tiles, the tail zero (``MLAConfig.
        pool_dim``'s lesson over again). A float32 pool ``[L, N, 16, 64]``
        gets a default device layout with the BLOCK axis minor, and every
        step program then transposes the pool whole on its way in and out
        (compiled for a described v5e, PR 46); the row-major layout would
        pad 64 lanes to 128 in memory anyway. 128 keeps it row-major, at the
        bytes the device would have spent: 512 B a token and layer for 256
        B of key."""
        return -(-self.head_dim // 128) * 128


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

    # deepseek_v3: latent attention (one cached latent a token instead of
    # keys and values a head) and routed experts after a leading dense
    # stack. None leaves the block and the tree as every other family has
    # them.
    mla: MLAConfig | None = None
    moe: MoEConfig | None = None

    # olmo_hybrid: layers of two KINDS in a repeating pattern. ``layer_types``
    # names every layer's kind ("linear_attention": a ``linear_attn`` mixer
    # and no keys and values; "full_attention": the attention block) and is
    # whole periods of its shortest repeating unit; None = one kind. The
    # block is Olmo 2's: no pre-norm, ``h + norm(branch(h))`` (``post_norm``),
    # and an RMSNorm over the WHOLE query and key projection (``qk_norm``).
    # qwen3_next: the same two kinds, pre-norm, each followed by the routed
    # experts (``moe``); its QK-norm is over each HEAD (``qk_norm_per_head``,
    # scales of ``head_dim``), and its query projection is twice as wide, a
    # query and a gate a head: the heads' output is times ``sigmoid(gate)``
    # before the output projection (``attn_gate``).
    layer_types: tuple[str, ...] | None = None
    linear_attn: LinearAttnConfig | None = None
    post_norm: bool = False
    qk_norm: bool = False
    qk_norm_per_head: bool = False
    attn_gate: bool = False

    # keye_vl2: attention over a learned top-k selection of the context.
    # None leaves every other family's tree, cache and programs as they are.
    indexer: IndexerConfig | None = None

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

    @property
    def cache_row(self) -> tuple[int, ...]:
        """Trailing shape of what ONE token holds in ONE layer of a cache
        pool: ``(kv heads, head size)``, or ``(pool_dim,)`` for a model
        with latent attention (the normed latent beside the shared rotary
        key, zero-padded to whole lane tiles: one vector, no head axis)."""
        if self.mla is not None:
            return (self.mla.pool_dim,)
        return (self.pool_kv_heads, self.head_dim)

    @property
    def pool_kv_heads(self) -> int:
        """KV heads as a paged pool holds them: the model's, except where
        layers alternate kinds (``layer_types``). A bfloat16 pool's device
        layout tiles its two minor axes ``(heads, head size)`` by (16, 128),
        so a head count over one tile that is not whole tiles (30) is padded
        in memory anyway, and inside the period scan layout assignment then
        prefers the block's SLOT axis there and copies the whole pool into
        that layout and back around every step program (four copies of 1.5 GB
        a group at 30 heads; none at 32: compiled for a described v5e,
        PR 40). There the count is rounded up to whole tiles and the block
        pads its heads with zeros (``models/decoder.py: _block``). Only that
        program was compiled and measured, so only it is padded: every other
        family's pool holds ``n_kv_heads`` as it did. Multi-head attention
        only: a grouped count must keep dividing the query heads."""
        h = self.n_kv_heads
        if (self.layer_types is None or h <= 16 or h % 16 == 0
                or h != self.n_heads):
            return h
        return -(-h // 16) * 16

    @property
    def has_state(self) -> bool:
        """Whether a row holds a recurrent state beside its keys and values
        (a Mamba-2 mixer in every block, or linear-attention layers): THE
        question every feature that does not carry that state asks before it
        refuses the model (docs/recurrent-state.md)."""
        return self.ssm is not None or self.linear_attn is not None

    @property
    def period(self) -> tuple[str, ...] | None:
        """The shortest repeating unit of ``layer_types``; None for a model
        of one kind of layer."""
        t = self.layer_types
        if t is None:
            return None
        return next(
            t[:n] for n in range(1, len(t) + 1)
            if len(t) % n == 0 and t[:n] * (len(t) // n) == t
        )

    @property
    def n_kv_layers(self) -> int:
        """Layers that hold keys and values: the layer axis of the block
        pools and of ``params["blocks"]``."""
        if self.layer_types is None:
            return self.n_layers
        return self.layer_types.count("full_attention")

    @property
    def n_state_layers(self) -> int:
        """Layers that hold a recurrent state: the layer axis of the state
        pools (and of ``params["linear"]`` where the kinds alternate)."""
        if self.layer_types is None:
            return self.n_layers if self.ssm is not None else 0
        return self.layer_types.count("linear_attention")

    @property
    def n_lead_layers(self) -> int:
        """Layers of the leading stack (``params["lead"]``): the dense
        layers before the expert stack; 0 for a model of one kind of
        layer."""
        return 0 if self.moe is None else self.moe.n_dense_layers


def experts_held(hf, family: str) -> tuple[int, int, int | None]:
    """``(experts the router scores, first held here, how many; None: all)``
    of a published config with routed experts. ``num_experts`` counts the
    experts HELD; a chip's share of an expert-parallel deployment says so in
    ``expert_parallel``: ``{"num_experts": <the model's>, "chips": <that
    share each layer's>, "chip": <this one's index>}``, experts in
    contiguous ranges by chip (``family`` names the refusal)."""
    ep = getattr(hf, "expert_parallel", None)
    if ep is None:
        return hf.num_experts, 0, None
    total, chips, chip = ep["num_experts"], ep["chips"], ep["chip"]
    if total != chips * hf.num_experts or not 0 <= chip < chips:
        raise ValueError(
            f"{family}: expert_parallel {ep} does not give num_experts "
            f"{hf.num_experts} held here (the model's experts / chips)"
        )
    return total, chip * hf.num_experts, hf.num_experts


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
