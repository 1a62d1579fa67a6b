"""Unified TP decoder: one pure forward for every supported model family.

Replaces the reference's two ~700-line model files
(``custom_modeling/gptj_modeling.py``, ``gpt_bigcode_modeling.py``) with one
scan-based decoder driven by ``DecoderConfig`` flags. Differences from the
reference that are deliberate TPU-first design, not omissions:

- **Blocks run under ``lax.scan``** over parameters stacked on a leading
  layer axis: one compiled block body instead of ``n_layer`` unrolled copies
  (compile time O(1) in depth; the reference's Python ``nn.ModuleList`` loop
  (``gptj_modeling.py:371-376``) has no TPU analogue).
- **The KV cache is written in place** into a preallocated ring buffer
  (``engine/cache.py``) instead of concat-growing tuples
  (``gptj_modeling.py:229-236``).
- **No collectives appear in model code.** Parameters carry Megatron
  PartitionSpecs (``param_specs``); XLA inserts the reference's allreduces
  (``layers.py:178,213``) and head all-gather (``layers.py:125``) from the
  sharding constraints.
- fp32 numerics islands match the reference: attention softmax
  (``gptj_modeling.py:140-143``), norms, and final logits
  (``gptj_modeling.py:609``).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from llmss_tpu.engine.cache import (
    KVCache, PagedKVCache, dequantize_kv, gather_block_view,
    logical_to_physical, paged_write_stacked, quantize_kv, write_layer,
    write_positions,
)
from llmss_tpu.models.common import DecoderConfig, act_fn
from llmss_tpu.ops.attention import (
    decode_mask_penalty,
    dispatch_attention,
    fresh_kv_decode_attention,
    fresh_kv_window_attention,
    make_causal_mask,
    paged_decode_attention,
    ragged_cache_visibility,
    ragged_paged_attention,
    window_mask_penalty,
)
from llmss_tpu.ops.layers import (
    LinearParams, NormParams, dense, dense_t, embedding, layer_norm, rms_norm,
)
from llmss_tpu.ops.rope import apply_rope, sin_cos_tables
from llmss_tpu.ops.gdn import gdn_chunked, gdn_step, l2_normalize
from llmss_tpu.ops.ssm import causal_conv, ssd_scan, ssm_step
from llmss_tpu.parallel.mesh import AXIS_DP, AXIS_SP, AXIS_TP
from llmss_tpu.parallel.sharding import constrain
from llmss_tpu.utils import devtel


def _seq_axis(mesh, S: int) -> str | None:
    """Shard the sequence dim over ``sp`` when the mesh has a live sp axis
    and the length divides (long-context prefill); decode (S=1) and odd
    lengths stay replicated."""
    if mesh is None or S <= 1:
        return None
    sp = mesh.shape[AXIS_SP]
    return AXIS_SP if sp > 1 and S % sp == 0 else None

Params = dict[str, Any]


# -- parameter structure ------------------------------------------------------


def _norm_specs(stacked: bool, bias: bool) -> NormParams:
    lead = (None,) if stacked else ()
    return NormParams(
        scale=P(*lead, None), bias=P(*lead, None) if bias else None
    )


def param_specs(cfg: DecoderConfig, tp: int) -> Params:
    """PartitionSpec pytree matching ``init_params``/``load_params`` output.

    ``tp`` determines whether KV projections shard (GQA with enough heads) or
    replicate (MQA — the reference's replicated single KV head,
    ``gpt_bigcode_modeling.py:150-155``).
    """
    kv_axis = AXIS_TP if cfg.n_kv_heads % tp == 0 else None
    norm_bias = cfg.norm == "layernorm"

    blocks: Params = {
        "ln1": _norm_specs(True, norm_bias),
        # q/k weights are stored transposed — [L, out, in] — so the scan's
        # per-layer slice feeds the rope-fused matmul without a relayout
        # copy (see ops/layers.py:dense_t). Sharding stays Megatron
        # column-parallel: the out axis carries tp.
        "q": LinearParams(
            w=P(None, AXIS_TP, None),
            b=P(None, AXIS_TP) if cfg.attn_bias else None,
        ),
        "k": LinearParams(
            w=P(None, kv_axis, None),
            b=P(None, kv_axis) if cfg.attn_bias else None,
        ),
        "v": LinearParams(
            w=P(None, None, kv_axis),
            b=P(None, kv_axis) if cfg.attn_bias else None,
        ),
        "o": LinearParams(
            w=P(None, AXIS_TP, None), b=P(None) if cfg.o_bias else None
        ),
    }
    if cfg.mla is not None:
        # Latent attention: keys and values come from one latent, so the
        # two projections give way to the latent's down- and up-projection
        # and its norm. Served at tp == 1 only (DecodeEngine refuses more):
        # the up-projection carries the head split a later mesh would use,
        # the one latent "head" replicates as MQA's does.
        del blocks["k"], blocks["v"]
        blocks["kv_a"] = LinearParams(w=P(None, None, None), b=None)
        blocks["kv_norm"] = _norm_specs(True, False)
        blocks["kv_b"] = LinearParams(w=P(None, None, AXIS_TP), b=None)
    if cfg.qk_norm or cfg.qk_norm_per_head:
        # over the WHOLE projection (Olmo 2), so over every shard's heads;
        # or one scale of ``head_dim`` that every head shares (qwen3_next)
        blocks["q_norm"] = _norm_specs(True, False)
        blocks["k_norm"] = _norm_specs(True, False)
    if cfg.indexer is not None:
        # The indexer's three projections and the LayerNorm on its key,
        # replicated (served at tp == 1 only: DecodeEngine refuses more).
        for name in ("idx_q", "idx_k", "idx_w"):
            blocks[name] = LinearParams(w=P(None, None, None), b=None)
        blocks["idx_k_norm"] = _norm_specs(True, True)
    if cfg.has_ln2:
        blocks["ln2"] = _norm_specs(True, norm_bias)
    swiglu = {
        "gate": LinearParams(w=P(None, None, AXIS_TP), b=None),
        "up": LinearParams(w=P(None, None, AXIS_TP), b=None),
        "down": LinearParams(w=P(None, AXIS_TP, None), b=None),
    }
    lead = None
    rep3, rep4 = P(None, None, None), P(None, None, None, None)
    routed = None
    if cfg.moe is not None and cfg.mla is None:
        # The expert layer after EVERY layer of both kinds (qwen3_next): a
        # stack's own router and shared expert, replicated like the latent
        # family's; the stacked experts of all layers are ONE top-level
        # stack (``params["experts"]``), indexed by the layer's absolute
        # index whatever its kind.
        routed = {"router": LinearParams(w=rep3, b=None)}
        if cfg.moe.shared_size:  # 0: no shared expert, no leaf for one
            routed.update({
                "shared_gate": LinearParams(w=rep3, b=None),
                "shared_up": LinearParams(w=rep3, b=None),
                "shared_down": LinearParams(w=rep3, b=None),
            })
        if cfg.moe.shared_gate:
            routed["shared_sig"] = LinearParams(w=rep3, b=None)
        blocks.update(routed)
    elif cfg.moe is not None:
        # Two kinds of layer, two stacks (``_forward_latent``): the leading
        # dense layers keep the SwiGLU, the rest hold the router, the
        # stacked experts (replicated: no mesh axis divides them yet) and
        # the shared expert.
        lead = {**blocks, **swiglu}
        blocks.update({
            "router": LinearParams(w=rep3, b=P(None, None)),
            "experts_gate": rep4, "experts_up": rep4, "experts_down": rep4,
            "shared_gate": LinearParams(w=rep3, b=None),
            "shared_up": LinearParams(w=rep3, b=None),
            "shared_down": LinearParams(w=rep3, b=None),
        })
    elif cfg.mlp == "swiglu":
        blocks.update(swiglu)
    else:
        blocks["fc_in"] = LinearParams(
            w=P(None, None, AXIS_TP),
            b=P(None, AXIS_TP) if cfg.mlp_bias else None,
        )
        blocks["fc_out"] = LinearParams(
            w=P(None, AXIS_TP, None), b=P(None) if cfg.mlp_bias else None
        )

    if cfg.ssm is not None:
        # The mixer's leaves and its state are replicated over tp: a head
        # split would cut the convolution's channels by segment (x by head,
        # B and C by group). Attention and the MLP shard as everywhere.
        blocks["ssm_in"] = LinearParams(w=P(None, None, None), b=None)
        blocks["ssm_conv"] = LinearParams(
            w=P(None, None, None), b=P(None, None)
        )
        blocks["ssm_dt_bias"] = P(None, None)
        blocks["ssm_A_log"] = P(None, None)
        blocks["ssm_D"] = P(None, None)
        blocks["ssm_norm"] = _norm_specs(True, False)
        blocks["ssm_out"] = LinearParams(w=P(None, None, None), b=None)

    specs: Params = {
        "wte": P(AXIS_TP, None),
        "blocks": blocks,
        "ln_f": _norm_specs(False, norm_bias),
    }
    if lead is not None:
        specs["lead"] = lead
    if routed is not None:
        specs["experts"] = {
            "experts_gate": rep4, "experts_up": rep4, "experts_down": rep4,
        }
    if cfg.linear_attn is not None:
        # The linear-attention layers' own stack (``_layer_scan`` walks the
        # two stacks by period). The mixer and its state are replicated over
        # tp as a Mamba-2 mixer's are; the MLP shards as everywhere (the
        # expert layer, where the model has one, is replicated).
        rep = LinearParams(w=P(None, None, None), b=None)
        specs["linear"] = {
            "ln1": _norm_specs(True, norm_bias),
            "ln2": _norm_specs(True, norm_bias), **(routed or swiglu),
            "gdn_qkv": rep, "gdn_ab": rep, "gdn_g": rep, "gdn_o": rep,
            "gdn_conv": rep,
            "gdn_A_log": P(None, None), "gdn_dt_bias": P(None, None),
            "gdn_norm": _norm_specs(True, False),
        }
    if cfg.positions == "learned":
        specs["wpe"] = P(AXIS_TP, None)
    if not cfg.tie_word_embeddings:
        specs["head"] = LinearParams(
            w=P(None, AXIS_TP), b=P(AXIS_TP) if cfg.head_bias else None
        )
    return specs


def init_params(cfg: DecoderConfig, mesh, key) -> Params:
    """Random init (bench/tests without checkpoints), generated directly on
    device in the target sharding — no host-side materialization."""
    from jax.sharding import NamedSharding

    tp = mesh.shape[AXIS_TP]
    specs = param_specs(cfg, tp)
    shapes = param_shapes(cfg)
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    leaves, treedef = jax.tree.flatten(shapes)
    keys_tree = jax.tree.unflatten(
        treedef, list(jax.random.split(key, len(leaves)))
    )

    if cfg.ssm is not None:
        draw = _ssm_family_draw(cfg)
    elif cfg.moe is not None:
        draw = _routed_family_draw(cfg)
    elif cfg.linear_attn is not None:
        draw = _gdn_family_draw(cfg)
    else:
        draw = {}

    def _leaf(path, sds, k):
        name = next(
            (p.key for p in reversed(path) if getattr(p, "key", None) in draw),
            None,
        )
        if name is not None:
            return draw[name](k, sds.shape).astype(sds.dtype)
        return jax.random.normal(k, sds.shape, sds.dtype) * 0.02

    def _init(keys):
        return jax.tree_util.tree_map_with_path(_leaf, shapes, keys)

    # The span times the host's part (tracing, compiling, dispatch): the
    # device may still be filling the arrays when it closes.
    with devtel.setup_span("setup.weights") as sp:
        params = jax.jit(_init, out_shardings=shardings)(keys_tree)
        sp.set(bytes=devtel.tree_bytes(params))
    return params


def _draw_dt_bias(k, shape):
    """The inverse softplus of a log-uniform time step in [1e-3, 1e-1]: how
    the published initialisations of both recurrent families draw the bias
    of their decay's time step."""
    dt = jnp.exp(jax.random.uniform(
        k, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)
    ))
    return dt + jnp.log(-jnp.expm1(-dt))


def _ssm_family_draw(cfg: DecoderConfig) -> dict:
    """The seeded draw of a family with a parallel Mamba-2 mixer, leaf by
    leaf: ``{leaf name: (key, shape) -> float32 array}``. N(0, 0.02) on
    every leaf breaks such a family twice. ``A_log`` and ``dt_bias`` near 0
    give a decay of a half a token, so the state forgets in five tokens and
    a wrong recurrence passes every check; and a branch whose output is
    times a multiplier of a hundredth is a few percent of the residual, so a
    dropped branch sits inside a bfloat16 tolerance. So the recurrence's
    leaves are drawn as the published initialisation draws them, and each
    matrix ``N(0, (c / sqrt(fan_in))^2)`` with ``c`` chosen so that, under
    the published multipliers, attention's scores have a deviation near 1
    and attention, mixer and MLP each add about a twentieth to a residual
    that starts at a ninth (0.02 x the embedding multiplier). Leaves not
    named here (the embedding, norm scales: the benchmark's server and the
    tests add 1 to those) stay N(0, 0.02)."""
    E, Q, I = cfg.hidden_size, cfg.q_size, cfg.intermediate_size
    s = cfg.ssm

    def normal(c, fan_in):
        return lambda k, shape: (
            jax.random.normal(k, shape, jnp.float32) * (c / fan_in ** 0.5)
        )

    def uniform(lo, hi):
        return lambda k, shape: jax.random.uniform(
            k, shape, jnp.float32, lo, hi
        )

    return {
        "q": normal(8.0, E), "k": normal(11.3, E), "v": normal(1.0, E),
        "o": normal(4.5, Q),
        "gate": normal(8.5, E), "up": normal(1.0, E),
        "down": normal(5.6, I),
        "ssm_in": normal(16.0, E), "ssm_out": normal(0.6, s.d_ssm),
        "ssm_conv": uniform(-0.5, 0.5),  # weight and bias
        "ssm_A_log": lambda k, shape: jnp.log(uniform(1.0, 16.0)(k, shape)),
        "ssm_dt_bias": _draw_dt_bias,
        "ssm_D": lambda k, shape: jnp.ones(shape, jnp.float32),
        "head": normal(1.0, E),
    }


def _gdn_family_draw(cfg: DecoderConfig) -> dict:
    """The seeded draw of a family whose linear-attention layers run the
    gated delta rule, leaf by leaf (as ``_ssm_family_draw`` is for a Mamba-2
    mixer, and for its first reason: ``A_log`` and ``dt_bias`` near 0 make a
    state that forgets in a few tokens, which hides a wrong recurrence). The
    block is post-norm, so every branch adds a unit-size vector to the
    residual whatever its matrices' scale: a lost branch leaves any
    tolerance by itself. What the scales decide is where each nonlinearity
    works and how far a rounding travels: matrices are ``N(0, 1 / fan_in)``,
    so that ``beta`` fills (0, 2), the decay's time step moves by a factor of
    e either way with the token and the SiLUs leave their linear part; the
    embedding is N(0, 4^2). With keys drawn at random the delta rule's
    corrections solve an ill-conditioned system once a head remembers more
    tokens than it has key dimensions (the slow heads over 512 tokens do), and
    a branch normed AFTER it hands that on at full size: an embedding of size
    1 put bfloat16 at 0.10-0.13 of the reference's deviation against a
    tolerance of 0.15, size 4 at 0.05 (each branch still a tenth of the
    residual's variance: the lost ``beta`` projection reads 1.4-1.5; CPU,
    PR 40, 12 layers at the published head sizes, 4 x 512 tokens). This is
    the benchmark's draw, not the published one: Olmo 2 / Olmo 3 initialise
    every matrix AND the embedding at N(0, 0.02) and train from there, and no
    trained size is in the config; a 0.02 embedding under post-norm is
    swamped by the first branch (the regime below size 1; not measured).
    Size 4 was chosen for room under the tolerance the harness already had, so the
    tolerance's upper reading is not this draw's to give: it comes from the
    precision controls of ``tools/olmo_hybrid_check.py`` (PERF.md section 6).
    Norm scales, not named here, stay N(0, 0.02) (the benchmark's server and
    the tests add 1)."""

    def normal(transposed=False):
        def draw(k, shape):
            fan_in = shape[-1 if transposed else -2]
            return jax.random.normal(k, shape, jnp.float32) / fan_in ** 0.5
        return draw

    w, wt = normal(), normal(transposed=True)
    return {
        "wte": lambda k, shape: 4.0 * jax.random.normal(k, shape, jnp.float32),
        "q": wt, "k": wt, "v": w, "o": w,
        "gate": w, "up": w, "down": w, "head": w,
        "gdn_qkv": w, "gdn_ab": w, "gdn_g": w, "gdn_o": w,
        "gdn_conv": lambda k, shape: jax.random.uniform(
            k, shape, jnp.float32, -0.5, 0.5
        ),
        "gdn_A_log": lambda k, shape: jnp.log(
            jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0)
        ),
        "gdn_dt_bias": _draw_dt_bias,
    }


def routing_block(cfg: DecoderConfig) -> int:
    """Width of the leading block of the hidden dimensions that the seeded
    draw of a model with routed experts keeps for routing (see
    ``_routed_family_draw``): an eighth of the hidden size."""
    return cfg.hidden_size // 8


def _routed_family_draw(cfg: DecoderConfig) -> dict:
    """The seeded draw of a family with routed experts, leaf by leaf (as
    ``_ssm_family_draw`` is for a mixer). Top-k routing is discontinuous: the
    6th and 7th of 128 random scores lie a few hundredths of a deviation
    apart, so the rounding of a bfloat16 residual flips a choice now and
    then, and one flip swaps a sixth of a layer's routed output: far above
    any tolerance on the logits, and no fault of the program. So the draw
    makes the router's input the same numbers on both sides of a comparison:

    - the first ``routing_block(cfg)`` hidden dimensions hold the token's
      embedding (drawn N(0, 1) there, N(0, 0.02) elsewhere) and NOTHING
      else: every matrix that writes the residual (attention's output, the
      dense, shared and routed down-projections) has zeros in those output
      columns, so the block is carried unchanged, exactly, in any dtype;
    - the router reads that block alone (zeros elsewhere), and reads the
      normed input in float32 before it is rounded (``_latent_block``), so its
      scores differ between dtypes by RMSNorm's one positive factor a
      token, which reorders nothing.

    Routing is then a fixed pseudo-random function of the token id: uniform
    over the experts under random ids, ``top_k`` distinct experts a token.
    Attention, the experts and the shared expert read all of the residual
    and write the rest of it; every expert's arithmetic and every byte a
    step reads are as with any other weights. The selection bias is drawn
    N(0, 0.02), not zero, so selection and weighting differ as published,
    and SMALL: the chosen scores lie within a few hundredths of each other,
    and a bias of N(0, 0.1) sent 42% of the tokens to a tenth of the experts
    (93 of 128 hit by 155 tokens where uniform routing hits all; at 0.02,
    127: CPU, PR 38), which would falsify the bytes a step reads. Norm
    scales, not named here, stay N(0, 0.02) (the benchmark's server and the
    tests add 1)."""
    R = routing_block(cfg)
    f32 = jnp.float32

    def normal(c, writes=False, transposed=False):
        """N(0, (c / sqrt(fan_in))^2) on ``[.., in, out]`` (``transposed``:
        ``[.., out, in]``); ``writes``: a matrix that writes the residual,
        its routing-block OUTPUT columns at zero."""
        def draw(k, shape):
            fan_in = shape[-1 if transposed else -2]
            w = jax.random.normal(k, shape, f32) * (c / fan_in ** 0.5)
            return w.at[..., :R].set(0.0) if writes else w
        return draw

    def router(k, shape):
        if len(shape) == 2:  # the selection bias [L, N]
            return jax.random.normal(k, shape, f32) * 0.02
        # [L, N, E]: scores of deviation near 1 from the block alone (near 3
        # before the first branch has written: the block is most of a norm)
        w = jax.random.normal(k, shape, f32) / R ** 0.5
        return w.at[..., R:].set(0.0)

    def wte(k, shape):
        w = jax.random.normal(k, shape, f32)
        return w.at[:, R:].multiply(0.02)

    # c = 0.02 x sqrt(fan_in) at kanana-2-30b-a3b's widths, so that the
    # deployment's weights are N(0, 0.02) as every other family's are and a
    # small model (tests) behaves like it; but the queries at 2 (scores of a
    # deviation near 1.3: a wrong scale or rotation shows) and attention's
    # output at 2.5.
    draw = {
        "wte": wte, "router": router,
        "q": normal(2.0, transposed=True), "kv_a": normal(0.9),
        "kv_b": normal(0.45), "o": normal(2.5, writes=True),
        "gate": normal(0.9), "up": normal(0.9),
        "down": normal(1.57, writes=True),
        "experts_gate": normal(0.9), "experts_up": normal(0.9),
        "experts_down": normal(0.55, writes=True),
        "shared_gate": normal(0.9), "shared_up": normal(0.9),
        "shared_down": normal(0.78, writes=True),
        "head": normal(0.9),
    }
    if cfg.mla is None:
        # The experts after layers of two kinds (qwen3_next). The same block
        # and the same rule: the linear mixer's output projection writes none
        # of it either. The query projection holds a gate a head (a deviation
        # of 2: ``sigmoid`` works over its whole range); the QK-norm makes the
        # scores' size its own. Softmax weights sum to 1 over the chosen (the
        # sigmoid family's to 2.4) and a chip's share holds a part of them,
        # and the shared expert is halved by its gate: their down-projections
        # are drawn larger, so that a fault in one shows in the logits. How
        # large every branch is beside the block that is carried exactly was
        # then MEASURED: both mixers read dot products of near-orthogonal
        # vectors (the delta rule's ``S^T k`` and ``S^T q`` of unit vectors,
        # attention's scores of normed heads), which turn a bfloat16 input's
        # 2^-9 into percents of their small result. With the linear mixer's
        # output at 1.2 that branch alone put bfloat16 at 0.18-0.25 of a
        # logit's deviation against the harness's 0.15 (CPU, PR 44: 8 layers
        # at a hidden size of 64); at the published widths with (gdn_o, o,
        # experts_down, shared_down) at (0.6, 1.0, 6.0, 2.0) the check read
        # 0.07-0.11 over three seeds, and with all four halved, as drawn
        # here, 0.06-0.07 (my chip runs, PR 44), beside 0.03-0.04 for the
        # float32 reference with only its residual rounded to bfloat16. The
        # delta rule's own leaves as ``_gdn_family_draw`` has them, for its
        # reason.
        gdn = _gdn_family_draw(cfg)
        draw.update({
            "k": normal(0.9, transposed=True), "v": normal(0.9),
            "o": normal(0.5, writes=True),
            "experts_down": normal(3.0, writes=True),
            "shared_down": normal(1.0, writes=True),
            "shared_sig": normal(0.9),
            "gdn_qkv": normal(0.9), "gdn_ab": normal(0.9),
            "gdn_g": normal(0.9), "gdn_o": normal(0.3, writes=True),
            **{k: gdn[k] for k in ("gdn_conv", "gdn_A_log", "gdn_dt_bias")},
        })
    if cfg.indexer is not None:
        # The indexer's top-k is the router's hazard over again (thousands
        # of scores a query, neighbours in rank closer than a bfloat16
        # rounding), and has the router's cure: its three projections read
        # the routing block ALONE (zero rows elsewhere), from the normed
        # input in float32 before its rounding. A token's key is then the
        # same numbers in any compute dtype (its LayerNorm takes out the one
        # positive factor RMSNorm leaves on the block) and a query's scores
        # the same up to one positive factor a query, which orders nothing
        # otherwise. Scores of a deviation of a few units from the block.
        def reads_block(k, shape):  # [L, E, out]
            w = jax.random.normal(k, shape, f32) / R ** 0.5
            return w.at[..., R:, :].set(0.0)

        draw.update(idx_q=reads_block, idx_k=reads_block, idx_w=reads_block)
    return draw


def param_shapes(cfg: DecoderConfig) -> Params:
    """ShapeDtypeStruct pytree of the full parameter set."""
    L, E, V = cfg.n_layers, cfg.hidden_size, cfg.vocab_size
    Q, KV, I = cfg.q_size, cfg.kv_size, cfg.intermediate_size
    norm_bias = cfg.norm == "layernorm"
    dt = cfg.compute_dtype

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, dt)

    def norm_shape(n):
        """``n`` stacked layers; None: the final norm."""
        lead = () if n is None else (n,)
        return NormParams(
            scale=sds(*lead, E), bias=sds(*lead, E) if norm_bias else None
        )

    def attn_shapes(n):
        if cfg.mla is not None:
            m, H = cfg.mla, cfg.n_heads
            return {
                "ln1": norm_shape(n),
                "q": LinearParams(sds(n, H * m.qk_head_dim, E), None),
                "kv_a": LinearParams(sds(n, E, m.latent_dim), None),
                "kv_norm": NormParams(sds(n, m.kv_lora_rank), None),
                "kv_b": LinearParams(
                    sds(n, m.kv_lora_rank,
                        H * (m.qk_nope_head_dim + m.v_head_dim)), None
                ),
                "o": LinearParams(sds(n, H * m.v_head_dim, E), None),
            }
        # a query and a gate a head, side by side ([H, 2, D] on the out axis)
        Qw = 2 * Q if cfg.attn_gate else Q
        extra = {}  # the QK-norms' and the indexer's leaves
        if cfg.qk_norm or cfg.qk_norm_per_head:
            D = cfg.head_dim
            extra = {
                "q_norm": NormParams(sds(n, D if cfg.qk_norm_per_head else Q), None),
                "k_norm": NormParams(sds(n, D if cfg.qk_norm_per_head else KV), None),
            }
        if cfg.indexer is not None:
            x = cfg.indexer
            extra.update({
                "idx_q": LinearParams(sds(n, E, x.n_heads * x.head_dim), None),
                "idx_k": LinearParams(sds(n, E, x.head_dim), None),
                "idx_w": LinearParams(sds(n, E, x.n_heads), None),
                "idx_k_norm": NormParams(
                    sds(n, x.head_dim), sds(n, x.head_dim)),
            })
        return {
            "ln1": norm_shape(n),
            # q/k transposed storage [L, out, in] (see param_specs).
            "q": LinearParams(
                sds(n, Qw, E), sds(n, Qw) if cfg.attn_bias else None),
            "k": LinearParams(
                sds(n, KV, E), sds(n, KV) if cfg.attn_bias else None),
            "v": LinearParams(
                sds(n, E, KV), sds(n, KV) if cfg.attn_bias else None),
            "o": LinearParams(sds(n, Q, E), sds(n, E) if cfg.o_bias else None),
            **extra,
        }

    def routed_shapes(n):
        """The expert layer's own leaves in a stack of ``n`` layers (the
        experts themselves are one stack of all layers)."""
        x = cfg.moe
        out = {"router": LinearParams(sds(n, x.n_experts, E), None)}
        if x.shared_size:
            out.update({
                "shared_gate": LinearParams(sds(n, E, x.shared_size), None),
                "shared_up": LinearParams(sds(n, E, x.shared_size), None),
                "shared_down": LinearParams(sds(n, x.shared_size, E), None),
            })
        if x.shared_gate:
            out["shared_sig"] = LinearParams(sds(n, E, 1), None)
        return out

    def swiglu_shapes(n):
        return {
            "gate": LinearParams(sds(n, E, I), None),
            "up": LinearParams(sds(n, E, I), None),
            "down": LinearParams(sds(n, I, E), None),
        }

    n_lead = cfg.n_lead_layers
    # the main stack: the leading dense layers, and the layers of the other
    # kind where kinds alternate, are stacks of their own
    L = cfg.n_kv_layers - n_lead
    blocks: Params = attn_shapes(L)
    if cfg.has_ln2:
        blocks["ln2"] = norm_shape(L)
    lead = None
    mlp_shapes = swiglu_shapes
    if cfg.moe is not None and cfg.mla is None:
        mlp_shapes = routed_shapes
        blocks.update(routed_shapes(L))
    elif cfg.moe is not None:
        x = cfg.moe
        lead = {
            **attn_shapes(n_lead), "ln2": norm_shape(n_lead),
            **swiglu_shapes(n_lead),
        }
        blocks.update({
            # [experts, hidden] as published; the selection bias beside it
            "router": LinearParams(
                sds(L, x.n_experts, E), sds(L, x.n_experts)
            ),
            "experts_gate": sds(L, x.n_experts, E, x.expert_size),
            "experts_up": sds(L, x.n_experts, E, x.expert_size),
            "experts_down": sds(L, x.n_experts, x.expert_size, E),
            "shared_gate": LinearParams(sds(L, E, x.shared_size), None),
            "shared_up": LinearParams(sds(L, E, x.shared_size), None),
            "shared_down": LinearParams(sds(L, x.shared_size, E), None),
        })
    elif cfg.mlp == "swiglu":
        blocks.update(swiglu_shapes(L))
    else:
        blocks["fc_in"] = LinearParams(
            sds(L, E, I), sds(L, I) if cfg.mlp_bias else None
        )
        blocks["fc_out"] = LinearParams(
            sds(L, I, E), sds(L, E) if cfg.mlp_bias else None
        )

    if cfg.ssm is not None:
        m = cfg.ssm
        blocks["ssm_in"] = LinearParams(sds(L, E, m.proj_dim), None)
        # [K, C]: channels minor (the published conv1d weight is [C, 1, K])
        blocks["ssm_conv"] = LinearParams(
            sds(L, m.d_conv, m.conv_dim), sds(L, m.conv_dim)
        )
        blocks["ssm_dt_bias"] = sds(L, m.n_heads)
        blocks["ssm_A_log"] = sds(L, m.n_heads)
        blocks["ssm_D"] = sds(L, m.n_heads)
        blocks["ssm_norm"] = NormParams(scale=sds(L, m.d_ssm), bias=None)
        blocks["ssm_out"] = LinearParams(sds(L, m.d_ssm, E), None)

    shapes: Params = {
        "wte": sds(V, E), "blocks": blocks, "ln_f": norm_shape(None)
    }
    if lead is not None:
        shapes["lead"] = lead
    if mlp_shapes is routed_shapes:
        x = cfg.moe
        shapes["experts"] = {
            "experts_gate": sds(cfg.n_layers, x.n_held, E, x.expert_size),
            "experts_up": sds(cfg.n_layers, x.n_held, E, x.expert_size),
            "experts_down": sds(cfg.n_layers, x.n_held, x.expert_size, E),
        }
    if cfg.linear_attn is not None:
        m, n = cfg.linear_attn, cfg.n_state_layers
        shapes["linear"] = {
            "ln1": norm_shape(n), "ln2": norm_shape(n), **mlp_shapes(n),
            # q, k and v in one projection, as the one convolution over
            # their concatenated channels reads them; a and b likewise (a
            # value head each)
            "gdn_qkv": LinearParams(sds(n, E, m.conv_dim), None),
            "gdn_ab": LinearParams(sds(n, E, 2 * m.n_v_heads), None),
            "gdn_g": LinearParams(sds(n, E, m.value_dim), None),
            "gdn_o": LinearParams(sds(n, m.value_dim, E), None),
            # [K, C]: channels minor, no bias
            "gdn_conv": LinearParams(sds(n, m.d_conv, m.conv_dim), None),
            "gdn_A_log": sds(n, m.n_v_heads),
            "gdn_dt_bias": sds(n, m.n_v_heads),
            "gdn_norm": NormParams(sds(n, m.value_head_dim), None),
        }
    if cfg.positions == "learned":
        shapes["wpe"] = sds(cfg.max_position_embeddings, E)
    if not cfg.tie_word_embeddings:
        shapes["head"] = LinearParams(
            sds(E, V), sds(V) if cfg.head_bias else None
        )
    return shapes


# -- forward ------------------------------------------------------------------


def _norm(cfg: DecoderConfig, x, p: NormParams, out_dtype=None):
    from llmss_tpu.ops.layers import layer_norm, rms_norm

    if cfg.norm == "rmsnorm":
        return rms_norm(
            x, p, cfg.norm_eps, cfg.norm_scale_offset, out_dtype=out_dtype
        )
    assert out_dtype is None
    return layer_norm(x, p, cfg.norm_eps)


def _scale(x, m: float):
    """``x`` times a fixed scalar of the architecture, multiplied in float32
    (a weak-typed product would round the scalar to the compute dtype: a
    systematic 2^-9 on a whole branch). 1.0 adds nothing to the program."""
    if m == 1.0:
        return x
    return (x.astype(jnp.float32) * m).astype(x.dtype)


def _mlp(cfg: DecoderConfig, bp: Params, x):
    act = act_fn(cfg.activation)
    if cfg.mlp == "swiglu":
        m_gate, m_out = cfg.mlp_multipliers
        gate = _scale(dense(x, bp["gate"]), m_gate)
        return _scale(
            dense(act(gate) * dense(x, bp["up"]), bp["down"]), m_out
        )
    return dense(act(dense(x, bp["fc_in"])), bp["fc_out"])


def _routed_mlp(cfg: DecoderConfig, bp: Params, x, x32, live, experts):
    """The expert layer of both families that have one (deepseek_v3 after
    latent attention; qwen3_next after a linear-attention mixer and after
    gated attention alike): ``x`` [B, S, E] the block's normed input,
    ``x32`` the same before it was rounded to the compute dtype (what the
    router reads), ``live`` [B, S] which tokens are real, ``experts`` the
    stacked experts of ALL expert layers and this layer's index among them
    (the grouped matmul reads the layer's weights in place: ops/moe.py).
    The router scores all ``n_experts``; the stack may hold one chip's share
    of them (``MoEConfig.first`` / ``count``), and the pairs of the others
    are left out. Returns the layer's output and ``(pairs, experts_hit,
    pairs_elsewhere)`` int32 [3]."""
    from llmss_tpu.ops import moe

    m = cfg.moe
    B, S, E = x.shape
    act = act_fn(cfg.activation)
    flat = x.reshape(B * S, E)
    with jax.named_scope("moe.route"):
        if m.scoring == "softmax":
            idx, w = moe.route_softmax(
                x32.reshape(B * S, E), bp["router"].w, top_k=m.top_k,
                norm=m.norm_topk_prob,
            )
        else:
            idx, w = moe.route(
                x32.reshape(B * S, E), bp["router"].w, bp["router"].b,
                top_k=m.top_k, norm=m.norm_topk_prob,
                scale=m.routed_scaling_factor,
            )
    with jax.named_scope("moe.experts"):
        stacks, layer = experts
        y, counts = moe.routed_experts(
            flat, idx, w, live.reshape(B * S), stacks["experts_gate"],
            stacks["experts_up"], stacks["experts_down"], act, layer=layer,
            first=None if m.count is None else m.first,
        )
    if "shared_gate" not in bp:  # ``MoEConfig.shared_size`` 0
        return y.reshape(B, S, E), counts
    with jax.named_scope("moe.shared"):
        shared = dense(
            act(dense(x, bp["shared_gate"])) * dense(x, bp["shared_up"]),
            bp["shared_down"],
        )
        if m.shared_gate:
            sig = jax.nn.sigmoid(dense(x, bp["shared_sig"]).astype(jnp.float32))
            shared = (shared.astype(jnp.float32) * sig).astype(x.dtype)
    return y.reshape(B, S, E) + shared, counts


def _mlp_or_experts(cfg: DecoderConfig, bp: Params, h, moe_in):
    """The second half of a pre-norm block: ``h + MLP(norm(h))``, the MLP
    the dense one or, where the layer holds a router, the routed experts
    (``moe_in``: ``(live, experts)`` as ``_routed_mlp`` takes them). Returns
    ``(h, routing counts or None)``."""
    if "router" not in bp:
        return h + _mlp(cfg, bp, _norm(cfg, h, bp["ln2"])), None
    if moe_in is None:
        raise NotImplementedError(
            f"model_type {cfg.model_type!r} has routed experts, which this "
            "forward does not carry: they are served from the paged cache, "
            "through the period scan or the latent family's scans"
        )
    # the router reads the normed input before its rounding
    x32 = _norm(cfg, h, bp["ln2"], jnp.float32)
    mlp, counts = _routed_mlp(cfg, bp, x32.astype(h.dtype), x32, *moe_in)
    return h + mlp, counts


def _latent_attention(cfg: DecoderConfig, bp: Params, x, positions, sin_cos,
                      attend):
    """Multi-head latent attention in the ABSORBED form: ``x`` [B, S, E] the
    block's normed input; ``attend(q [B, S, H, W], latent [B, S, 1, W]) ->
    [B, S, H, W]`` (W: C + R padded to the pool's row) attention of the
    queries over the cached latents and these fresh ones, keys and values
    both the latent itself (multi-query attention with one shared head).
    Returns the branch's output [B, S, E] and the fresh latent to cache.

    ``W_kv_b`` is split a head into ``W_uk`` and ``W_uv``; the query meets
    ``W_uk`` before the cache and the weighted sum of latents meets ``W_uv``
    after it, so no head's keys or values of the context are ever rebuilt:
    ``q_nope . (c W_uk) == (q_nope W_uk^T) . c``."""
    m, H = cfg.mla, cfg.n_heads
    B, S, _ = x.shape
    C, Dn, Dv = m.kv_lora_rank, m.qk_nope_head_dim, m.v_head_dim
    q = dense_t(x, bp["q"]).reshape(B, S, H, m.qk_head_dim)
    q = constrain(q, P(AXIS_DP, None, AXIS_TP, None))
    kv = dense(x, bp["kv_a"])  # [B, S, C + R]
    c_kv = rms_norm(kv[..., :C], bp["kv_norm"], cfg.norm_eps)
    rope = partial(
        apply_rope, positions=positions, theta=cfg.rope_theta,
        style=cfg.rope_style, sin_cos=sin_cos,
    )
    q_rope = rope(q[..., Dn:])
    k_rope = rope(kv[..., None, C:])  # ONE rotary key, shared by all heads
    w_kv = bp["kv_b"].w.astype(x.dtype).reshape(C, H, Dn + Dv)
    q_lat = jnp.einsum("bshd,chd->bshc", q[..., :Dn], w_kv[..., :Dn])
    # both padded with zeros to the pool's row (``MLAConfig.pool_dim``):
    # the scores gain zeros, the weighted sum columns nobody reads
    pad = [jnp.zeros((B, S, n, m.pool_dim - m.latent_dim), x.dtype)
           for n in (1, H)]
    latent = jnp.concatenate([c_kv[:, :, None, :], k_rope, pad[0]], axis=-1)
    o_lat = attend(jnp.concatenate([q_lat, q_rope, pad[1]], axis=-1), latent)
    o = jnp.einsum("bshc,chd->bshd", o_lat[..., :C], w_kv[..., Dn:])
    return dense(o.reshape(B, S, H * Dv), bp["o"]), latent


def _mixer(cfg: DecoderConfig, bp: Params, x, ssm, conv, lens, layer=None):
    """The Mamba-2 branch of a block: ``x`` [B, S, E] is the block's normed
    input, ``ssm`` [B, H, P, N] float32 and ``conv`` [B, K-1, C] this layer's
    state of every row, ``lens`` [B] how many of the S positions are real
    (0: the row is done and its state stays as it is). Returns the branch's
    output [B, S, E] and the new ``(ssm, conv)``.

    S == 1 is the decode update (``ssm.decode``), anything longer the
    chunked scan (``ssm.prefill``); both start from the state handed in and
    treat positions at or after ``lens`` as no-ops (time step 0, window
    taken at the true length).

    With ``layer`` set (``state_update`` chose ``ssm.kernel``) ``ssm`` is the
    WHOLE pool ``[L, rows, H, P, N]``, batch row i its row i: layer ``layer``
    of it is updated where it lies (ops/pallas_ssm.py, under ``ssm.decode``
    whatever S is: a step's few positions) and the pool is what comes back."""
    m = cfg.ssm
    B, S, _ = x.shape
    H, Pd, G, N = m.n_heads, m.head_dim, m.n_groups, m.d_state
    f32 = jnp.float32
    mz, mx, mb, mc, mdt = m.multipliers

    p = dense(_scale(x, m.in_multiplier), bp["ssm_in"])
    z, xbc, dt = jnp.split(p, [m.d_ssm, m.d_ssm + m.conv_dim], axis=-1)
    seg = jnp.asarray(
        [mx] * m.d_ssm + [mb] * m.bc_dim + [mc] * m.bc_dim, f32
    )
    # rounded to the compute dtype here, so that the convolution reads the
    # same values from this call's inputs as from the carried window
    xbc = (xbc.astype(f32) * seg).astype(x.dtype)
    xbc, conv = causal_conv(
        xbc, conv, bp["ssm_conv"].w, bp["ssm_conv"].b, lens
    )
    xbc = jax.nn.silu(xbc)
    xs, Bm, Cm = jnp.split(xbc, [m.d_ssm, m.d_ssm + m.bc_dim], axis=-1)
    xs = xs.reshape(B, S, H, Pd)
    Bm, Cm = Bm.reshape(B, S, G, N), Cm.reshape(B, S, G, N)
    live = jnp.arange(S, dtype=lens.dtype)[None, :] < lens[:, None]
    dt = jax.nn.softplus(dt.astype(f32) * mdt + bp["ssm_dt_bias"].astype(f32))
    dt = jnp.where(live[..., None], dt, 0.0)
    A = -jnp.exp(bp["ssm_A_log"].astype(f32))
    if layer is not None:
        import importlib

        from llmss_tpu.ops import pallas_ssm

        interp = importlib.import_module(
            "llmss_tpu.ops.attention"
        ).pallas_interpret()
        with jax.named_scope("ssm.decode"):
            y, ssm = pallas_ssm.ssm_pool_update(
                ssm, xs, dt, A, Bm, Cm, lens, layer, interpret=interp
            )
    elif S == 1:
        with jax.named_scope("ssm.decode"):
            y, ssm = ssm_step(xs[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], ssm)
            y = y[:, None]
    else:
        with jax.named_scope("ssm.prefill"):
            y, ssm = ssd_scan(xs, dt, A, Bm, Cm, ssm, m.chunk_size)
    y = y + bp["ssm_D"].astype(f32)[:, None] * xs
    # gate, then RMSNorm over each group's channels (mamba_norm_before_gate
    # false, mamba_rms_norm true)
    y = y.reshape(B, S, m.d_ssm) * jax.nn.silu(z.astype(f32) * mz)
    yg = y.reshape(B, S, G, m.d_ssm // G)
    yg = yg * jax.lax.rsqrt(
        jnp.mean(yg * yg, axis=-1, keepdims=True) + cfg.norm_eps
    )
    y = yg.reshape(B, S, m.d_ssm) * bp["ssm_norm"].scale.astype(f32)
    out = _scale(dense(y.astype(x.dtype), bp["ssm_out"]), m.out_multiplier)
    return out, (ssm, conv)


def _gdn_mixer(cfg: DecoderConfig, bp: Params, x, ssm, conv, lens, at=None):
    """The mixer of a linear-attention layer (Gated DeltaNet): ``x``
    [B, S, E] the layer's input (normed already where the block is pre-norm),
    ``ssm`` [B, H, Dk, Dv] float32 (H the VALUE heads) and
    ``conv`` [B, (K-1) * C] (the window, flattened as the pool holds it)
    this layer's state of every row, ``lens`` [B] how many of the
    S positions are real (0: the row is done and its state stays as it is).
    Returns the mixer's output [B, S, E] and the new ``(ssm, conv)``.

    S == 1 is the decode update (``gdn.decode``), anything longer the
    chunked form (``gdn.prefill``); both start from the state handed in and
    treat positions at or after ``lens`` as no-ops (``g = 0``, ``beta = 0``,
    window taken at the true length).

    With ``at`` set (``state_update`` chose ``gdn.kernel``: ``(layer, the
    step's live rows)``) ``ssm`` is the WHOLE pool ``[L, rows, H, Dk, Dv]``,
    batch row i its row i: layer ``layer`` of it is updated where it lies
    (ops/pallas_gdn.py, under ``gdn.decode`` whatever S is: a step's few
    positions) and the pool is what comes back."""
    m = cfg.linear_attn
    B, S, _ = x.shape
    H, Dk, Dv = m.n_v_heads, m.key_head_dim, m.value_head_dim
    f32 = jnp.float32
    with jax.named_scope("gdn.conv"):
        qkv, window = causal_conv(
            dense(x, bp["gdn_qkv"]), conv.reshape(B, m.d_conv - 1, m.conv_dim),
            bp["gdn_conv"].w, None, lens,
        )
        qkv = jax.nn.silu(qkv)
    q, k, v = jnp.split(qkv, [m.key_dim, 2 * m.key_dim], axis=-1)
    q = l2_normalize(q.reshape(B, S, m.n_heads, Dk)) * Dk ** -0.5
    k = l2_normalize(k.reshape(B, S, m.n_heads, Dk))
    if H != m.n_heads:
        # grouped value heads: value head j reads key head j // (H / Hk)
        q, k = (jnp.repeat(a, H // m.n_heads, axis=2) for a in (q, k))
    v = v.reshape(B, S, H, Dv)
    a, b = jnp.split(dense(x, bp["gdn_ab"]).astype(f32), 2, axis=-1)
    live = (jnp.arange(S, dtype=lens.dtype)[None, :] < lens[:, None])[..., None]
    beta = jax.nn.sigmoid(b) * (2.0 if m.allow_neg_eigval else 1.0)
    beta = jnp.where(live, beta, 0.0)
    g = -jnp.exp(bp["gdn_A_log"].astype(f32)) * jax.nn.softplus(
        a + bp["gdn_dt_bias"].astype(f32)
    )
    g = jnp.where(live, g, 0.0)
    if at is not None:
        import importlib

        from llmss_tpu.ops import pallas_gdn

        interp = importlib.import_module(
            "llmss_tpu.ops.attention"
        ).pallas_interpret()
        layer, live_rows = at
        with jax.named_scope("gdn.decode"):
            o, ssm = pallas_gdn.gdn_pool_update(
                ssm, q, k, v, g, beta, lens, live_rows, layer,
                interpret=interp,
            )
    elif S == 1:
        with jax.named_scope("gdn.decode"):
            o, ssm = gdn_step(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], ssm
            )
            o = o[:, None]
    else:
        with jax.named_scope("gdn.prefill"):
            o, ssm = gdn_chunked(q, k, v, g, beta, ssm)
    with jax.named_scope("gdn.gate"):
        # RMSNorm over each head's values, then the output gate
        o = o * jax.lax.rsqrt(
            jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps
        ) * bp["gdn_norm"].scale.astype(f32)
        gate = dense(x, bp["gdn_g"]).astype(f32).reshape(B, S, H, Dv)
        o = (o * jax.nn.silu(gate)).reshape(B, S, H * Dv)
        out = dense(o.astype(x.dtype), bp["gdn_o"])
    return out, (ssm, window.reshape(conv.shape))


def _linear_block(cfg: DecoderConfig, bp: Params, h, state_in, moe_in=None):
    """One linear-attention layer of a model whose kinds alternate: the
    mixer, then the MLP or the routed experts (``moe_in`` as
    ``_mlp_or_experts`` takes it). ``cfg.post_norm`` (olmo_hybrid): each
    normed AFTER it and added; else (qwen3_next) pre-norm, ``h +
    mixer(norm(h))``, ``h + experts(norm(h))``. ``state_in`` is ``(ssm,
    conv, lens)`` or ``(the ssm POOL, conv, lens, at)`` as ``_gdn_mixer``
    takes them; returns ``(h, (ssm, conv), routing counts or None)``."""
    spec = P(AXIS_DP, None, None)  # the paged layouts have no sp axis
    if cfg.post_norm:
        mix, state = _gdn_mixer(cfg, bp, h, *state_in)
        h = h + constrain(_norm(cfg, mix, bp["ln1"]), spec)
        h = h + _norm(cfg, _mlp(cfg, bp, h), bp["ln2"])
        return constrain(h, spec), state, None
    mix, state = _gdn_mixer(cfg, bp, _norm(cfg, h, bp["ln1"]), *state_in)
    h, counts = _mlp_or_experts(cfg, bp, h + constrain(mix, spec), moe_in)
    return constrain(h, spec), state, counts


def _latent_block(cfg: DecoderConfig, bp: Params, h, positions, sin_cos,
                  attend, live, experts, mesh=None):
    """One block of a model with latent attention: pre-norm, sequential
    residual; the MLP is the dense one, or the routed experts where the
    layer holds a router. ``attend`` as ``_latent_attention`` takes it,
    ``live`` [B, S] which tokens are real, ``experts`` as ``_routed_mlp``
    takes them. Returns ``(h, fresh latent, routing counts or None)``."""
    spec = P(AXIS_DP, _seq_axis(mesh, h.shape[1]), None)
    attn, latent = _latent_attention(
        cfg, bp, _norm(cfg, h, bp["ln1"]), positions, sin_cos, attend
    )
    h, counts = _mlp_or_experts(
        cfg, bp, h + constrain(attn, spec), (live, experts)
    )
    return constrain(h, spec), latent, counts


def _block(
    cfg: DecoderConfig,
    bp: Params,
    h: jax.Array,  # [B, S, E]
    positions: jax.Array,  # [B, S]
    k_cache: jax.Array,  # [B, T, Hkv, D]
    v_cache: jax.Array,
    kv_positions: jax.Array,  # [B, T] (see ``defer_write`` for semantics)
    slots: jax.Array,  # [B, S]
    mask: jax.Array | None,  # [B, S, T] (None in defer_write mode)
    mesh=None,
    defer_write: bool = False,
    # (q, k_new, v_new, k_cache, v_cache) -> attn; set in defer_write mode
    # by the stacked-cache Pallas kernel (ignores the cache slices) or the
    # sp>1 fresh-KV LSE merge (uses them).
    attn_override=None,
    sin_cos=None,  # precomputed rope tables, hoisted out of the layer scan
    penalty=None,  # precomputed decode mask penalty, hoisted likewise
    # int8 cache: per-token-per-head dequant scales [B, T, Hkv]; when set,
    # k_cache/v_cache are the RAW int8 slices and the scales fold into the
    # attention contractions (ops/attention.py) — no dequant materializes.
    k_scale=None,
    v_scale=None,
    # (ssm [B, H, P, N], conv [B, K-1, C], lens [B]) of this layer, for a
    # config with a mixer, or (the ssm POOL, conv, lens, layer) where the
    # state is updated in place (see ``_mixer``)
    ssm_in=None,
    # heads of the paged pool this block reads and writes, where they are
    # more than the model's (``DecoderConfig.pool_kv_heads``): q, k and v
    # are padded with zero heads up to it and the padding's output dropped
    pool_heads: int | None = None,
    # (live [B, S], (stacked experts, this layer's index among them)) where
    # the block's MLP is the routed experts (see ``_mlp_or_experts``)
    moe_in=None,
    # out-parameter of a block with an indexer: ``aux["index_key"]`` [B, S,
    # Di] float32, the tokens' fresh indexer keys for the post-scan write
    aux: dict | None = None,
):
    """One decoder block. The last two elements returned are the mixer's
    new ``(ssm, conv)`` state, None for a config without one, and the expert
    layer's routing counts, None for a block without one.

    ``defer_write=False``: current-token KV is scattered into the cache,
    then attention reads the updated cache (``kv_positions`` includes the
    current tokens); returns the updated cache layer.

    ``defer_write=True`` (single-token decode): attention runs against the
    *stale* cache merged with the fresh KV in one softmax
    (``fresh_kv_decode_attention`` — ``kv_positions`` is pre-write), and the
    fresh KV is returned for one batched scatter after the layer scan.
    """
    B, S, E = h.shape
    Hq, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    seq_ax = _seq_axis(mesh, S)
    head_spec = P(AXIS_DP, seq_ax, AXIS_TP, None)
    kv_spec = head_spec if Hkv > 1 else P(AXIS_DP, seq_ax, None, None)

    res = h
    # post-norm (Olmo 2): a branch reads the residual as it is and is
    # normed on its way back into it
    index = None
    if cfg.indexer is not None:
        if not defer_write or attn_override is None:
            raise NotImplementedError(
                f"model_type {cfg.model_type!r} selects what attention "
                "reads, which this forward does not carry: it is served "
                "from the paged cache (kv_layout='paged')"
            )
        # the indexer reads the normed input before its rounding
        x32 = _norm(cfg, h, bp["ln1"], jnp.float32)
        x = x32.astype(h.dtype)
        with jax.named_scope("dsa.index"):
            index = _index_of(cfg, bp, x32)
        aux["index_key"] = index[2]
    else:
        x = h if cfg.post_norm else _norm(cfg, h, bp["ln1"])

    xa = _scale(x, cfg.attn_in_multiplier)
    q = dense_t(xa, bp["q"])
    if cfg.qk_norm:  # over the whole projection, before the head split
        q = _norm(cfg, q, bp["q_norm"])
    out_gate = None
    if cfg.attn_gate:  # a query and a gate a head, side by side
        q = q.reshape(B, S, Hq, 2 * D)
        q, out_gate = q[..., :D], q[..., D:]
    q = constrain(q.reshape(B, S, Hq, D), head_spec)
    k = _scale(dense_t(xa, bp["k"]), cfg.key_multiplier)
    if cfg.qk_norm:
        k = _norm(cfg, k, bp["k_norm"])
    k = constrain(k.reshape(B, S, Hkv, D), kv_spec)
    if cfg.qk_norm_per_head:  # over each head's own features
        with jax.named_scope("attn.qk_norm"):
            q = _norm(cfg, q, bp["q_norm"])
            k = _norm(cfg, k, bp["k_norm"])
    v = constrain(dense(xa, bp["v"]).reshape(B, S, Hkv, D), kv_spec)

    if cfg.positions == "rotary":
        q = apply_rope(
            q, positions, rotary_dim=cfg.rotary_dim, theta=cfg.rope_theta,
            style=cfg.rope_style, sin_cos=sin_cos,
        )
        k = apply_rope(
            k, positions, rotary_dim=cfg.rotary_dim, theta=cfg.rope_theta,
            style=cfg.rope_style, sin_cos=sin_cos,
        )

    padded = pool_heads is not None and pool_heads != Hkv
    if padded:
        pad = [(0, 0), (0, 0), (0, pool_heads - Hkv), (0, 0)]
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)

    if index is not None:
        attn = attn_override(q, k, v, k_cache, v_cache, index=index)
    elif defer_write:
        if attn_override is not None:
            attn = attn_override(q, k, v, k_cache, v_cache)
        else:
            attn = fresh_kv_decode_attention(
                q, k_cache, v_cache, k, v, positions, kv_positions, slots,
                scale=cfg.attn_scale, window=cfg.sliding_window,
                penalty=penalty, k_scale=k_scale, v_scale=v_scale,
            )
    else:
        k_cache, v_cache = write_layer(k_cache, v_cache, k, v, slots)
        attn = dispatch_attention(
            q, k_cache, v_cache, mask=mask, q_positions=positions,
            kv_positions=kv_positions, scale=cfg.attn_scale, mesh=mesh,
            window=cfg.sliding_window,
        )
    if padded:
        attn = attn[:, :, :Hq]
    if out_gate is not None:
        with jax.named_scope("attn.gate"):
            attn = (
                attn.astype(jnp.float32)
                * jax.nn.sigmoid(out_gate.astype(jnp.float32))
            ).astype(attn.dtype)
    attn = _scale(
        dense(attn.reshape(B, S, Hq * D), bp["o"]), cfg.attn_out_multiplier
    )
    if cfg.post_norm:
        attn = _norm(cfg, attn, bp["ln1"])
    ssm_out = None
    if cfg.ssm is not None:
        # The second branch reads the same normed input and adds to the
        # residual beside attention.
        mix, ssm_out = _mixer(cfg, bp, x, *ssm_in)
        attn = attn + mix
    attn = constrain(attn, P(AXIS_DP, seq_ax, None))

    counts = None
    if cfg.parallel_residual:
        # GPT-J form: one pre-LN feeds both branches; residual adds both
        # (gptj_modeling.py:295-310). GPT-NeoX gives the MLP branch its
        # own pre-norm (parallel_residual_ln2).
        mlp_in = _norm(cfg, res, bp["ln2"]) if cfg.has_ln2 else x
        h = res + attn + _mlp(cfg, bp, mlp_in)
    elif cfg.post_norm:
        h = res + attn
        h = h + _norm(cfg, _mlp(cfg, bp, h), bp["ln2"])
    else:
        h, counts = _mlp_or_experts(cfg, bp, res + attn, moe_in)
    h = constrain(h, P(AXIS_DP, seq_ax, None))
    if defer_write:
        # fresh KV for the single post-scan scatter
        return h, k, v, ssm_out, counts
    return h, k_cache, v_cache, k, v, ssm_out, counts


def _index_of(cfg: DecoderConfig, bp: Params, x32):
    """The indexer's side of a block (``IndexerConfig``): from the normed
    input ``x32`` [B, S, E] float32, the queries ``[B, S, Hi, Di]``, the
    head weights ``[B, S, Hi]`` (times ``Hi^-1/2 Di^-1/2``) and the ONE key
    ``[B, S, Di]`` a token (LayerNorm with scale and bias, eps 1e-6), all
    float32 at ``Precision.HIGHEST`` whatever the compute dtype. No rotary
    (docs/sparse-attention.md)."""
    x = cfg.indexer
    B, S, _ = x32.shape
    f32, hi = jnp.float32, jax.lax.Precision.HIGHEST

    def proj(name):
        return jnp.einsum(
            "bse,eo->bso", x32, bp[name].w.astype(f32), precision=hi
        )

    qi = proj("idx_q").reshape(B, S, x.n_heads, x.head_dim)
    ki = layer_norm(proj("idx_k"), bp["idx_k_norm"], 1e-6)
    wi = proj("idx_w") * (x.n_heads ** -0.5 * x.head_dim ** -0.5)
    return qi, wi, ki


def _ssm_lens(cache, kv_write_positions, slots):
    """How many of a call's positions are real, a row: those that record a
    position (padding carries -1) and write a slot (a done row's slot is
    positive out of range). What the mixer may advance its state by."""
    live = (kv_write_positions >= 0) & (slots < cache.max_len)
    return jnp.sum(live.astype(jnp.int32), axis=1)


def _layer_scan(cfg: DecoderConfig, cache, lens, body, h, xs, linear=None,
                in_place: bool = False, moe=None):
    """``lax.scan`` of ``body(h, xs, ssm_in, moe_in) -> (h, ys, ssm_out,
    counts)`` over the stacked layers; returns ``(h, ys, state, counts)``
    (``counts``: the expert layers' routing counts summed, None for a model
    without experts outside the latent family). For a config with a
    recurrent state the state pool ``[L_state, rows, ...]`` rides the scan's
    carry: the pool is donated with the rest of the cache, so a step holds
    one copy of it. What a LAYER costs depends on the branch:

    * ``in_place`` (a decode or mixed step where ``state_update`` chose a
      kernel: ``ssm.kernel`` for a Mamba-2 pool, ``gdn.kernel`` for the
      linear-attention layers of the period branch below): the body, or
      ``_linear_block``, gets the whole state pool and the layer's index
      and returns the pool, updated where it lies by one kernel
      (ops/pallas_ssm.py: one read and one write of the layer's state;
      ops/pallas_gdn.py: of its LIVE rows' state, walking one list of them
      made here for all layers): no slice, no update back. The convolution
      window's pool (3 x ``conv_dim`` a row) is still sliced and set.
    * every other stateful scan (the XLA oracles, an admission view)
      SLICES the layer's state out of the pool, which
      the compiler makes a copy, and writes the new one back with a
      ``dynamic-update-slice``: two more passes over the layer's state than
      the update itself makes (cell 2's line of PR 42: 0.755 s and 1.082 s
      of a 6 s profile).

    Where the kinds of layer ALTERNATE (``cfg.layer_types``) the scan is over
    PERIODS of the pattern: ``xs`` (every leaf ``[L_kv, ...]``) is the
    attention layers' and ``linear`` (``params["linear"]``, ``[L_state,
    ...]``) the linear-attention layers' stack, both closed over; a step of
    the scan runs one period's layers in the published order, ``body`` on each attention layer
    (no state) and ``_linear_block`` on each linear one, every layer indexing
    its own stack and pool by its index WITHIN its kind. ``ys`` comes back
    ``[L_kv, ...]``. Where every layer of both kinds is followed by routed
    experts (``moe``: ``(live [B, S], the stacked experts of ALL layers)``)
    each layer is handed ``moe_in`` = ``(live, (experts, its ABSOLUTE
    index))``, and the counts of a period's layers are summed on the way out.

    ``cache.state_rows`` None: batch row i IS pool row i and goes on from
    the state it has (decode, and a ragged chunk). Set (an admission view):
    every batch row starts from zeros, as a prompt does, and its final state
    is written to pool row ``state_rows[i]``; a row index out of range (the
    view's padding rows) writes nowhere."""
    if not cfg.has_state and moe is None:
        def plain(h, xs):
            h, ys, _, _ = body(h, xs, None, None)
            return h, ys

        h, ys = jax.lax.scan(plain, h, xs)
        return h, ys, None, None
    if not cfg.has_state:
        # one kind of layer, each followed by routed experts (keye_vl2)
        def routed(h, xs_l):
            xs, l = xs_l
            h, ys, _, counts = body(h, xs, None, (moe[0], (moe[1], l)))
            return h, (ys, counts)

        h, (ys, counts) = jax.lax.scan(
            routed, h, (xs, jnp.arange(cfg.n_layers, dtype=jnp.int32))
        )
        return h, ys, None, jnp.sum(counts, axis=0)
    rows, B = cache.state_rows, h.shape[0]

    def state_in(ssm, conv, l):
        if rows is None:
            return ssm[l], conv[l]
        return (jnp.zeros((B,) + ssm.shape[2:], ssm.dtype),
                jnp.zeros((B,) + conv.shape[2:], conv.dtype))

    def state_out(ssm, conv, l, s_l, c_l):
        if rows is None:
            return ssm.at[l].set(s_l), conv.at[l].set(c_l)
        return (ssm.at[l, rows].set(s_l, mode="drop"),
                conv.at[l, rows].set(c_l, mode="drop"))

    if cfg.layer_types is None:
        def stateful(carry, xs_l):
            h, ssm, conv = carry
            xs, l = xs_l
            if in_place:  # ``rows`` is None: ``state_update`` saw to it
                h, ys, (ssm, c_l), _ = body(
                    h, xs, (ssm, conv[l], lens, l), None
                )
                return (h, ssm, conv.at[l].set(c_l)), ys
            s_in, c_in = state_in(ssm, conv, l)
            h, ys, (s_l, c_l), _ = body(h, xs, (s_in, c_in, lens), None)
            return (h, *state_out(ssm, conv, l, s_l, c_l)), ys

        (h, ssm, conv), ys = jax.lax.scan(
            stateful, (h, cache.ssm, cache.conv),
            (xs, jnp.arange(cfg.n_layers, dtype=jnp.int32)),
        )
        return h, ys, (ssm, conv), None

    period = cfg.period
    n_per = cfg.n_layers // len(period)
    n_lin = period.count("linear_attention")
    n_kv = len(period) - n_lin
    if in_place:  # one list of the step's live rows serves every layer
        from llmss_tpu.ops import pallas_gdn

        live_rows = pallas_gdn.live_rows(lens)

    def one_period(carry, p):
        h, ssm, conv = carry
        ys, counts, i_lin, i_kv = [], [], 0, 0
        for i, kind in enumerate(period):
            moe_in = moe and (moe[0], (moe[1], p * len(period) + i))
            # Each layer reads ITS layer of the whole stack, closed over, by
            # a dynamic index: with a period's layers handed in as one ``xs``
            # block the compiler copies that block (three layers' matrices)
            # out of the stack every period of every step.
            if kind == "linear_attention":
                l = p * n_lin + i_lin
                bp = _layer_of(linear, l)
                if in_place:  # ``rows`` is None: ``state_update`` saw to it
                    h, (ssm, c_l), c = _linear_block(
                        cfg, bp, h, (ssm, conv[l], lens, (l, live_rows)),
                        moe_in,
                    )
                    conv = conv.at[l].set(c_l)
                else:
                    h, (s_l, c_l), c = _linear_block(
                        cfg, bp, h, (*state_in(ssm, conv, l), lens), moe_in
                    )
                    ssm, conv = state_out(ssm, conv, l, s_l, c_l)
                i_lin += 1
            else:
                h, y, _, c = body(
                    h, _layer_of(xs, p * n_kv + i_kv), None, moe_in
                )
                ys.append(y)
                i_kv += 1
            counts.append(c)
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
        return (h, ssm, conv), (ys, sum(counts) if moe else None)

    (h, ssm, conv), (ys, counts) = jax.lax.scan(
        one_period, (h, cache.ssm, cache.conv),
        jnp.arange(n_per, dtype=jnp.int32),
    )
    ys = jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), ys)
    if counts is not None:
        counts = jnp.sum(counts, axis=0)
    return h, ys, (ssm, conv), counts


def _moe_of(params: Params, cache, kv_write_positions, slots):
    """``_layer_scan``'s ``moe`` for a model whose every layer is followed by
    routed experts (``params["experts"]``; None for any other): which
    tokens are real (as ``_forward_latent`` has it: padding and done rows
    record no position or write no slot, and are routed nowhere) and the
    stacked experts of all layers."""
    if "experts" not in params:
        return None
    live = (kv_write_positions >= 0) & (slots < cache.max_len)
    return live, params["experts"]


def _layer_of(stack, l):
    """Layer ``l`` (traced) of a pytree of ``[L, ...]`` stacks."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False), stack
    )


def _make_sp_decode_attn(cfg, mesh, cache, positions, slots):
    """Dispatch for sp>1 deferred-write decode: returns a
    ``(q, k_new, v_new, k_cache, v_cache) -> attn`` callable running
    ``lse_merge_fresh_kv_attention`` inside shard_map, or None when the
    shapes can't ride the sp axis (caller falls back to in-scan writes +
    the plain LSE merge, same as before)."""
    import importlib

    from llmss_tpu.ops import ring_attention as ring_mod

    attention_mod = importlib.import_module("llmss_tpu.ops.attention")
    if attention_mod.IMPL_OVERRIDE is not None:
        return None
    B, T = cache.k.shape[1], cache.max_len
    ok, kv_ax = attention_mod.sp_plan(
        mesh, B, T, cfg.n_heads, cfg.n_kv_heads
    )
    if not ok:
        return None

    qs = P(AXIS_DP, None, AXIS_TP, None)
    ks = P(AXIS_DP, AXIS_SP, kv_ax, None)
    kns = P(AXIS_DP, None, kv_ax, None)
    ps = P(AXIS_DP, None)

    def local(q, kc, vc, qp, kvp, kn, vn, sl):
        return ring_mod.lse_merge_fresh_kv_attention(
            q, kc, vc, qp, kvp, kn, vn, sl, axis_name=AXIS_SP,
            scale=cfg.attn_scale, window=cfg.sliding_window,
        )

    sharded = jax.shard_map(
        local, mesh=mesh,
        in_specs=(qs, ks, ks, ps, P(AXIS_DP, AXIS_SP), kns, kns, ps),
        out_specs=qs, check_vma=False,
    )

    def attn(q, k_new, v_new, k_cache, v_cache):
        return sharded(
            q, k_cache, v_cache, positions, cache.positions, k_new, v_new,
            slots,
        )

    return attn


def _embed_in(cfg: DecoderConfig, params: Params, input_ids, positions, mesh):
    """Token (+learned position) embedding into the hidden stream — the
    shared entry of the dense and paged forwards."""
    dtype = cfg.compute_dtype
    # Vocab-parallel embedding. Prefill uses the one-hot matmul formulation:
    # algebraically the reference's mask + partial-gather + psum
    # (layers.py:200-213), and it stays on the MXU. Decode (S=1) uses a
    # gather — the one-hot matmul streams the whole [V, E] table through
    # the MXU for one token (~5% of all param bytes per step at 1B scale),
    # where a gather reads B·E floats.
    #
    # The one-hot form exists to partition over a vocab-sharded table: with
    # tp == 1 there is nothing to partition, and at a vocabulary of 261,120
    # the matmul is 2.7 GFLOP a prompt token (more than five full-width
    # layers) and the compiler holds a re-laid-out copy of the 2.7 GB table
    # beside it (compiled for a described v5e, PR 29). So prefill gathers
    # too unless the mesh shards the vocabulary. The rows are the same bits
    # either way: a one-hot row sums one term.
    one_hot = input_ids.shape[1] > 1 and (
        mesh is not None and mesh.shape[AXIS_TP] > 1
    )
    h = embedding(input_ids, params["wte"].astype(dtype), one_hot=one_hot)
    if cfg.embed_multiplier is not None:
        # Gemma scales hidden states by sqrt(hidden_size) post-embedding
        # (cast-then-scale order matches HF's bf16 reference).
        h = h * jnp.asarray(cfg.embed_multiplier, dtype)
    if cfg.positions == "learned":
        h = h + embedding(
            positions, params["wpe"].astype(dtype), one_hot=one_hot
        )
    return constrain(h, P(AXIS_DP, _seq_axis(mesh, h.shape[1]), None))


def _head_out(
    cfg: DecoderConfig, params: Params, h, gather_idx, last_only,
):
    """Final norm + hidden-state gather + vocab head — the shared exit of
    the dense and paged forwards. Returns fp32 logits."""
    h = _norm(cfg, h, params["ln_f"])
    if gather_idx is not None:
        B = h.shape[0]
        h = h[jnp.arange(B), gather_idx][:, None, :]
    elif last_only:
        h = h[:, -1:, :]

    if cfg.tie_word_embeddings:
        # Tied head (gpt_bigcode_modeling.py:792-797): contract against the
        # vocab-sharded embedding; constraining the output replicated makes
        # XLA emit the reference's all-gather (layers.py:125).
        logits = jnp.einsum(
            "bse,ve->bsv", h, params["wte"].astype(h.dtype)
        ).astype(jnp.float32)
    else:
        from llmss_tpu.ops.layers import lm_head

        logits = lm_head(h, params["head"])
    if cfg.lm_head_multiplier != 1.0:
        logits = logits * cfg.lm_head_multiplier
    return constrain(logits, P(AXIS_DP, None, None))


def forward(
    cfg: DecoderConfig,
    params: Params,
    input_ids: jax.Array,  # [B, S]
    positions: jax.Array,  # [B, S] absolute positions
    cache: KVCache,
    slots: jax.Array,  # [B, S] ring slots for the new tokens
    *,
    last_only: bool = False,
    gather_idx: jax.Array | None = None,  # [B] per-row index into S
    kv_write_positions: jax.Array | None = None,  # [B, S]; -1 marks padding
    mesh=None,  # enables the Pallas attention path (shard_map needs a Mesh)
    t_bucket: int | None = None,  # static; decode reads only slots [0, t_bucket)
    # "no_scatter" drops the deferred decode write (tests/test_ring.py's
    # receipt that an sp>1 mesh takes the deferred path); dense ring only
    _ablate: str | None = None,
    # out-parameter for traced by-products of the call, read by the caller
    # in the same trace: ``aux["moe_counts"]`` (a model with routed experts)
    aux: dict | None = None,
) -> tuple[jax.Array, KVCache]:
    """Run the decoder; returns (logits fp32, updated cache).

    ``last_only=True`` projects only each row's final hidden state through the
    vocab head — the decode-loop path (the reference computes full-sequence
    logits every step and indexes [-1], ``generate.py:106-108``).
    ``gather_idx`` generalizes this to a per-row dynamic index (right-padded
    prefill: each row's last real token). ``kv_write_positions`` lets padding
    slots be recorded as −1 (invalid) so later steps never attend them —
    unlike the reference, whose pads participate in attention unmasked
    (``generate.py:104,150`` — SURVEY.md §2.11.3, a quirk fixed here).

    ``t_bucket`` (static) bounds the decode attention's cache read to ring
    slots ``[0, t_bucket)``: KV-read HBM traffic scales with *live* context,
    not the provisioned ring size (the decode step is bandwidth-bound, so a
    quarter-full cache decodes measurably faster — round 5,
    PROFILE.md@e57f952). Writes still
    land in the full buffer. **Caller contract** (DecodeEngine.decode_bucket
    enforces it): every live slot (position >= 0) of every row, and every
    slot written this call, is < ``t_bucket`` — i.e. no row has ring-wrapped
    and none will pass position ``t_bucket`` this call. Violations silently
    drop context. Applied only on the deferred-write decode path (S == 1,
    sp == 1, XLA attention); other paths ignore it.
    """
    if isinstance(cache, PagedKVCache):
        return _forward_paged(
            cfg, params, input_ids, positions, cache, slots,
            last_only=last_only, gather_idx=gather_idx,
            kv_write_positions=kv_write_positions, mesh=mesh,
            t_bucket=t_bucket, aux=aux,
        )

    if cfg.mla is not None:
        raise NotImplementedError(
            "a model with latent attention is served from the paged cache "
            "only (kv_layout='paged'): the dense ring has no latent pool"
        )
    if cfg.has_state:
        raise NotImplementedError(
            "a model with a recurrent state is served from the paged cache "
            "only (kv_layout='paged'): the dense ring has no state pool"
        )
    if cfg.indexer is not None:
        raise NotImplementedError(
            "a model that selects what attention reads is served from the "
            "paged cache only (kv_layout='paged'): the dense ring has no "
            "pool for its indexer's keys"
        )
    dtype = cfg.compute_dtype
    h = _embed_in(cfg, params, input_ids, positions, mesh)

    if kv_write_positions is None:
        kv_write_positions = positions
    new_kv_positions = write_positions(cache.positions, kv_write_positions, slots)

    S = input_ids.shape[1]
    # Rope sin/cos depend only on positions — compute ONCE per forward,
    # outside the layer scan. Computed inside the body, the q-rope and
    # k-rope share the trig subexpressions and XLA's producer-fusion
    # heuristics then stop fusing the cache dynamic-slices into the
    # attention contractions (+0.67 ms/step measured at bench scale).
    sin_cos = None
    if cfg.positions == "rotary":
        sin_cos = sin_cos_tables(
            positions, cfg.rotary_dim or cfg.head_dim, cfg.rope_theta,
            cfg.rope_freq_factors, cfg.rope_attn_factor,
        )
    # Single-token decode defers all KV writes to one batched scatter after
    # the layer scan (TPU scatter cost is per-op; L in-scan scatters were
    # ~25% of decode step time) — on sp>1 meshes too, via the fresh-KV LSE
    # merge over the stale sequence-sharded cache (falls back to in-scan
    # writes + plain LSE merge only when shapes can't ride the sp axis).
    sp_attn = None
    if S == 1 and mesh is not None and mesh.shape[AXIS_SP] > 1:
        sp_attn = _make_sp_decode_attn(cfg, mesh, cache, positions, slots)
    # Small decode windows (speculative verify: a handful of tokens per
    # row) also take the deferred-write path via the windowed fresh-KV
    # merge — one post-scan scatter + bucketable cache reads instead of
    # the prefill machinery (L in-scan scatters, materialized masks).
    window_defer = (
        1 < S <= 8
        and cfg.sliding_window is None
        and not cache.quantized
        and (mesh is None or mesh.shape[AXIS_SP] == 1)
    )
    defer_write = window_defer or (
        S == 1 and (
            mesh is None or mesh.shape[AXIS_SP] == 1 or sp_attn is not None
        )
    )

    quant = cache.quantized
    if defer_write:
        # Bucketed cache read: in bucket mode the per-layer KV (and
        # scales) is fetched with a hand-emitted ``lax.dynamic_slice``
        # of size [1, B, t_bucket, Hkv, D] from the full stacked cache
        # (a scan *constant*, not an xs operand) — only live-context
        # bytes ever stream from HBM. This slicing must be explicit:
        # XLA does NOT fold a static T-slice into the scan's
        # per-iteration layer dynamic-slice — a pre-scan slice of the
        # stacked cache materializes a fresh [L, B, tb, H, D] operand
        # (+1.3 ms/step at bench scale) and an in-body slice adds an
        # HBM round-trip after the full-T copy (+0.3 ms/step); both
        # measured slower than just reading the full ring. The
        # post-scan scatter below still writes the full buffers.
        bucket = (
            t_bucket
            if t_bucket is not None and t_bucket < cache.max_len
            and sp_attn is None
            else None
        )
        kv_pos_src = (
            cache.positions[:, :bucket]
            if bucket is not None else cache.positions
        )
        penalty = None
        win_attn = None
        if sp_attn is None:
            if S == 1:
                penalty = decode_mask_penalty(
                    positions, kv_pos_src, slots, cfg.sliding_window
                )
            else:
                # Windowed fresh-KV merge: one [B, T] cache penalty
                # (every pre-window slot is visible to all window
                # queries) + a compile-time triangular intra-window
                # mask inside the attention itself.
                penalty_w = window_mask_penalty(
                    positions[:, :1], kv_pos_src, slots
                )

                def win_attn(q, k_new, v_new, k_c, v_c):
                    return fresh_kv_window_attention(
                        q, k_c, v_c, k_new, v_new, penalty_w,
                        scale=cfg.attn_scale,
                    )
        B = input_ids.shape[0]
        Hkv, D = cfg.n_kv_heads, cfg.head_dim

        def layer_kv(l):
            """[B, bucket, ...] KV (+scale) slices of layer ``l``."""
            def sl(buf, *feat):
                return jax.lax.dynamic_slice(
                    buf, (l,) + (0,) * (2 + len(feat)),
                    (1, B, bucket) + feat,
                )[0]

            k_l = sl(cache.k, Hkv, D)
            v_l = sl(cache.v, Hkv, D)
            if not quant:
                return k_l, v_l, None, None
            return k_l, v_l, sl(cache.k_scale, Hkv), sl(
                cache.v_scale, Hkv
            )

        def body(h, xs):
            ks_l = vs_l = None
            if bucket is not None:
                bp, l = xs
                k_l, v_l, ks_l, vs_l = layer_kv(l)
            elif quant:
                bp, k_l, v_l, ks_l, vs_l = xs
            else:
                bp, k_l, v_l = xs
            if quant and sp_attn is not None:
                # The sp shard_map path expects compute-dtype chunks:
                # pre-dequantize (materializes a bf16 copy of the
                # layer — the price of int8 on sp meshes). Otherwise
                # the raw int8 slices ride: the scales fold into the
                # attention contractions (fresh_kv_decode_attention)
                # so no dequantized copy ever materializes.
                k_l = dequantize_kv(k_l, ks_l, dtype)
                v_l = dequantize_kv(v_l, vs_l, dtype)
                ks_l = vs_l = None
            h, k_f, v_f, _, _ = _block(
                cfg, bp, h, positions, k_l, v_l, kv_pos_src, slots,
                None, mesh=mesh, defer_write=True,
                attn_override=sp_attn if sp_attn is not None
                else win_attn,
                sin_cos=sin_cos, penalty=penalty,
                k_scale=ks_l, v_scale=vs_l,
            )
            ys = None if _ablate == "no_scatter" else (k_f, v_f)
            return h, ys

        if bucket is not None:
            xs = (
                params["blocks"],
                jnp.arange(cfg.n_layers, dtype=jnp.int32),
            )
        elif quant:
            xs = (params["blocks"], cache.k, cache.v, cache.k_scale,
                  cache.v_scale)
        else:
            xs = (params["blocks"], cache.k, cache.v)
        h, ys = jax.lax.scan(body, h, xs)
        ks_new, vs_new = cache.k_scale, cache.v_scale
        if _ablate == "no_scatter":
            k_new, v_new = cache.k, cache.v
        else:
            k_fresh, v_fresh = ys
            B = input_ids.shape[0]
            b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]
            if quant:
                k_fresh, ks_f = quantize_kv(k_fresh)
                v_fresh, vs_f = quantize_kv(v_fresh)
                ks_new = cache.k_scale.at[:, b_idx, slots].set(ks_f)
                vs_new = cache.v_scale.at[:, b_idx, slots].set(vs_f)
            k_new = cache.k.at[:, b_idx, slots].set(
                k_fresh.astype(cache.k.dtype)
            )
            v_new = cache.v.at[:, b_idx, slots].set(
                v_fresh.astype(cache.v.dtype)
            )
    else:
        kv_valid = new_kv_positions >= 0
        mask = make_causal_mask(positions, new_kv_positions, kv_valid)

        b_idx = jnp.arange(input_ids.shape[0], dtype=jnp.int32)[:, None]

        def body(h, xs):
            if quant:
                bp, k_q, v_q, ks_l, vs_l = xs
                k_l = dequantize_kv(k_q, ks_l, dtype)
                v_l = dequantize_kv(v_q, vs_l, dtype)
            else:
                bp, k_l, v_l = xs
            h, k_l, v_l, k_f, v_f, _, _ = _block(
                cfg, bp, h, positions, k_l, v_l, new_kv_positions, slots,
                mask, mesh=mesh, sin_cos=sin_cos,
            )
            if quant:
                # Quantize ONLY the freshly written tokens and scatter them
                # (values + scales) into the carried int8 cache. Untouched
                # slots are never dequant→requant round-tripped, so their
                # STORAGE is bit-stable by construction — a reused prefix
                # holds identical int8 bits. (Reads are not bitwise
                # identical across paths: this S>1 branch dequantizes in
                # compute dtype, while the decode path folds the scales in
                # fp32 — a small, bounded read-side difference.)
                k8, ks_f = quantize_kv(k_f)  # [B, S, Hkv(, D)]
                v8, vs_f = quantize_kv(v_f)
                k_q = k_q.at[b_idx, slots].set(k8)
                v_q = v_q.at[b_idx, slots].set(v8)
                ks_l = ks_l.at[b_idx, slots].set(ks_f)
                vs_l = vs_l.at[b_idx, slots].set(vs_f)
                return h, (k_q, v_q, ks_l, vs_l)
            return h, (k_l, v_l)

        if quant:
            h, (k_new, v_new, ks_new, vs_new) = jax.lax.scan(
                body, h,
                (params["blocks"], cache.k, cache.v, cache.k_scale,
                 cache.v_scale),
            )
        else:
            ks_new, vs_new = None, None
            h, (k_new, v_new) = jax.lax.scan(
                body, h, (params["blocks"], cache.k, cache.v)
            )

    logits = _head_out(cfg, params, h, gather_idx, last_only)
    return logits, KVCache(
        k=k_new, v=v_new, positions=new_kv_positions,
        k_scale=ks_new, v_scale=vs_new,
    )


def _forward_paged(
    cfg: DecoderConfig,
    params: Params,
    input_ids: jax.Array,  # [B, S]
    positions: jax.Array,  # [B, S]
    cache: PagedKVCache,
    slots: jax.Array,  # [B, S] LOGICAL slots (same arithmetic as dense)
    *,
    last_only: bool = False,
    gather_idx: jax.Array | None = None,
    kv_write_positions: jax.Array | None = None,
    mesh=None,
    t_bucket: int | None = None,
    aux: dict | None = None,
) -> tuple[jax.Array, PagedKVCache]:
    """``forward`` over the paged block-pool cache (``kv_layout="paged"``).

    The contract with callers is IDENTICAL to the dense forward — logical
    slots, position bookkeeping, bucketing, sampling inputs are unchanged —
    only the storage under a row's logical slot axis is indirected through
    its block table. Decode (S == 1) keeps the deferred-write structure:
    attention runs over the stale pool (XLA: per-row gathered logical views,
    identical values and slot order to the dense ring — or
    ops/pallas_kv.py reading blocks in place), and the fresh KV lands in one
    batched all-layer pool scatter after the scan. Prefill gathers each
    layer's logical view, runs the dense write-then-attend block over it,
    and persists the fresh tokens through ``(block, offset)`` scatters.

    ``t_bucket`` rounds up to whole blocks (reads table columns
    ``[0, ceil(t_bucket/bs))`` — same caller contract as dense). sp>1
    meshes and the speculative window-defer path are dense-only for now:
    S in (1, 8] routes through the general prefill branch here.
    """
    if cfg.mla is not None:
        return _forward_latent(
            cfg, params, input_ids, positions, cache, slots,
            last_only=last_only, gather_idx=gather_idx,
            kv_write_positions=kv_write_positions, mesh=mesh,
            t_bucket=t_bucket, aux=aux,
        )
    if cfg.indexer is not None:
        return _forward_selected(
            cfg, params, input_ids, positions, cache, slots,
            last_only=last_only, gather_idx=gather_idx,
            kv_write_positions=kv_write_positions, mesh=mesh,
            t_bucket=t_bucket, aux=aux,
        )
    dtype = cfg.compute_dtype
    h = _embed_in(cfg, params, input_ids, positions, mesh)

    if kv_write_positions is None:
        kv_write_positions = positions
    new_kv_positions = write_positions(
        cache.positions, kv_write_positions, slots
    )

    B, S = input_ids.shape
    bs, MB = cache.block_size, cache.max_blocks
    quant = cache.quantized
    lens = _ssm_lens(cache, kv_write_positions, slots)
    moe = _moe_of(params, cache, kv_write_positions, slots)

    sin_cos = None
    if cfg.positions == "rotary":
        sin_cos = sin_cos_tables(
            positions, cfg.rotary_dim or cfg.head_dim, cfg.rope_theta,
            cfg.rope_freq_factors, cfg.rope_attn_factor,
        )

    if S == 1:
        # Bucketed pool read: round the slot bucket up to whole table
        # columns — the gather then copies only ceil(t_bucket/bs) blocks
        # per row, so KV-read HBM traffic scales with live context exactly
        # as the dense bucketed dynamic-slice does.
        nb = None
        if t_bucket is not None and t_bucket < cache.max_len:
            nb = min(-(-t_bucket // bs), MB)
        Tv = (nb if nb is not None else MB) * bs
        kv_pos_src = cache.positions[:, :Tv]

        # The layer scan closes over the stacked pool and reads it with the
        # layer as an INDEX (of the kernel's block map, or of the one
        # gather): never as a slice, which the compiler copies out whole
        # ahead of a gather (docs/paged-kv.md).
        if attn_read(cfg, cache, mesh, 1) == "kv.kernel":
            attn = _make_kv_read(
                cfg, cache, positions[:, 0], jnp.ones((B,), jnp.int32),
                slots[:, 0], kv_pos_src,
            )
        else:
            penalty = decode_mask_penalty(
                positions, kv_pos_src, slots, cfg.sliding_window
            )

            def attn(q, k_new, v_new, k_c, v_c, *, layer):
                del k_c, v_c  # reads the stacked pool directly
                return paged_decode_attention(
                    q, cache.k, cache.v, k_new, v_new, positions,
                    kv_pos_src, cache.block_tables, slots,
                    scale=cfg.attn_scale, window=cfg.sliding_window,
                    penalty=penalty, k_scale_layer=cache.k_scale,
                    v_scale_layer=cache.v_scale, n_blocks=nb, layer=layer,
                )

        def body(h, xs, ssm_in, moe_in):
            bp, layer = xs
            h, k_f, v_f, ssm_out, counts = _block(
                cfg, bp, h, positions, None, None, kv_pos_src, slots,
                None, mesh=mesh, defer_write=True,
                attn_override=partial(attn, layer=layer),
                sin_cos=sin_cos, ssm_in=ssm_in, pool_heads=cache.k.shape[3],
                moe_in=moe_in,
            )
            return h, (k_f, v_f), ssm_out, counts

        h, ys, state, moe_counts = _layer_scan(
            cfg, cache, lens, body, h,
            (params["blocks"], jnp.arange(cfg.n_kv_layers, dtype=jnp.int32)),
            linear=params.get("linear"),
            in_place=state_update(cfg, cache, mesh, 1) != "xla",
            moe=moe,
        )

        ks_new, vs_new = cache.k_scale, cache.v_scale
        k_fresh, v_fresh = ys  # [L, B, 1, Hkv, D]
        if quant:
            k_fresh, ks_f = quantize_kv(k_fresh)
            v_fresh, vs_f = quantize_kv(v_fresh)
            ks_new = paged_write_stacked(
                cache.k_scale, ks_f, cache.block_tables, slots, bs
            )
            vs_new = paged_write_stacked(
                cache.v_scale, vs_f, cache.block_tables, slots, bs
            )
        k_new = paged_write_stacked(
            cache.k, k_fresh, cache.block_tables, slots, bs
        )
        v_new = paged_write_stacked(
            cache.v, v_fresh, cache.block_tables, slots, bs
        )
    else:
        kv_valid = new_kv_positions >= 0
        mask = make_causal_mask(positions, new_kv_positions, kv_valid)
        blk, off = logical_to_physical(cache.block_tables, slots, bs)

        def body(h, xs, ssm_in, moe_in):
            # Write-then-attend over the row-indirected logical view (same
            # values/slot order as a dense ring, so _block is reused
            # verbatim); then persist ONLY the fresh tokens back to the
            # pool — writes through sentinel table entries drop.
            if quant:
                bp, kp_l, vp_l, ksp_l, vsp_l = xs
                k_l = dequantize_kv(
                    gather_block_view(kp_l, cache.block_tables),
                    gather_block_view(ksp_l, cache.block_tables), dtype,
                )
                v_l = dequantize_kv(
                    gather_block_view(vp_l, cache.block_tables),
                    gather_block_view(vsp_l, cache.block_tables), dtype,
                )
            else:
                bp, kp_l, vp_l = xs
                k_l = gather_block_view(kp_l, cache.block_tables)
                v_l = gather_block_view(vp_l, cache.block_tables)
            h, _, _, k_f, v_f, ssm_out, counts = _block(
                cfg, bp, h, positions, k_l, v_l, new_kv_positions, slots,
                mask, mesh=mesh, sin_cos=sin_cos, ssm_in=ssm_in,
                pool_heads=cache.k.shape[3], moe_in=moe_in,
            )
            if quant:
                # Quantize only the fresh tokens (storage bit-stability —
                # same contract as the dense prefill branch).
                k8, ks_f = quantize_kv(k_f)
                v8, vs_f = quantize_kv(v_f)
                kp_l = kp_l.at[blk, off].set(k8, mode="drop")
                vp_l = vp_l.at[blk, off].set(v8, mode="drop")
                ksp_l = ksp_l.at[blk, off].set(ks_f, mode="drop")
                vsp_l = vsp_l.at[blk, off].set(vs_f, mode="drop")
                return h, (kp_l, vp_l, ksp_l, vsp_l), ssm_out, counts
            kp_l = kp_l.at[blk, off].set(
                k_f.astype(kp_l.dtype), mode="drop"
            )
            vp_l = vp_l.at[blk, off].set(
                v_f.astype(vp_l.dtype), mode="drop"
            )
            return h, (kp_l, vp_l), ssm_out, counts

        if quant:
            h, (k_new, v_new, ks_new, vs_new), state, moe_counts = _layer_scan(
                cfg, cache, lens, body, h,
                (params["blocks"], cache.k, cache.v, cache.k_scale,
                 cache.v_scale),
                linear=params.get("linear"), moe=moe,
            )
        else:
            ks_new, vs_new = None, None
            h, (k_new, v_new), state, moe_counts = _layer_scan(
                cfg, cache, lens, body, h,
                (params["blocks"], cache.k, cache.v),
                linear=params.get("linear"), moe=moe,
            )

    if aux is not None:
        aux["moe_counts"] = moe_counts
    logits = _head_out(cfg, params, h, gather_idx, last_only)
    ssm_new, conv_new = state if state is not None else (None, None)
    return logits, PagedKVCache(
        k=k_new, v=v_new, block_tables=cache.block_tables,
        positions=new_kv_positions, k_scale=ks_new, v_scale=vs_new,
        ssm=ssm_new, conv=conv_new,
    )


def _latent_value_cols(m) -> int:
    """Leading columns of a latent row that are its value: the latent itself
    (the rotary key and the padding follow), in whole lanes."""
    return min(-(-m.kv_lora_rank // 128) * 128, m.pool_dim)


def _kernel_heads(cfg: DecoderConfig, cache: PagedKVCache) -> tuple[int, int]:
    """``(query heads, KV heads)`` as a read of the pool sees them: the
    model's, or, where the pool pads its heads (``cfg.pool_kv_heads``: a
    head a query head), the pool's for both (``_block`` pads the queries)."""
    pool_heads = cache.k.shape[3]
    if pool_heads != cfg.n_kv_heads:
        return pool_heads, pool_heads
    return cfg.n_heads, pool_heads


def _blocks_held(cache: PagedKVCache) -> jax.Array:
    """``[B]``: a row's table columns up to its last live slot, where a walk
    of its blocks stops (not a count of live slots: a ring that wrapped, or
    a window that let early blocks go, leaves holes before the last)."""
    last = jnp.max(
        jnp.where(
            cache.positions >= 0,
            jnp.arange(cache.positions.shape[1], dtype=jnp.int32), -1,
        ),
        axis=1,
    )
    return last // cache.block_size + 1


def _make_kv_read(cfg, cache, q_pos0, q_lens, slot0, kv_pos_src):
    """``attn_read``'s ``kv.kernel`` as a ``(q, k_new, v_new, k_cache,
    v_cache, *, layer) -> attn`` callable for ``_block``: the decode step's
    (``q_lens`` all 1) and the mixed step's read of the stacked pools where
    they lie (ops/pallas_kv.py)."""
    import importlib

    from llmss_tpu.ops import pallas_kv

    interp = importlib.import_module(
        "llmss_tpu.ops.attention"
    ).pallas_interpret()
    n_blocks = _blocks_held(cache)

    def attn(q, k_new, v_new, k_c, v_c, *, layer):
        del k_c, v_c  # reads the stacked pools directly
        return pallas_kv.kv_paged_attention(
            q, cache.k, cache.v, k_new, v_new, q_pos0, q_lens, kv_pos_src,
            cache.block_tables, n_blocks, slot0, layer,
            ring_len=cache.max_len, scale=cfg.attn_scale,
            window=cfg.sliding_window, interpret=interp,
        )

    return attn


def attn_read(cfg: DecoderConfig, cache: PagedKVCache, mesh, chunk: int) -> str:
    """How a decode step (``chunk`` 1) or a mixed step of ``chunk`` tokens a
    row reads the paged pool, as its program is traced NOW: ``gather`` (the
    rows' logical views gathered, the XLA oracles), ``mla.kernel`` (a latent
    pool read in place, ops/pallas_mla.py), ``kv.kernel`` (a pool of keys
    and values read in place, each row's own blocks up to its length,
    ops/pallas_kv.py) or, for a model that selects what attention reads:
    ``dsa.kernel`` (both pools read in place under the selection as bits,
    live rows only, ops/pallas_dsa.py) and its XLA forms ``dsa.tokens`` (a
    decode step: the kept tokens read by token) and ``dsa.mask`` (a mixed
    step: every row's first query by token, the feeding rows through the
    mask form over gathered views); ops/sparse_attention.py. A decode step
    whose read bucket is at most ``topk`` slots reads as ``gather`` does.

    ``mla.kernel``, ``kv.kernel`` and ``dsa.kernel`` are chosen by
    ``dispatch_attention``'s own rule: one device, the pool in the compute
    dtype (an int8 pool keeps the XLA read), shapes inside the kernel's
    ``supports`` and compiled on a TPU, or forced (interpreted: the CPU
    tests); never under ``force == "xla"``."""
    import importlib

    from llmss_tpu.ops import pallas_kv, pallas_mla

    attention_mod = importlib.import_module("llmss_tpu.ops.attention")
    force = attention_mod.IMPL_OVERRIDE
    one_device = mesh is None or mesh.size == 1
    if cfg.indexer is not None:
        return _selected_read(cfg, cache, one_device, chunk, attention_mod)
    if force == "xla":
        return "gather"
    if cfg.mla is None:
        Hq, Hkv = _kernel_heads(cfg, cache)
        ok = (
            one_device
            and not cache.quantized
            and cache.k.dtype == cfg.compute_dtype
            and pallas_kv.supports(
                cache.block_size, Hq, Hkv, cfg.head_dim, chunk, cache.k.dtype
            )
        )
        if force == "pallas" and not ok:
            attention_mod.forced_pallas_miss(
                "shapes out of the pool read kernel's envelope "
                f"(devices={1 if mesh is None else mesh.size}, "
                f"bs={cache.block_size}, Hq={Hq}, Hkv={Hkv}, "
                f"D={cfg.head_dim}, chunk={chunk}, {cache.k.dtype})"
            )
        if ok and (force == "pallas" or not attention_mod.pallas_interpret()):
            return "kv.kernel"
        return "gather"
    ok = (
        one_device
        and cache.k.dtype == cfg.compute_dtype
        and pallas_mla.supports(
            cache.block_size, cfg.n_heads, cfg.mla.pool_dim, chunk,
            cache.k.dtype, _latent_value_cols(cfg.mla),
        )
    )
    if force == "pallas" and not ok:
        attention_mod.forced_pallas_miss(
            "shapes out of the latent read kernel's envelope "
            f"(bs={cache.block_size}, H={cfg.n_heads}, "
            f"W={cfg.mla.pool_dim}, chunk={chunk}, {cache.k.dtype})"
        )
    if ok and (force == "pallas" or not attention_mod.pallas_interpret()):
        return "mla.kernel"
    return "gather"


def feed_rows(cfg: DecoderConfig, cache: PagedKVCache, chunk: int,
              t_bucket: int | None = None) -> int | None:
    """How many rows may feed a prompt through ONE mixed step of ``chunk``
    tokens a row; None: as many as there are. For a model that selects what
    attention reads (``cfg.indexer``): what one turn of the mask form holds
    of ``[heads, chunk, context]`` float32 scores (``ops/sparse_attention.py:
    chunk_rows``). Under ``dsa.kernel`` no score leaves VMEM and the cap no
    longer rests on them; the NUMBER stays (the selection's ``[rows, chunk,
    context]`` passes still scale with it, and it sets the traffic a step
    carries: changing it is the scheduler's token cap, ROADMAP R9 (c))."""
    if cfg.indexer is None:
        return None
    from llmss_tpu.ops import sparse_attention as dsa

    B = cache.block_tables.shape[0]
    T = cache.max_len if t_bucket is None else min(t_bucket, cache.max_len)
    F = dsa.chunk_rows(B, cfg.n_heads, chunk, T + chunk)
    return None if F >= B else F


def state_update(cfg: DecoderConfig, cache: PagedKVCache, mesh, chunk: int) -> str:
    """How a decode step (``chunk`` 1) or a mixed step of ``chunk`` tokens a
    row updates the recurrent state, as its program is traced NOW:
    ``ssm.kernel`` (each layer of a Mamba-2 pool updated where it lies,
    ops/pallas_ssm.py), ``gdn.kernel`` (each linear-attention layer of a
    delta-rule pool likewise, the live rows only, ops/pallas_gdn.py) or
    ``xla`` (the layer sliced out, ``ops.ssm``'s ``ssm_step`` / ``ssd_scan``
    or ``ops.gdn``'s ``gdn_step`` / ``gdn_chunked``, and updated back; also
    what a config with no state says).

    A kernel is for a step in which batch row i IS pool row i (no admission
    view) on one device (heads are sharded under ``tp``); there,
    ``attn_read``'s rule: shapes inside the kernel's ``supports`` and
    compiled on a TPU, or forced (interpreted: the CPU tests); never under
    ``force == "xla"``."""
    import importlib

    from llmss_tpu.ops import pallas_gdn, pallas_ssm

    attention_mod = importlib.import_module("llmss_tpu.ops.attention")
    force = attention_mod.IMPL_OVERRIDE
    if (
        not cfg.has_state or cache.ssm is None or force == "xla"
        or cache.state_rows is not None
        or not (mesh is None or mesh.size == 1)
    ):
        return "xla"
    if cfg.linear_attn is not None:
        m, name = cfg.linear_attn, "gdn.kernel"
        shapes = dict(
            n_heads=m.n_v_heads, key_dim=m.key_head_dim,
            value_dim=m.value_head_dim,
        )
        ok = pallas_gdn.supports(**shapes, chunk=chunk, dtype=cache.ssm.dtype)
    else:
        m, name = cfg.ssm, "ssm.kernel"
        shapes = dict(
            n_heads=m.n_heads, head_dim=m.head_dim, d_state=m.d_state,
            n_groups=m.n_groups,
        )
        ok = pallas_ssm.supports(**shapes, chunk=chunk, dtype=cache.ssm.dtype)
    if force == "pallas" and not ok:
        attention_mod.forced_pallas_miss(
            "shapes out of the state update kernel's envelope "
            f"({shapes}, chunk={chunk}, {cache.ssm.dtype})"
        )
    if ok and (force == "pallas" or not attention_mod.pallas_interpret()):
        return name
    return "xla"


def _forward_latent(
    cfg: DecoderConfig,
    params: Params,
    input_ids: jax.Array,  # [B, S]
    positions: jax.Array,  # [B, S]
    cache: PagedKVCache,  # a latent pool: ``v`` is None
    slots: jax.Array,  # [B, S] LOGICAL slots
    *,
    q_lens: jax.Array | None = None,  # [B]: the call is a mixed step
    last_only: bool = False,
    gather_idx: jax.Array | None = None,
    kv_write_positions: jax.Array | None = None,
    mesh=None,
    t_bucket: int | None = None,
    aux: dict | None = None,
) -> tuple[jax.Array, PagedKVCache]:
    """The paged forwards of a model with latent attention (``cfg.mla``):
    prefill, the decode step (S == 1) and the mixed step (``q_lens`` set),
    under the callers' contract of ``_forward_paged`` / ``forward_ragged``.

    One discipline for all three: the layer scans close over the stale pool
    and READ it (each layer gathers its rows' blocks by layer and block in
    one gather, ``gather_block_view(layer=)``), every layer hands back its
    fresh latents, and ONE ``paged_write_stacked`` after the scans writes
    them. Decode and the mixed step attend over the stale view merged with
    the fresh latent in one softmax (``paged_decode_attention`` /
    ``ragged_paged_attention`` with keys and values the same pool); prefill
    writes its fresh latents into the gathered view (a temporary of the
    admitted rows, not the pool) and attends write-then-read through
    ``dispatch_attention``. All in the absorbed form (``_latent_attention``).

    Two kinds of layer: the leading dense stack (``params["lead"]``, layers
    ``[0, cfg.n_lead_layers)``) and the main stack run as two scans in order
    over the ONE pool, whose layer axis spans both. Tokens that are not real
    (padding, done rows: no recorded position, or a slot out of range) are
    routed nowhere; the expert layers' ``(pairs, experts_hit)``, summed, are
    left in ``aux["moe_counts"]``."""
    h = _embed_in(cfg, params, input_ids, positions, mesh)
    if kv_write_positions is None:
        kv_write_positions = positions
    new_kv_positions = write_positions(
        cache.positions, kv_write_positions, slots
    )
    B, S = input_ids.shape
    bs, MB = cache.block_size, cache.max_blocks
    # the pool seen with the one "head" the attention functions expect
    pool, tables = cache.k[:, :, :, None, :], cache.block_tables
    live = (kv_write_positions >= 0) & (slots < cache.max_len)
    sin_cos = sin_cos_tables(
        positions, cfg.mla.qk_rope_head_dim, cfg.rope_theta
    )
    nb = None
    if t_bucket is not None and t_bucket < cache.max_len:
        nb = min(-(-t_bucket // bs), MB)
    kv_pos_src = cache.positions[:, : (nb if nb is not None else MB) * bs]

    if (q_lens is not None or S == 1) and (
        attn_read(cfg, cache, mesh, S) == "mla.kernel"
    ):
        import importlib

        from llmss_tpu.ops import pallas_mla

        scope = "mla.decode"
        interp = importlib.import_module(
            "llmss_tpu.ops.attention"
        ).pallas_interpret()
        lens = q_lens if q_lens is not None else jnp.ones((B,), jnp.int32)
        held = _blocks_held(cache)

        def attend(layer, q, lat):
            # the pool as stored: no unit axis beside its minor dimension
            return pallas_mla.latent_paged_attention(
                q, cache.k, lat, positions[:, 0], lens, kv_pos_src, tables,
                held, slots[:, 0], layer, ring_len=cache.max_len,
                scale=cfg.attn_scale, v_dim=_latent_value_cols(cfg.mla),
                interpret=interp,
            )
    elif q_lens is not None:
        scope = "mla.decode"
        q_pos0, slot0 = positions[:, 0], slots[:, 0]
        cache_vis = ragged_cache_visibility(
            q_lens, kv_pos_src, slot0, cache.max_len
        )

        def attend(layer, q, lat):
            return ragged_paged_attention(
                q, pool, pool, lat, lat, q_pos0, q_lens, kv_pos_src, tables,
                slot0, cache.max_len, scale=cfg.attn_scale,
                cache_vis=cache_vis, n_blocks=nb, layer=layer,
            )
    elif S == 1:
        scope = "mla.decode"
        penalty = decode_mask_penalty(positions, kv_pos_src, slots, None)

        def attend(layer, q, lat):
            return paged_decode_attention(
                q, pool, pool, lat, lat, positions, kv_pos_src, tables, slots,
                scale=cfg.attn_scale, penalty=penalty, n_blocks=nb,
                layer=layer,
            )
    else:
        scope = "mla.prefill"
        mask = make_causal_mask(
            positions, new_kv_positions, new_kv_positions >= 0
        )
        b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]

        def attend(layer, q, lat):
            view = gather_block_view(pool, tables, layer=layer)
            view = view.at[b_idx, slots].set(lat.astype(view.dtype))
            return dispatch_attention(
                q, view, view, mask=mask, q_positions=positions,
                kv_positions=new_kv_positions, scale=cfg.attn_scale,
                mesh=mesh,
            )

    n_lead = cfg.n_lead_layers
    # The stacked experts stay out of the scan's xs: the scan would copy a
    # layer's slice out of the stack for the grouped matmul's kernel.
    experts = {
        k: v for k, v in params["blocks"].items() if k.startswith("experts_")
    }
    stack = {k: v for k, v in params["blocks"].items() if k not in experts}

    def body(h, xs):
        bp, layer = xs

        def attn(q, lat):
            with jax.named_scope(scope):
                return attend(layer, q, lat)

        h, latent, moe_counts = _latent_block(
            cfg, bp, h, positions, sin_cos, attn, live,
            (experts, layer - n_lead), mesh=mesh,
        )
        return h, (latent, moe_counts)

    layers = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    fresh, counts = [], None
    if n_lead:
        h, (lat, _) = jax.lax.scan(
            body, h, (params["lead"], layers[:n_lead])
        )
        fresh.append(lat)
    h, (lat, per_layer) = jax.lax.scan(body, h, (stack, layers[n_lead:]))
    fresh.append(lat)
    if per_layer is not None:
        counts = jnp.sum(per_layer, axis=0)
    if aux is not None:
        aux["moe_counts"] = counts
    pool = paged_write_stacked(
        cache.k, jnp.concatenate(fresh, axis=0)[:, :, :, 0], tables, slots, bs
    )
    logits = _head_out(cfg, params, h, gather_idx, last_only)
    return logits, cache._replace(k=pool, positions=new_kv_positions)


def _forward_selected(
    cfg: DecoderConfig,
    params: Params,
    input_ids: jax.Array,  # [B, S]
    positions: jax.Array,  # [B, S]
    cache: PagedKVCache,  # with ``idx``, the pool of indexer keys
    slots: jax.Array,  # [B, S] LOGICAL slots
    *,
    q_lens: jax.Array | None = None,  # [B]: the call is a mixed step
    last_only: bool = False,
    gather_idx: jax.Array | None = None,
    kv_write_positions: jax.Array | None = None,
    mesh=None,
    t_bucket: int | None = None,
    aux: dict | None = None,
) -> tuple[jax.Array, PagedKVCache]:
    """The paged forwards of a model that selects what attention reads
    (``cfg.indexer``, docs/sparse-attention.md): prefill, the decode step
    (S == 1) and the mixed step (``q_lens`` set), under the callers'
    contract of ``_forward_paged`` / ``forward_ragged``.

    One discipline for all three, the latent family's: the layer scan closes
    over the stale pools and READS them, every layer hands back its fresh
    keys, values and indexer keys, and one ``paged_write_stacked`` a pool
    after the scan writes them. The selection is made ONE way whatever reads
    (``decode_selection`` for a row's one query, ``chunk_selection`` a query
    position for the rows that feed: at most ``feed_rows`` of a mixed step's,
    every row of the prefill). Under ``attn_read``'s ``dsa.kernel`` the
    decode and mixed steps hand it as bits to one kernel call a layer
    (``_make_selected_read``); the XLA forms read the kept tokens by token
    (``sparse_decode_attention``) or mask gathered views
    (``sparse_chunk_attention``: also the prefill, a chunk as long as its
    bucket). A decode read bucket of at most ``topk`` slots drops nothing
    and is ``paged_decode_attention`` as every other family runs it.

    ``aux["dsa_counts"]`` int32 [4], over the step's live rows and all
    layers: cached and fresh positions the indexer scored for a row's LAST
    live query, positions that query kept, rows whose context was at most
    ``topk`` (nothing dropped), and the rows counted."""
    from llmss_tpu.ops import sparse_attention as dsa

    topk = cfg.indexer.topk
    h = _embed_in(cfg, params, input_ids, positions, mesh)
    if kv_write_positions is None:
        kv_write_positions = positions
    new_kv_positions = write_positions(
        cache.positions, kv_write_positions, slots
    )
    B, S = input_ids.shape
    bs, MB = cache.block_size, cache.max_blocks
    tables = cache.block_tables
    sin_cos = None
    if cfg.positions == "rotary":
        sin_cos = sin_cos_tables(
            positions, cfg.rotary_dim or cfg.head_dim, cfg.rope_theta,
            cfg.rope_freq_factors, cfg.rope_attn_factor,
        )
    nb = None
    if t_bucket is not None and t_bucket < cache.max_len:
        nb = min(-(-t_bucket // bs), MB)
    Tv = (nb if nb is not None else MB) * bs
    kv_pos_src = cache.positions[:, :Tv]
    # live positions a row: a mixed step's ``q_lens`` but for done rows
    lens = _ssm_lens(cache, kv_write_positions, slots)
    # the cached slots a row's queries may see at all (live, not pending)
    cache_vis = ragged_cache_visibility(
        lens, kv_pos_src, slots[:, 0], cache.max_len
    )

    # A mixed step of more rows than ``feed_rows`` gives (the scheduler
    # admits no more prompts at once) selects a query position only for the
    # rows that feed; every row's first query is selected as a decode step's.
    F = feed_rows(cfg, cache, S, t_bucket) if q_lens is not None else None
    feeding = None if F is None else jnp.nonzero(
        lens > 1, size=F, fill_value=B
    )[0]
    a_step = S == 1 or q_lens is not None  # not the prefill
    if S == 1 and q_lens is None and Tv <= topk:
        penalty = decode_mask_penalty(positions, kv_pos_src, slots, None)

        def attn(q, k_new, v_new, k_c, v_c, *, layer, index):
            del k_c, v_c, index  # every slot read is kept
            return paged_decode_attention(
                q, cache.k, cache.v, k_new, v_new, positions, kv_pos_src,
                tables, slots, scale=cfg.attn_scale, penalty=penalty,
                n_blocks=nb, layer=layer,
            )
    elif a_step and attn_read(cfg, cache, mesh, S) == "dsa.kernel":
        attn = _make_selected_read(
            cfg, cache, positions, slots, lens, kv_pos_src, cache_vis, nb,
            feeding, mesh,
        )
    elif S == 1 and q_lens is None:
        def attn(q, k_new, v_new, k_c, v_c, *, layer, index):
            del k_c, v_c  # reads the stacked pools directly
            qi, wi, ki = index
            with jax.named_scope("dsa.decode"):
                return dsa.sparse_decode_attention(
                    q, cache.k, cache.v, cache.idx, k_new, v_new, ki, qi,
                    wi, positions, kv_pos_src, tables, slots, layer,
                    topk=topk, scale=cfg.attn_scale, n_blocks=nb,
                )
    else:
        q_pos0 = positions[:, 0]

        def attn(q, k_new, v_new, k_c, v_c, *, layer, index):
            del k_c, v_c  # reads the stacked pools directly
            qi, wi, ki = index

            def mask_form(rows):
                """Rows ``rows`` (None: all) through their chunk. The rows'
                views are gathered from the pools by THEIR tables: a gather
                of rows out of all rows' views made layout assignment carry
                the pools slot-minor and copy them whole around every step
                (compiled for a described v5e, PR 46)."""
                r = (lambda a: a) if rows is None else (lambda a: a[rows])
                views = (
                    gather_block_view(pool, r(tables), nb, layer)
                    for pool in (cache.k, cache.v, cache.idx)
                )
                return dsa.sparse_chunk_attention(
                    r(q), *views, r(k_new), r(v_new), r(ki), r(qi), r(wi),
                    r(q_pos0), r(lens), r(kv_pos_src), r(cache_vis),
                    topk=topk, scale=cfg.attn_scale,
                )

            if feeding is None:  # the prefill: turn after turn
                with jax.named_scope("dsa.chunk"):
                    return mask_form(None)
            # every row's first query by token (a decoding row has no other;
            # a dense view of all rows was 31 of a step's 85 ms: PR 46) ...
            with jax.named_scope("dsa.decode"):
                first = dsa.sparse_decode_attention(
                    q[:, :1], cache.k, cache.v, cache.idx, k_new[:, :1],
                    v_new[:, :1], ki[:, :1], qi[:, :1], wi[:, :1],
                    positions[:, :1], kv_pos_src, tables, slots[:, :1],
                    layer, topk=topk, scale=cfg.attn_scale, n_blocks=nb,
                )
            # ... and the rows that feed through all of their chunk
            with jax.named_scope("dsa.chunk"):
                whole = mask_form(jnp.minimum(feeding, B - 1))
            out = jnp.zeros_like(q).at[:, :1].set(first)
            return out.at[feeding].set(whole, mode="drop")

    def body(h, xs, ssm_in, moe_in):
        bp, layer = xs
        out = {}
        h, k_f, v_f, _, counts = _block(
            cfg, bp, h, positions, None, None, kv_pos_src, slots, None,
            mesh=mesh, defer_write=True,
            attn_override=partial(attn, layer=layer), sin_cos=sin_cos,
            moe_in=moe_in, aux=out,
        )
        return h, (k_f, v_f, out["index_key"]), None, counts

    h, (k_f, v_f, ki_f), _, moe_counts = _layer_scan(
        cfg, cache, lens, body, h,
        (params["blocks"], jnp.arange(cfg.n_layers, dtype=jnp.int32)),
        moe=_moe_of(params, cache, kv_write_positions, slots),
    )
    if aux is not None:
        aux["moe_counts"] = moe_counts
        # what a row's last live query saw: the cached slots that are live,
        # not pending and not after it, and the step's own tokens up to it
        last = positions[:, 0] + lens - 1
        seen = lens + jnp.sum(
            cache_vis & (kv_pos_src <= last[:, None]), axis=1,
            dtype=jnp.int32,
        )
        live = lens > 0
        aux["dsa_counts"] = cfg.n_layers * jnp.stack([
            jnp.sum(jnp.where(live, seen, 0)),
            jnp.sum(jnp.where(live, jnp.minimum(seen, topk), 0)),
            jnp.sum(live & (seen <= topk)),
            jnp.sum(live),
        ]).astype(jnp.int32)

    write = partial(
        paged_write_stacked, block_tables=tables, slots=slots, block_size=bs
    )
    logits = _head_out(cfg, params, h, gather_idx, last_only)
    # the keys zero-padded to the pool's row (``IndexerConfig.pool_dim``)
    ki_f = jnp.pad(
        ki_f, [(0, 0)] * 3 + [(0, cache.idx.shape[-1] - ki_f.shape[-1])]
    )
    return logits, cache._replace(
        k=write(cache.k, k_f), v=write(cache.v, v_f),
        idx=write(cache.idx, ki_f), positions=new_kv_positions,
    )


def forward_ragged(
    cfg: DecoderConfig,
    params: Params,
    input_ids: jax.Array,  # [B, CB] — ragged chunks, q_lens live per row
    positions: jax.Array,  # [B, CB] — row's first query at positions[:, 0]
    cache: PagedKVCache,
    slots: jax.Array,  # [B, CB] LOGICAL slots; max_len marks dead columns
    q_lens: jax.Array,  # [B] int32 — 1 for decode rows, up to CB mid-prefill
    *,
    kv_write_positions: jax.Array | None = None,  # [B, CB]; -1 = no write
    mesh=None,
    t_bucket: int | None = None,
    aux: dict | None = None,
) -> tuple[jax.Array, PagedKVCache]:
    """Mixed prefill+decode forward over the paged pool: every row carries
    a ``CB``-token query chunk of which the first ``q_lens[b]`` are live —
    1 for rows mid-decode, more for rows streaming a prompt through
    chunked prefill. One dispatch serves both phases, so prefill compute
    is metered per step instead of monopolizing a dedicated (P, S)
    prefill program (ISSUE 10; "Ragged Paged Attention", PAPERS.md).

    Deferred-write structure exactly like the S == 1 decode branch of
    ``_forward_paged``: attention runs over the stale pool (ops/pallas_kv.py
    reading blocks in place, or per-row gathered logical views through the
    XLA oracle), and the chunk's fresh KV lands in one batched all-layer
    pool scatter after the scan. Logits gather at each row's
    last live chunk position (``q_lens - 1``) — for a prompt's final chunk
    that is the prefill sampling position, for a decode row it is the
    usual last-token gather. Padding columns (``>= q_lens``) write nowhere
    (slots carry ``max_len``, positions −1) and their hidden states are
    never gathered.
    """
    if cfg.mla is not None:
        return _forward_latent(
            cfg, params, input_ids, positions, cache, slots, q_lens=q_lens,
            gather_idx=q_lens - 1, kv_write_positions=kv_write_positions,
            mesh=mesh, t_bucket=t_bucket, aux=aux,
        )
    if cfg.indexer is not None:
        return _forward_selected(
            cfg, params, input_ids, positions, cache, slots, q_lens=q_lens,
            gather_idx=q_lens - 1, kv_write_positions=kv_write_positions,
            mesh=mesh, t_bucket=t_bucket, aux=aux,
        )
    dtype = cfg.compute_dtype
    del dtype  # same compute-dtype flow as _forward_paged via _block
    h = _embed_in(cfg, params, input_ids, positions, mesh)

    if kv_write_positions is None:
        kv_write_positions = positions
    new_kv_positions = write_positions(
        cache.positions, kv_write_positions, slots
    )

    B, S = input_ids.shape
    bs, MB = cache.block_size, cache.max_blocks
    quant = cache.quantized
    lens = _ssm_lens(cache, kv_write_positions, slots)

    sin_cos = None
    if cfg.positions == "rotary":
        sin_cos = sin_cos_tables(
            positions, cfg.rotary_dim or cfg.head_dim, cfg.rope_theta,
            cfg.rope_freq_factors, cfg.rope_attn_factor,
        )

    # Bucketed pool read, same caller contract as _forward_paged.
    nb = None
    if t_bucket is not None and t_bucket < cache.max_len:
        nb = min(-(-t_bucket // bs), MB)
    Tv = (nb if nb is not None else MB) * bs
    kv_pos_src = cache.positions[:, :Tv]

    q_pos0 = positions[:, 0]
    slot0 = slots[:, 0]

    # Same discipline as the S == 1 branch of _forward_paged: the scan
    # closes over the stacked pool and the layer is an index of the read.
    if attn_read(cfg, cache, mesh, S) == "kv.kernel":
        attn = _make_kv_read(cfg, cache, q_pos0, q_lens, slot0, kv_pos_src)
    else:
        # Hoist the query-invariant visibility out of the layer scan (the
        # per-query causal bound stays inside the oracle — it is chunk
        # structure, not a [B, T] penalty).
        cache_vis = ragged_cache_visibility(
            q_lens, kv_pos_src, slot0, cache.max_len
        )

        def attn(q, k_new, v_new, k_c, v_c, *, layer):
            del k_c, v_c  # reads the stacked pool directly
            return ragged_paged_attention(
                q, cache.k, cache.v, k_new, v_new, q_pos0, q_lens,
                kv_pos_src, cache.block_tables, slot0, cache.max_len,
                scale=cfg.attn_scale, window=cfg.sliding_window,
                cache_vis=cache_vis, k_scale_layer=cache.k_scale,
                v_scale_layer=cache.v_scale, n_blocks=nb, layer=layer,
            )

    def body(h, xs, ssm_in, moe_in):
        bp, layer = xs
        h, k_f, v_f, ssm_out, counts = _block(
            cfg, bp, h, positions, None, None, kv_pos_src, slots,
            None, mesh=mesh, defer_write=True,
            attn_override=partial(attn, layer=layer),
            sin_cos=sin_cos, ssm_in=ssm_in, pool_heads=cache.k.shape[3],
            moe_in=moe_in,
        )
        return h, (k_f, v_f), ssm_out, counts

    h, ys, state, moe_counts = _layer_scan(
        cfg, cache, lens, body, h,
        (params["blocks"], jnp.arange(cfg.n_kv_layers, dtype=jnp.int32)),
        linear=params.get("linear"),
        in_place=state_update(cfg, cache, mesh, S) != "xla",
        moe=_moe_of(params, cache, kv_write_positions, slots),
    )
    if aux is not None:
        aux["moe_counts"] = moe_counts

    ks_new, vs_new = cache.k_scale, cache.v_scale
    k_fresh, v_fresh = ys  # [L, B, CB, Hkv, D]
    if quant:
        k_fresh, ks_f = quantize_kv(k_fresh)
        v_fresh, vs_f = quantize_kv(v_fresh)
        ks_new = paged_write_stacked(
            cache.k_scale, ks_f, cache.block_tables, slots, bs
        )
        vs_new = paged_write_stacked(
            cache.v_scale, vs_f, cache.block_tables, slots, bs
        )
    k_new = paged_write_stacked(
        cache.k, k_fresh, cache.block_tables, slots, bs
    )
    v_new = paged_write_stacked(
        cache.v, v_fresh, cache.block_tables, slots, bs
    )

    logits = _head_out(cfg, params, h, q_lens - 1, False)
    ssm_new, conv_new = state if state is not None else (None, None)
    return logits, PagedKVCache(
        k=k_new, v=v_new, block_tables=cache.block_tables,
        positions=new_kv_positions, k_scale=ks_new, v_scale=vs_new,
        ssm=ssm_new, conv=conv_new,
    )


def _selected_read(cfg, cache, one_device, chunk, attention_mod) -> str:
    """``attn_read`` for a model that selects what attention reads:
    ``dsa.kernel`` by the rule of the other two kernels, else the XLA forms
    (a ``tp`` mesh, an int8 pool, the CPU, ``force == "xla"``)."""
    from llmss_tpu.ops import pallas_dsa

    force = attention_mod.IMPL_OVERRIDE
    ok = (
        force != "xla"
        and one_device
        and not cache.quantized
        and cache.k.dtype == cfg.compute_dtype
        and pallas_dsa.supports(
            cache.block_size, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            chunk, cache.k.dtype,
        )
    )
    if force == "pallas" and not ok:
        attention_mod.forced_pallas_miss(
            "shapes out of the selected read kernel's envelope "
            f"(bs={cache.block_size}, Hq={cfg.n_heads}, "
            f"Hkv={cfg.n_kv_heads}, D={cfg.head_dim}, chunk={chunk}, "
            f"{cache.k.dtype})"
        )
    if ok and (force == "pallas" or not attention_mod.pallas_interpret()):
        return "dsa.kernel"
    return "dsa.tokens" if chunk == 1 else "dsa.mask"


def index_read(cfg: DecoderConfig, cache: PagedKVCache, mesh, chunk: int) -> str:
    """How a decode step (``chunk`` 1) or a mixed step of ``chunk`` tokens a
    row makes the indexer's scores over the cached slots, as its program is
    traced NOW: ``none`` (no indexer), ``idx.kernel`` (the indexer's pool
    walked where it lies, the live rows' blocks only,
    ``ops/pallas_dsa.py: idx_paged_scores``) or ``gather`` (every row's view
    of the pool gathered and ``index_scores`` over it, XLA). The kernel goes
    with ``attn_read``'s ``dsa.kernel`` (its rule: one device, compiled on a
    TPU or forced) where the pool's shapes are inside its own ``supports``.
    Like ``attn_read`` it does not know of a decode read bucket of at most
    ``topk`` slots, which selects nothing and scores nothing."""
    from llmss_tpu.ops import pallas_dsa

    if cfg.indexer is None:
        return "none"
    if attn_read(cfg, cache, mesh, chunk) == "dsa.kernel" and (
        pallas_dsa.index_supports(
            cache.block_size, cfg.indexer.n_heads, cache.idx.shape[-1],
            chunk, cache.max_len, cache.idx.dtype,
        )
    ):
        return "idx.kernel"
    return "gather"


def _make_selected_read(
    cfg, cache, positions, slots, lens, kv_pos_src, cache_vis, nb, feeding,
    mesh,
):
    """``attn_read``'s ``dsa.kernel`` as a ``(q, k_new, v_new, k_cache,
    v_cache, *, layer, index) -> attn`` callable for ``_block``: the decode
    step's and the mixed step's read of the stacked pools where they lie
    under the layer's selection (ops/pallas_dsa.py). The selection is the XLA
    forms': ``decode_selection`` for every row's first query (a decoding row
    has no other), ``chunk_selection`` a query position for the rows
    ``feeding`` (None: every row), packed a bit a query; the kernel walks the
    live rows' blocks (``lens`` > 0) and reads nothing else of the pools.
    Under ``index_read``'s ``idx.kernel`` the scores both selections start
    from come from the same walk over the indexer's pool
    (``pallas_dsa.idx_paged_scores``: a feeding slot that holds no row walks
    nothing) and no view of that pool is gathered."""
    import importlib

    from llmss_tpu.ops import pallas_dsa
    from llmss_tpu.ops import sparse_attention as dsa

    interp = importlib.import_module(
        "llmss_tpu.ops.attention"
    ).pallas_interpret()
    topk, tables = cfg.indexer.topk, cache.block_tables
    B, S, Tv = *positions.shape, kv_pos_src.shape[1]
    n_blocks = _blocks_held(cache)
    some = feeding is not None
    r = (lambda a: a[jnp.minimum(feeding, a.shape[0] - 1)]) if some else (
        lambda a: a
    )
    walk = index_read(cfg, cache, mesh, S) == "idx.kernel"
    # a feeding slot that holds no row (``feeding`` == B) walks nothing
    fed_lens = jnp.where(feeding < B, r(lens), 0) if some else lens

    def attn(q, k_new, v_new, k_c, v_c, *, layer, index):
        del k_c, v_c  # reads the stacked pools directly
        qi, wi, ki = index

        def read(keep_c, keep_w):
            return pallas_dsa.dsa_paged_attention(
                q, cache.k, cache.v, k_new, v_new, keep_c, keep_w, lens,
                tables, n_blocks, layer, scale=cfg.attn_scale,
                interpret=interp,
            )

        def scores(qi, wi, lens, pick):
            """The rows' (``pick``: which) queries' scores over their cached
            slots by the walk, or None: the selection gathers and scores."""
            if not walk:
                return None
            return pallas_dsa.idx_paged_scores(
                qi, wi, cache.idx, lens, pick(tables), pick(n_blocks), layer,
                n_slots=Tv, interpret=interp,
            )

        if S == 1 or some:
            with jax.named_scope("dsa.decode"):
                first = dsa.decode_selection(
                    cache.idx, ki[:, :1], qi[:, :1], wi[:, :1],
                    positions[:, :1], kv_pos_src, tables, slots[:, :1],
                    layer, topk=topk, n_blocks=nb,
                    scores=scores(qi[:, :1], wi[:, :1], lens, lambda a: a),
                ).astype(jnp.int32)  # [B, Tv + 1]: bit 0 of a word
                keep_c = first[:, :Tv]
                keep_w = jnp.pad(first[:, Tv:], ((0, 0), (0, S - 1)))
                if S == 1:
                    return read(keep_c, keep_w)
        with jax.named_scope("dsa.chunk"):
            view = None if walk else gather_block_view(
                cache.idx, r(tables), nb, layer
            )
            words = pallas_dsa.pack_queries(dsa.chunk_selection(
                view, r(ki), r(qi), r(wi), r(positions[:, 0]), r(lens),
                r(kv_pos_src), r(cache_vis), topk=topk,
                scores=scores(r(qi), r(wi), fed_lens, r),
            ))  # [rows, Tv + S]
            if not some:
                return read(words[:, :Tv], words[:, Tv:])
            return read(
                keep_c.at[feeding].set(words[:, :Tv], mode="drop"),
                keep_w.at[feeding].set(words[:, Tv:], mode="drop"),
            )

    return attn


def attn_form(cfg: DecoderConfig, cache: PagedKVCache, mesh, chunk: int) -> str:
    """What ``attn_read``'s ``kv.kernel`` does with a landed chunk of the
    walk in a decode step (``chunk`` 1) or a mixed step, as its program is
    traced NOW: ``heads`` (all heads of a chunk in one product: a pool with
    a KV head a query head or nearly, whose head alone cannot fill a tile),
    ``head`` (a product a KV head) or ``none`` (another read). The rule is
    the kernel's own, on shapes alone (``ops/pallas_kv.py: attn_form``)."""
    from llmss_tpu.ops import pallas_kv

    if attn_read(cfg, cache, mesh, chunk) != "kv.kernel":
        return "none"
    Hq, Hkv = _kernel_heads(cfg, cache)
    return pallas_kv.attn_form(Hq, Hkv, chunk, cache.k.dtype)
