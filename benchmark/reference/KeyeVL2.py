"""Keye-VL-2.0's language model (``model_type`` ``KeyeVL2``) in plain float32
``jax.numpy``, given ONE CHIP'S SHARE of the experts where the configuration
states one. Written from the catalog's config keys
(``benchmark/configs/keye-vl-2.0-30b-a3b-1chip.json``), DeepSeek-V3.2's
published description of its sparse attention (its equations (1) and (2)) and
the Qwen3-MoE backbone as remembered (no network here). It imports nothing of
the program; it reads the engine's parameter tree for the numbers only. No
cache, no batching of rows, no kernels. The contract is in
benchmark/README.md.

The model, as published. Hidden 2,048; 48 layers of ONE kind; pre-norm,
sequential residual::

    h = h + attn(norm(h));  h = h + experts(norm(h))

RMSNorm ``x / rms(x) * w``, eps ``rms_norm_eps`` (1e-6); a final norm; an
untied head of 151,936.

*Attention*: ``q`` [32 heads x 128], ``k, v`` [4 x 128], no bias; RMSNorm over
each head's 128 of ``q`` and of ``k`` (the family's convention: the backbone's
numbers are Qwen3-30B-A3B's, whose heads are normed so; the catalog has no key
for it - ASSUMED); rotary over all 128 dimensions in the rotate-half form
(feature ``i`` pairs with ``i + 64``), theta ``rope_theta`` (1e7), no scaling;
8 query heads a KV head; softmax at ``128^-1/2`` over the SELECTED positions;
``o_proj`` 4,096 -> 2,048.

*Indexer* (``sa_config``: 16 heads of 64, one key head, ``topk`` 2048), from
the layer's normed input ``x_t``::

    qI_t = x_t W_qI  [16 x 64];   kI_t = LayerNorm(x_t W_kI)  [64]
    w_t  = x_t W_w   [16]  *  16^-1/2  *  64^-1/2
    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s]),   s <= t

``S_t`` is the ``min(t + 1, topk)`` positions with the largest ``I[t, :]``, of
equal scores the EARLIER position first; every head of the layer attends over
``S_t`` and nothing else. A query whose context is at most ``topk`` attends
over all of it: dense causal attention.

*Experts*: ``p = softmax(x W_r)`` over ALL the model's experts (128) in
float32; the ``num_experts_per_tok`` (8) largest; weights ``p_j / sum of the
chosen p`` (``norm_topk_prob``); ``y = sum_j w_j SwiGLU_j(x)`` at width
``moe_intermediate_size`` (768). No shared expert, no bias, no factor.
(``intermediate_size`` 6,144 is unused: ``mlp_only_layers`` [] and
``decoder_sparse_step`` 1 make every layer sparse.)

THE SHARE. ``num_experts`` counts the experts HELD here; where the file has
``expert_parallel`` (``num_experts`` the model's, ``chips`` sharing a layer,
``chip`` this one's index) the router still scores all of the model's experts
and chooses its eight among them, and ``y`` sums over those of the eight that
lie in this chip's range: what the experts held elsewhere would have added is
left out, here as in the program, and that partial result goes on to the next
layer (tests/test_keye_vl2.py adds the four shares up to the uncut layer).

Departures and what the config does not settle, all under ``assumed`` in the
configuration file: the indexer's query is projected from the hidden state
(the model has no query low-rank to project from); the LayerNorm on its key
(scale and bias, eps 1e-6) and the two scale factors are DeepSeek's; NO rotary
on the indexer (DeepSeek rotates half of its 128; ``mrope_section`` sums to 64
pairs, which fits the main heads' 128 and not the indexer's 64, and the config
gives the indexer no rotary width); ``q_chunk_size`` and ``kv_chunk_size`` 512
are read as the tile sizes in which the published code computes the scores,
which change no value; DeepSeek's Hadamard rotation and FP8 rounding of the
indexer are left out (that implementation's arithmetic, not the mathematics);
the indexer and the router run in float32 from the normed input before any
rounding (the program does the same whatever its compute type); the stored
norm scales are the scales themselves (seeded as ``1 + N(0, 0.02)``); the
vision tower is not part of the forward (text alone: the three components of
``mrope_section`` [16, 24, 24] are equal and the rotary is the ordinary one).

Sizes: the scores of a block of ``QUERIES`` query positions against the whole
sequence are formed at a time (a ``[32, 4097, 4097]`` float32 tensor a row is
2.1 GB; a block of 256 is 134 MB), rows one after another.

The engine's tree: ``blocks`` stacks the layers (``q`` and ``k`` stored [out,
in] and the router [experts, in], every other matrix [in, out]; ``idx_q``,
``idx_k``, ``idx_w`` the indexer's projections and ``idx_k_norm`` its
LayerNorm), ``experts`` the held experts of all layers; one layer at a time is
upcast.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
KIND = "sparse_attention"
#: query positions whose scores are formed at a time
QUERIES = 256


def _share(hf: dict) -> tuple[int, int, int]:
    """``(experts the router scores, first held here, held here)``."""
    held, ep = hf["num_experts"], hf.get("expert_parallel")
    if ep is None:
        return held, 0, held
    return ep["num_experts"], ep["chip"] * held, held


def _sizes(hf: dict) -> dict:
    E, sa = hf["hidden_size"], hf["sa_config"]
    H, KV, D = (hf["num_attention_heads"], hf["num_key_value_heads"],
                hf["head_dim"])
    Hi, Di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    routed, _, _ = _share(hf)
    return {
        "attn": E * (H * D + 2 * KV * D) + H * D * E,
        "indexer": E * (Hi * Di + Di + Hi),
        # the QK-norms, the indexer's LayerNorm, the block norms
        "small": 2 * D + 2 * Di + 2 * E,
        "router": routed * E,
        "expert": 3 * E * hf["moe_intermediate_size"],
    }


def dims(hf: dict) -> dict:
    """What ``lib/costs.py`` prices. ``kv_layers`` is 0, and that is the
    point: ``decode_step_floor_s`` charges every cached key and value of the
    context (2 KB a token and layer here) to a step of every layer it is told
    holds keys and values, and a correct step of this model need NOT read
    them: it must read 256 B of indexer key a cached token and the keys and
    values of at most ``topk`` selected ones. A floor an honest kernel can
    beat is no floor. With no layer whose whole context a step must read, the
    floor is the held parameters and the operations outside attention, which
    every step does pay: it can only read low (the cell's file has both
    reckonings; the sparse term for ``costs.py`` is queued in PERF.md section
    7)."""
    z = _sizes(hf)
    E, V, L = hf["hidden_size"], hf["vocab_size"], hf["num_hidden_layers"]
    routed, _, held = _share(hf)
    per_layer = z["attn"] + z["indexer"] + z["router"]
    chosen = hf["num_experts_per_tok"] * held / routed * z["expert"]
    return {
        "layers": L, "kv_layers": 0, "hidden": E,
        "heads": hf["num_attention_heads"],
        "kv_heads": hf["num_key_value_heads"], "head_dim": hf["head_dim"],
        "inner": hf["moe_intermediate_size"], "vocab": V,
        "matmul_params": int(L * (per_layer + chosen) + E * V),
        # held here AND read every step: every held expert of every layer,
        # the final norm and the head's slice; the embedding is gathered
        "total_params": (
            L * (per_layer + z["small"] + held * z["expert"]) + E + E * V),
    }


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale.astype(F32)


def _rope(x, theta: float):
    """x [B, T, H, D]: every feature of a head rotated by the position along
    T, feature i with feature i + D / 2."""
    T, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]  # [T, D/2]
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    a, b = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _selection(hf, lp, x, t0, n):
    """bool [B, n, T]: which positions each of the queries ``[t0, t0 + n)``
    attends over. Every position ``s <= t`` is scored; the ``topk`` best are
    kept, found by RANK: a stable sort of the scores, descending, puts equal
    scores in the order of their positions."""
    sa = hf["sa_config"]
    Hi, Di, topk = sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]
    B, T, _ = x.shape
    f = lambda a: a.astype(F32)
    k = x @ f(lp["idx_k"].w)  # [B, T, Di]: one key a position
    mu = k.mean(-1, keepdims=True)
    k = (k - mu) * jax.lax.rsqrt(((k - mu) ** 2).mean(-1, keepdims=True) + 1e-6)
    k = k * f(lp["idx_k_norm"].scale) + f(lp["idx_k_norm"].bias)
    xq = jax.lax.dynamic_slice_in_dim(x, t0, n, axis=1)
    q = (xq @ f(lp["idx_q"].w)).reshape(B, n, Hi, Di)
    w = (xq @ f(lp["idx_w"].w)) * (Hi ** -0.5 * Di ** -0.5)
    score = (jax.nn.relu(jnp.einsum("bqhd,bsd->bqhs", q, k))
             * w[..., None]).sum(2)  # [B, n, T]
    t = t0 + jnp.arange(n)
    causal = jnp.arange(T)[None, :] <= t[:, None]
    score = jnp.where(causal[None], score, -jnp.inf)
    order = jnp.argsort(-score, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return (rank < topk) & causal[None]


def _attention(hf, lp, x):
    B, T, _ = x.shape
    H, KV, D = (hf["num_attention_heads"], hf["num_key_value_heads"],
                hf["head_dim"])
    eps, theta = hf["rms_norm_eps"], float(hf["rope_theta"])
    f = lambda a: a.astype(F32)
    q = (x @ f(lp["q"].w).T).reshape(B, T, H, D)
    k = (x @ f(lp["k"].w).T).reshape(B, T, KV, D)
    v = (x @ f(lp["v"].w)).reshape(B, T, KV, D)
    q = _rope(_rms(q, lp["q_norm"].scale, eps), theta)
    k = _rope(_rms(k, lp["k_norm"].scale, eps), theta)
    k, v = (jnp.repeat(a, H // KV, axis=2) for a in (k, v))
    n = min(QUERIES, T)
    # blocks of n queries, the last one moved back to end at T (its first
    # rows are then computed twice and written twice, the same values)
    starts = jnp.asarray([min(t0, T - n) for t0 in range(0, T, n)])

    def one(i, a):
        t0 = starts[i]
        keep = _selection(hf, lp, x, t0, n)  # [B, n, T]
        qb = jax.lax.dynamic_slice_in_dim(q, t0, n, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(F32(D))
        s = jnp.where(keep[:, None], s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        return jax.lax.dynamic_update_slice_in_dim(a, o, t0, axis=1)

    a = jax.lax.fori_loop(
        0, len(starts), one, jnp.zeros((B, T, H, D), F32))
    return a.reshape(B, T, H * D) @ f(lp["o"].w)


def _swiglu(x, gate, up, down):
    f = lambda a: a.astype(F32)
    return (jax.nn.silu(x @ f(gate)) * (x @ f(up))) @ f(down)


def _experts(hf, lp, x):
    """The router over all the model's experts, then every HELD expert in
    turn over every token, weighted (0 where the token did not choose it)."""
    routed, first, held = _share(hf)
    p = jax.nn.softmax(x @ lp["router"].w.astype(F32).T, -1)  # [B, T, routed]
    w, chosen = jax.lax.top_k(p, hf["num_experts_per_tok"])
    if hf["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    dense_w = (jax.nn.one_hot(chosen, routed, dtype=F32) * w[..., None]).sum(-2)
    mine = dense_w[..., first:first + held]

    def one(y, e):
        gate, up, down, w_e = e
        return y + w_e[..., None] * _swiglu(x, gate, up, down), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (lp["experts_gate"], lp["experts_up"], lp["experts_down"],
         jnp.moveaxis(mine, -1, 0)),
    )
    return y


def layer(hf: dict, kind: str, lp, h):
    eps = hf["rms_norm_eps"]
    # one row at a time: a row's scores are what is large
    h = h + jax.lax.map(
        lambda x: _attention(hf, lp, x[None])[0],
        _rms(h, lp["ln1"].scale, eps),
    )
    return h + _experts(hf, lp, _rms(h, lp["ln2"].scale, eps))


def layers(hf: dict, params):
    for l in range(hf["num_hidden_layers"]):
        yield KIND, {
            **jax.tree.map(lambda a: a[l], params["blocks"]),
            **jax.tree.map(lambda a: a[l], params["experts"]),
        }


def control(params):
    """The negative control's one fault: the indexer's key projection lost
    (``self_attn.indexer.wk`` at zero, as a loader that skips ``indexer.*``
    and fills what it cannot find with zeros would leave it). Every
    position's key is then the LayerNorm's bias, every score of a query ties,
    and the selection falls to the FIRST ``topk`` positions of its context: a
    late position no longer sees what it chose, nor itself."""
    blocks = params["blocks"]
    k = blocks["idx_k"]
    return "indexer_key_lost", {
        **params, "blocks": {**blocks, "idx_k": k._replace(w=k.w * 0)},
    }


def embed(hf: dict, params, ids):
    return params["wte"][ids].astype(F32)


def head(hf: dict, params, h):
    x = _rms(h, params["ln_f"].scale, hf["rms_norm_eps"])
    return x @ params["head"].w.astype(F32)
