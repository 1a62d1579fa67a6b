"""Qwen3-Next forward pass (``model_type`` ``qwen3_next``) in plain float32
``jax.numpy``, given ONE CHIP'S SHARE of the experts where the configuration
states one. Written from the catalog's config keys
(``benchmark/configs/qwen3-next-80b-a3b-1chip.json``) and the published
implementation as remembered (no network here). It imports nothing of the
program; it reads the engine's parameter tree for the numbers only. The
contract is in benchmark/README.md.

The model, as published. Hidden 2,048; layer ``i`` is full attention where
``(i + 1) % full_attention_interval == 0`` (interval 4), else linear
attention; every layer is followed by the expert layer
(``decoder_sparse_step`` 1, ``mlp_only_layers`` []). Pre-norm, sequential
residual::

    h = h + mixer(norm(h));  h = h + experts(norm(h))

a final norm, an untied head. ``norm`` is RMSNorm with a ZERO-CENTRED scale,
``x / rms(x) * (1 + w)``, eps ``rms_norm_eps``, except the gated norm inside
the linear mixer, whose scale is plain.

*Linear attention (Gated DeltaNet)*, Hk key heads of Dk under Hv value heads
of Dv (16 x 128 under 32 x 128): from ``x``, ``q, k`` [Hk x Dk], ``v, z``
[Hv x Dv], ``b, a`` [Hv]. ``q, k, v`` concatenated (8,192 channels) through a
depthwise causal convolution of ``linear_conv_kernel_dim`` taps, no bias
(output t sees inputs t-K+1 .. t, zeros before the sequence), then SiLU.
``q, k`` L2-normalised a head (``x / sqrt(|x|^2 + 1e-6)``), ``q`` times
``Dk^-1/2``; each key head repeated for its value heads (value head ``j``
reads key head ``j // (Hv / Hk)``). ``beta = sigmoid(b)`` in (0, 1);
``g = -exp(A_log) softplus(a + dt_bias)`` a value head. With the state ``S``
[Dk, Dv] a value head, from zero, TOKEN BY TOKEN::

    S' = exp(g) S;  u = beta (v - S'^T k);  S = S' + k u^T;  o = S^T q

then ``o = RMSNorm_Dv(o) * w * SiLU(z)`` a head (plain scale) and the output
projection 4,096 -> 2,048.

*Full attention*, H heads of D on KV heads (16 on 2 of 256): ``q_proj``
gives ``[H, 2 D]``, a query and a gate a head; ``k, v`` ``[KV, D]``. RMSNorm
(zero-centred scale) over each head's D of ``q`` and of ``k``; rotary on the
first ``partial_rotary_factor x D`` dimensions (64), rotate-half form (feature
``i`` pairs with ``i + 32``), ``rope_theta``, no scaling; causal softmax at
``D^-1/2``, H / KV query heads a KV head; the heads' output times
``sigmoid(gate)``, then ``o_proj``.

*Experts*: ``p = softmax(x W_r)`` over ALL the model's experts (512) in
float32; the ``num_experts_per_tok`` (10) largest; weights ``p_j / sum of the
chosen p`` (``norm_topk_prob``); ``y = sum_j w_j SwiGLU_j(x)`` at width
``moe_intermediate_size``; plus ``sigmoid(x . w_s) * SwiGLU_shared(x)`` at
``shared_expert_intermediate_size``. No selection bias, no scaling factor.

THE SHARE. ``num_experts`` counts the experts HELD here; where the file has
``expert_parallel`` (``num_experts`` the model's, ``chips`` sharing a layer,
``chip`` this one's index) the router still scores all of the model's
experts and chooses its ten among them, and ``y`` sums over those of the ten
that lie in this chip's range ``[chip x held, (chip + 1) x held)``: what the
experts held elsewhere would have added is left out, here as in the program,
and that partial result goes on to the next layer. Without the key every
expert is held and the layer is the whole published layer
(tests/test_qwen3_next.py adds the four shares up to it).

Departures, all under ``assumed`` in the configuration file: the stored norm
scales are ``1 + w`` already (the program's loader folds the 1 in; the seeded
weights are drawn as ``1 + N(0, 0.02)``), so ``_rms`` here multiplies by the
stored scale; the router reads the normed input in float32 (the program reads
it before its rounding to bfloat16, PR 38's departure); the published model's
multi-token-prediction module is not part of the forward. The recurrence is a
plain ``lax.scan`` over tokens: no chunked form, no cache.

The engine's tree: ``blocks`` stacks the attention layers and ``linear`` the
linear-attention layers (q and k of an attention layer stored [out, in] and
the router [experts, in], every other matrix [in, out]; ``gdn_qkv`` is q, k,
v side by side and ``gdn_conv`` [K, C] their convolution, ``gdn_ab`` a beside
b, ``gdn_g`` z), ``experts`` the held experts of ALL layers by the layer's
absolute index; one layer at a time is upcast.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
LINEAR, FULL = "linear_attention", "full_attention"


def _kinds(hf: dict) -> list[str]:
    every = hf.get("full_attention_interval", 4)
    return hf.get("layer_types") or [
        FULL if (i + 1) % every == 0 else LINEAR
        for i in range(hf["num_hidden_layers"])
    ]


def _share(hf: dict) -> tuple[int, int, int]:
    """``(experts the router scores, first held here, held here)``."""
    held, ep = hf["num_experts"], hf.get("expert_parallel")
    if ep is None:
        return held, 0, held
    return ep["num_experts"], ep["chip"] * held, held


def _sizes(hf: dict) -> dict:
    E = hf["hidden_size"]
    Hk, Hv = hf["linear_num_key_heads"], hf["linear_num_value_heads"]
    Dk, Dv = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    H, KV, D = (hf["num_attention_heads"], hf["num_key_value_heads"],
                hf["head_dim"])
    conv, K = 2 * Hk * Dk + Hv * Dv, hf["linear_conv_kernel_dim"]
    routed, _, _ = _share(hf)
    return {
        "Hk": Hk, "Hv": Hv, "Dk": Dk, "Dv": Dv, "key": Hk * Dk,
        "value": Hv * Dv, "conv": conv, "K": K,
        # q, k, v, z, then b and a; the output projection
        "lin": E * (conv + Hv * Dv + 2 * Hv) + Hv * Dv * E,
        # the convolution, A_log, dt_bias, the gated norm; the block norms
        "lin_small": K * conv + 2 * Hv + Dv + 2 * E,
        "full": E * (2 * H * D + 2 * KV * D) + H * D * E,
        "full_small": 2 * D + 2 * E,  # the QK-norms; the block norms
        "router": routed * E,
        "shared": 3 * E * hf["shared_expert_intermediate_size"] + E,
        "expert": 3 * E * hf["moe_intermediate_size"],
    }


def dims(hf: dict) -> dict:
    z = _sizes(hf)
    E, V = hf["hidden_size"], hf["vocab_size"]
    kinds = _kinds(hf)
    n_lin, n_full = kinds.count(LINEAR), kinds.count(FULL)
    routed, _, held = _share(hf)
    outside = z["router"] + z["shared"]
    # what ONE token is multiplied with: its mixer, the router, the shared
    # expert and the part of its chosen experts that is held here
    # (num_experts_per_tok x held / routed of them, on average)
    chosen = hf["num_experts_per_tok"] * held / routed * z["expert"]
    return {
        "layers": n_lin + n_full, "kv_layers": n_full, "hidden": E,
        "heads": hf["num_attention_heads"],
        "kv_heads": hf["num_key_value_heads"], "head_dim": hf["head_dim"],
        "inner": hf["moe_intermediate_size"], "vocab": V,
        "matmul_params": int(
            n_lin * z["lin"] + n_full * z["full"]
            + (n_lin + n_full) * (outside + chosen) + E * V),
        # held here AND read every step: every held expert of every layer
        # (a mixed step of this cell's 500 tokens hits all of them), the
        # final norm and the head's slice; the embedding is gathered by row
        "total_params": (
            n_lin * (z["lin"] + z["lin_small"])
            + n_full * (z["full"] + z["full_small"])
            + (n_lin + n_full) * (outside + held * z["expert"]) + E + E * V),
        # float32 state and a (K-1)-step window of bfloat16 inputs, a
        # linear-attention layer
        "state_bytes_per_row": n_lin * (
            z["Hv"] * z["Dk"] * z["Dv"] * 4 + (z["K"] - 1) * z["conv"] * 2),
    }


def _rms(x, scale, eps):
    """``scale`` is the stored one: ``1 + w`` for a zero-centred norm."""
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale.astype(F32)


def _swiglu(x, gate, up, down):
    f = lambda a: a.astype(F32)
    return (jax.nn.silu(x @ f(gate)) * (x @ f(up))) @ f(down)


def _rope(x, theta: float, n: int):
    """x [B, T, H, D]: the first ``n`` features of a head rotated by the
    position along T, feature i with feature i + n / 2."""
    T, half = x.shape[1], n // 2
    inv = 1.0 / (theta ** (jnp.arange(0, n, 2, dtype=F32) / n))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]  # [T, n/2]
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:n]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., n:]], -1)


def _attention(hf, lp, x):
    B, T, _ = x.shape
    H, KV, D = (hf["num_attention_heads"], hf["num_key_value_heads"],
                hf["head_dim"])
    eps, theta = hf["rms_norm_eps"], float(hf["rope_theta"])
    n_rot = int(D * hf["partial_rotary_factor"])
    f = lambda a: a.astype(F32)
    qg = (x @ f(lp["q"].w).T).reshape(B, T, H, 2 * D)
    q, gate = qg[..., :D], qg[..., D:]
    k = (x @ f(lp["k"].w).T).reshape(B, T, KV, D)
    v = (x @ f(lp["v"].w)).reshape(B, T, KV, D)
    q = _rope(_rms(q, lp["q_norm"].scale, eps), theta, n_rot)
    k = _rope(_rms(k, lp["k_norm"].scale, eps), theta, n_rot)
    k, v = (jnp.repeat(a, H // KV, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(D))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    a = a * jax.nn.sigmoid(gate)
    return a.reshape(B, T, H * D) @ f(lp["o"].w)


def _delta_net(hf, lp, x):
    B, T, _ = x.shape
    z = _sizes(hf)
    Hk, Hv, Dk, Dv, K = z["Hk"], z["Hv"], z["Dk"], z["Dv"], z["K"]
    f = lambda a: a.astype(F32)
    w = f(lp["gdn_conv"].w)  # [K, C]: the last tap multiplies the current input
    padded = jnp.pad(x @ f(lp["gdn_qkv"].w), [(0, 0), (K - 1, 0), (0, 0)])
    qkv = jax.nn.silu(sum(padded[:, t:t + T] * w[t] for t in range(K)))
    q, k, v = jnp.split(qkv, [z["key"], 2 * z["key"]], axis=-1)
    unit = lambda a: a / jnp.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)
    q = unit(q.reshape(B, T, Hk, Dk)) * Dk ** -0.5
    k = unit(k.reshape(B, T, Hk, Dk))
    q, k = (jnp.repeat(a, Hv // Hk, axis=2) for a in (q, k))
    v = v.reshape(B, T, Hv, Dv)
    a, b = jnp.split(x @ f(lp["gdn_ab"].w), 2, axis=-1)  # [B, T, Hv] each
    beta = jax.nn.sigmoid(b)
    alpha = jnp.exp(-jnp.exp(f(lp["gdn_A_log"]))
                    * jax.nn.softplus(a + f(lp["gdn_dt_bias"])))

    def step(S, inp):  # S [B, Hv, Dk, Dv]
        q_t, k_t, v_t, beta_t, alpha_t = inp
        S = alpha_t[..., None, None] * S
        u = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    t_first = lambda arr: jnp.moveaxis(arr, 1, 0)
    _, o = jax.lax.scan(
        step, jnp.zeros((B, Hv, Dk, Dv), F32),
        tuple(t_first(arr) for arr in (q, k, v, beta, alpha)),
    )
    o = _rms(jnp.moveaxis(o, 0, 1), lp["gdn_norm"].scale, hf["rms_norm_eps"])
    gate = jax.nn.silu(x @ f(lp["gdn_g"].w)).reshape(B, T, Hv, Dv)
    return (o * gate).reshape(B, T, Hv * Dv) @ f(lp["gdn_o"].w)


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
    shared = _swiglu(
        x, lp["shared_gate"].w, lp["shared_up"].w, lp["shared_down"].w)
    return y + jax.nn.sigmoid(x @ lp["shared_sig"].w.astype(F32)) * shared


def layer(hf: dict, kind: str, lp, h):
    eps = hf["rms_norm_eps"]
    mixer = _delta_net if kind == LINEAR else _attention
    h = h + mixer(hf, lp, _rms(h, lp["ln1"].scale, eps))
    return h + _experts(hf, lp, _rms(h, lp["ln2"].scale, eps))


def layers(hf: dict, params):
    """The two kinds in the published pattern's order, each layer from its
    own kind's stack by its index within the kind, with its held experts
    from the one stack of all layers by its absolute index."""
    seen = {LINEAR: 0, FULL: 0}
    stacks = {LINEAR: params["linear"], FULL: params["blocks"]}
    for l, kind in enumerate(_kinds(hf)):
        i = seen[kind]
        seen[kind] += 1
        yield kind, {
            **jax.tree.map(lambda a: a[i], stacks[kind]),
            **jax.tree.map(lambda a: a[l], params["experts"]),
        }


def control(params):
    """The negative control's one fault: the shared expert's gate lost
    (``mlp.shared_expert_gate`` at zero, as a loader that fills a leaf it
    cannot find with zeros would leave it), so the shared expert is halved
    for every token where the model gates it token by token. Every branch
    still runs, in every layer of both kinds."""
    def lost(stack):
        sig = stack["shared_sig"]
        return {**stack, "shared_sig": sig._replace(w=sig.w * 0)}

    return "shared_expert_gate_lost", {
        **params, "blocks": lost(params["blocks"]),
        "linear": lost(params["linear"]),
    }


def embed(hf: dict, params, ids):
    return params["wte"][ids].astype(F32)


def head(hf: dict, params, h):
    x = _rms(h, params["ln_f"].scale, hf["rms_norm_eps"])
    return x @ params["head"].w.astype(F32)
