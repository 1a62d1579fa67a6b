"""Olmo-Hybrid forward pass in plain float32 ``jax.numpy``.

Written from the catalog's config keys (``benchmark/configs/
olmo-hybrid-7b-1chip.json``) and the published descriptions as remembered (no
network here): Gated DeltaNet (Yang, Kautz, Hatamizadeh, "Gated Delta
Networks", 2024) as flash-linear-attention's ``GatedDeltaNet`` layer
parameterises it, and Olmo 2's block. Token embedding; ``num_hidden_layers``
blocks whose kind ``layer_types`` names, both kinds

    h = h + RMSNorm(mixer(h))          no pre-norm: the "reordered norm"
    h = h + RMSNorm(SwiGLU(h))

then a final RMSNorm and an untied head.

``linear_attention`` (per token, x the layer's input, H heads of Dk keys and
Dv values): ``q~ = W_q x``, ``k~ = W_k x``, ``v~ = W_v x``; each through a
depthwise causal convolution of ``linear_conv_kernel_dim`` taps, no bias
(output t sees inputs t-K+1 .. t, zeros before the sequence), then SiLU;
``q = q / sqrt(|q|^2 + 1e-6) * Dk^-1/2``, ``k = k / sqrt(|k|^2 + 1e-6)`` a
head; ``beta = 2 sigmoid(W_b x)`` (the 2: ``linear_allow_neg_eigval``);
``g = -exp(A_log) softplus(W_a x + dt_bias)``; with the state ``S`` [Dk, Dv] a
head, from zero, TOKEN BY TOKEN:

    S' = exp(g) S;  u = beta (v - S'^T k);  S = S' + k u^T;  o = S^T q

then ``o = RMSNorm_Dv(o) * w * SiLU(W_g x)`` a head and ``y = W_o o``.

``full_attention``: H heads of ``hidden / H``, ``q = RMSNorm(W_q x)`` and
``k = RMSNorm(W_k x)`` over the WHOLE projection (Olmo 2), causal
softmax(q k^T / sqrt(head)) v, NO rotary embedding (``rope_theta`` null).

Departures and guesses, all under ``assumed`` in the configuration file: the
norm placement, the QK-norm and the absence of a rotary embedding are the
family's convention, not keys of the config; the L2 norm's epsilon (1e-6
inside the root) and the gated norm's form (norm, then gate) are
flash-linear-attention's defaults.

The recurrence is a plain ``lax.scan`` over tokens: no chunked form, no
cache, and nothing imported from the program. Reads the engine's parameter
tree only for the numbers: ``blocks`` stacks the attention layers and
``linear`` the linear-attention layers (q and k of an attention layer stored
[out, in], every other matrix [in, out]; ``gdn_qkv`` is W_q, W_k, W_v side by
side and ``gdn_conv`` [K, C] their three convolutions, ``gdn_ab`` W_a beside
W_b), and upcasts one layer at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
LINEAR, FULL = "linear_attention", "full_attention"


def _sizes(hf: dict) -> dict:
    H = hf["linear_num_key_heads"]
    Dk, Dv = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    return {"H": H, "Dk": Dk, "Dv": Dv, "key": H * Dk, "value": H * Dv,
            "conv": 2 * H * Dk + H * Dv, "K": hf["linear_conv_kernel_dim"]}


def dims(hf: dict) -> dict:
    E, V, I = hf["hidden_size"], hf["vocab_size"], hf["intermediate_size"]
    Hq = hf["num_attention_heads"]
    KV, D = hf["num_key_value_heads"], hf["hidden_size"] // Hq
    z = _sizes(hf)
    n_lin = hf["layer_types"].count(LINEAR)
    n_full = hf["layer_types"].count(FULL)
    mlp = 3 * E * I
    full = E * (Hq * D + 2 * KV * D) + Hq * D * E
    lin = E * (z["conv"] + 2 * z["H"] + z["value"]) + z["value"] * E
    # what is not a matrix: the two block norms, then the QK-norms, or the
    # convolutions, A_log, dt_bias and the gated norm's scale
    full_small = 2 * E + Hq * D + KV * D
    lin_small = 2 * E + z["K"] * z["conv"] + 2 * z["H"] + z["Dv"]
    return {
        "layers": n_lin + n_full, "kv_layers": n_full, "hidden": E,
        "heads": Hq, "kv_heads": KV, "head_dim": D, "inner": I, "vocab": V,
        "matmul_params": n_full * (full + mlp) + n_lin * (lin + mlp) + E * V,
        # held here AND read every step: the layers, the final norm and the
        # untied head; the embedding table is gathered by row (as
        # reference/falcon_h1.py counts its own)
        "total_params": (n_full * (full + full_small + mlp)
                         + n_lin * (lin + lin_small + mlp) + E + E * V),
        # float32 state and a (K-1)-step window of bfloat16 inputs, a
        # linear-attention layer
        "state_bytes_per_row": n_lin * (
            z["H"] * z["Dk"] * z["Dv"] * 4 + (z["K"] - 1) * z["conv"] * 2
        ),
    }


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale.astype(F32)


def _swiglu(lp, x):
    f = lambda a: a.astype(F32)
    return (jax.nn.silu(x @ f(lp["gate"].w)) * (x @ f(lp["up"].w))) @ f(lp["down"].w)


def _attention(hf, lp, x):
    B, T, E = x.shape
    H, KV = hf["num_attention_heads"], hf["num_key_value_heads"]
    D, eps = E // H, hf["rms_norm_eps"]
    f = lambda a: a.astype(F32)
    q = _rms(x @ f(lp["q"].w).T, lp["q_norm"].scale, eps).reshape(B, T, H, D)
    k = _rms(x @ f(lp["k"].w).T, lp["k_norm"].scale, eps).reshape(B, T, KV, D)
    v = (x @ f(lp["v"].w)).reshape(B, T, KV, D)
    k, v = (jnp.repeat(a, H // KV, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(D))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return a.reshape(B, T, H * D) @ f(lp["o"].w)


def _delta_net(hf, lp, x):
    B, T, _ = x.shape
    z = _sizes(hf)
    H, Dk, Dv, K = z["H"], z["Dk"], z["Dv"], z["K"]
    f = lambda a: a.astype(F32)
    w = f(lp["gdn_conv"].w)  # [K, C]: the last tap multiplies the current input
    padded = jnp.pad(x @ f(lp["gdn_qkv"].w), [(0, 0), (K - 1, 0), (0, 0)])
    qkv = jax.nn.silu(sum(padded[:, t:t + T] * w[t] for t in range(K)))
    q, k, v = jnp.split(qkv, [z["key"], 2 * z["key"]], axis=-1)
    unit = lambda a: a / jnp.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)
    q = unit(q.reshape(B, T, H, Dk)) * Dk ** -0.5
    k = unit(k.reshape(B, T, H, Dk))
    v = v.reshape(B, T, H, Dv)
    a, b = jnp.split(x @ f(lp["gdn_ab"].w), 2, axis=-1)  # [B, T, H] each
    beta = jax.nn.sigmoid(b) * (2.0 if hf["linear_allow_neg_eigval"] else 1.0)
    alpha = jnp.exp(-jnp.exp(f(lp["gdn_A_log"]))
                    * jax.nn.softplus(a + f(lp["gdn_dt_bias"])))

    def step(S, inp):  # S [B, H, Dk, Dv]
        q_t, k_t, v_t, beta_t, alpha_t = inp
        S = alpha_t[..., None, None] * S
        u = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    t_first = lambda arr: jnp.moveaxis(arr, 1, 0)
    _, o = jax.lax.scan(
        step, jnp.zeros((B, H, Dk, Dv), F32),
        tuple(t_first(arr) for arr in (q, k, v, beta, alpha)),
    )
    o = _rms(jnp.moveaxis(o, 0, 1), lp["gdn_norm"].scale, hf["rms_norm_eps"])
    gate = jax.nn.silu(x @ f(lp["gdn_g"].w)).reshape(B, T, H, Dv)
    return (o * gate).reshape(B, T, H * Dv) @ f(lp["gdn_o"].w)


def layer(hf: dict, kind: str, lp, h):
    eps = hf["rms_norm_eps"]
    mixer = _delta_net if kind == LINEAR else _attention
    h = h + _rms(mixer(hf, lp, h), lp["ln1"].scale, eps)
    return h + _rms(_swiglu(lp, h), lp["ln2"].scale, eps)


def layers(hf: dict, params):
    """The two kinds in the published pattern's order, each layer from its
    own kind's stack by its index within the kind."""
    seen = {LINEAR: 0, FULL: 0}
    stacks = {LINEAR: params["linear"], FULL: params["blocks"]}
    for kind in hf["layer_types"]:
        i = seen[kind]
        seen[kind] += 1
        yield kind, jax.tree.map(lambda a: a[i], stacks[kind])


def control(params):
    """The negative control's one fault: ``W_b`` lost (zero, so ``beta`` is 1
    at every token and head where the model writes with a strength in (0, 2)
    of its own choosing), as a loader that fills a leaf it cannot find with
    zeros would leave a model whose every branch still runs."""
    ab = params["linear"]["gdn_ab"]
    H = ab.w.shape[-1] // 2
    linear = {**params["linear"],
              "gdn_ab": ab._replace(w=ab.w.at[..., H:].set(0))}
    return "beta_projection_lost", {**params, "linear": linear}


def embed(hf: dict, params, ids):
    return params["wte"][ids].astype(F32)


def head(hf: dict, params, h):
    x = _rms(h, params["ln_f"].scale, hf["rms_norm_eps"])
    return x @ params["head"].w.astype(F32)
