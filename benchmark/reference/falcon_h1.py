"""Falcon-H1 forward pass in plain float32 ``jax.numpy``.

Written from the published description (Zuo et al., "Falcon-H1: A Family of
Hybrid-Head Language Models", 2025; ``FalconH1Config``), with the config's
keys at ``benchmark/configs/falcon-h1-34b-1chip.json``. Token embedding times
``embedding_multiplier``; ``num_hidden_layers`` blocks, each

    x  = RMSNorm(h)
    A  = attention(x * attention_in_multiplier) * attention_out_multiplier
    M  = mixer(x * ssm_in_multiplier) * ssm_out_multiplier
    h  = h + A + M
    h  = h + MLP(RMSNorm(h))

Attention: grouped-query, ``head_dim`` a key of its own, no bias, keys times
``key_multiplier``, RoPE in half-rotation form (feature i pairs with feature
i + head_dim/2) at ``rope_theta``, causal softmax(q k^T / sqrt(head_dim)) v.
Mixer (Mamba-2): input projection to z, x, B, C, dt (widths ``mamba_d_ssm``,
``mamba_d_ssm``, ``n_groups x d_state`` twice, ``mamba_n_heads``), each
segment times its entry of ``ssm_multipliers`` (order z, x, B, C, dt:
assumed); causal depthwise convolution over ``mamba_d_conv`` steps with bias
on [x, B, C], then SiLU; ``dt = softplus(dt + dt_bias)``, ``a = exp(-dt
exp(A_log))``; state ``S_t = a_t S_{t-1} + dt_t x_t (outer) B_t`` a head (a
head reads its group's B and C), ``y_t = S_t C_t + D x_t``; gate then norm
(``mamba_norm_before_gate`` false, ``mamba_rms_norm`` true): ``y = RMSNorm(y
* SiLU(z))`` over each group's channels; output projection. MLP: SwiGLU with
the gate's pre-activation times ``mlp_multipliers[0]`` and the output times
``mlp_multipliers[1]``. Final RMSNorm, untied head, logits times
``lm_head_multiplier``.

The recurrence is a plain ``lax.scan`` over tokens: no chunking, no cache, and
nothing imported from the program. Reads the engine's parameter tree only for
the numbers (``blocks`` stacked over the layers; q and k stored [out, in],
every other matrix [in, out]; the convolution's weight [K, C]) and upcasts one
layer at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _sizes(hf: dict) -> dict:
    d_ssm = hf.get("mamba_d_ssm") or hf["mamba_expand"] * hf["hidden_size"]
    bc = hf["mamba_n_groups"] * hf["mamba_d_state"]
    return {
        "d_ssm": d_ssm, "bc": bc, "conv_dim": d_ssm + 2 * bc,
        "proj": 2 * d_ssm + 2 * bc + hf["mamba_n_heads"],
    }


def dims(hf: dict) -> dict:
    E, L, V = hf["hidden_size"], hf["num_hidden_layers"], hf["vocab_size"]
    H, KV, D = (hf["num_attention_heads"], hf["num_key_value_heads"],
                hf["head_dim"])
    I, Hm, K = hf["intermediate_size"], hf["mamba_n_heads"], hf["mamba_d_conv"]
    z = _sizes(hf)
    attn = E * (H * D + 2 * KV * D) + H * D * E
    mixer = E * z["proj"] + z["d_ssm"] * E
    small = z["conv_dim"] * (K + 1) + 3 * Hm + z["d_ssm"] + 2 * E
    return {
        "layers": L, "kv_layers": L, "hidden": E, "heads": H, "kv_heads": KV,
        "head_dim": D, "inner": I, "vocab": V,
        "matmul_params": L * (attn + mixer + 3 * E * I) + E * V,
        # What is held here AND read every step (lib/costs.py's own words):
        # the layers, the final norm and the untied head. The embedding
        # table's V x E are held (2.67 GB of the 9.65) but a step gathers
        # `rows` of its rows, so it is left out of the decode floor; the
        # other families count theirs because theirs IS the head.
        "total_params": L * (attn + mixer + small + 3 * E * I) + E + E * V,
        # float32 state and a (K-1)-step window of bfloat16 inputs, a layer
        "state_bytes_per_row": L * (
            Hm * hf["mamba_d_head"] * hf["mamba_d_state"] * 4
            + (K - 1) * z["conv_dim"] * 2
        ),
    }


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale.astype(F32)


def _rope(x, theta: float):
    """x [B, T, H, D] rotated by the position along T, half-rotation form."""
    T, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]  # [T, D/2]
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    a, b = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(hf, lp, x):
    B, T, _ = x.shape
    H, KV, D = (hf["num_attention_heads"], hf["num_key_value_heads"],
                hf["head_dim"])
    f = lambda a: a.astype(F32)
    xa = x * hf["attention_in_multiplier"]
    q = (xa @ f(lp["q"].w).T).reshape(B, T, H, D)
    k = ((xa @ f(lp["k"].w).T) * hf["key_multiplier"]).reshape(B, T, KV, D)
    v = (xa @ f(lp["v"].w)).reshape(B, T, KV, D)
    q, k = _rope(q, float(hf["rope_theta"])), _rope(k, float(hf["rope_theta"]))
    k, v = (jnp.repeat(a, H // KV, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(D))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return (a.reshape(B, T, H * D) @ f(lp["o"].w)) * hf["attention_out_multiplier"]


def _mixer(hf, lp, x):
    B, T, _ = x.shape
    z_ = _sizes(hf)
    d_ssm, bc = z_["d_ssm"], z_["bc"]
    Hm, P, G, N, K = (hf["mamba_n_heads"], hf["mamba_d_head"],
                      hf["mamba_n_groups"], hf["mamba_d_state"],
                      hf["mamba_d_conv"])
    f = lambda a: a.astype(F32)
    mz, mx, mb, mc, mdt = hf["ssm_multipliers"]
    p = (x * hf["ssm_in_multiplier"]) @ f(lp["ssm_in"].w)
    z, xbc, dt = jnp.split(p, [d_ssm, d_ssm + z_["conv_dim"]], axis=-1)
    z, dt = z * mz, dt * mdt
    xbc = xbc * jnp.concatenate([
        jnp.full(d_ssm, mx, F32), jnp.full(bc, mb, F32), jnp.full(bc, mc, F32)
    ])
    # causal depthwise convolution: output t sees inputs t-K+1 .. t, zeros
    # before the sequence; the last tap multiplies the current input
    w, b = f(lp["ssm_conv"].w), f(lp["ssm_conv"].b)
    padded = jnp.pad(xbc, [(0, 0), (K - 1, 0), (0, 0)])
    xbc = jax.nn.silu(sum(padded[:, k:k + T] * w[k] for k in range(K)) + b)
    xs, Bm, Cm = jnp.split(xbc, [d_ssm, d_ssm + bc], axis=-1)
    xs = xs.reshape(B, T, Hm, P)
    # a head reads its group's B and C
    Bm = jnp.repeat(Bm.reshape(B, T, G, N), Hm // G, axis=2)
    Cm = jnp.repeat(Cm.reshape(B, T, G, N), Hm // G, axis=2)
    dt = jax.nn.softplus(dt + f(lp["ssm_dt_bias"]))  # [B, T, Hm]
    a = jnp.exp(dt * -jnp.exp(f(lp["ssm_A_log"])))

    def step(S, inp):  # S [B, Hm, P, N]
        x_t, B_t, C_t, dt_t, a_t = inp
        S = a_t[..., None, None] * S + (
            (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, C_t)

    t_first = lambda arr: jnp.moveaxis(arr, 1, 0)
    _, ys = jax.lax.scan(
        step, jnp.zeros((B, Hm, P, N), F32),
        tuple(t_first(arr) for arr in (xs, Bm, Cm, dt, a)),
    )
    y = jnp.moveaxis(ys, 0, 1) + f(lp["ssm_D"])[:, None] * xs
    y = y.reshape(B, T, d_ssm) * jax.nn.silu(z)
    yg = y.reshape(B, T, G, d_ssm // G)
    yg = yg * jax.lax.rsqrt((yg * yg).mean(-1, keepdims=True) + hf["rms_norm_eps"])
    y = yg.reshape(B, T, d_ssm) * f(lp["ssm_norm"].scale)
    return (y @ f(lp["ssm_out"].w)) * hf["ssm_out_multiplier"]


def layer(hf: dict, kind: str, lp, h):
    eps = hf["rms_norm_eps"]
    f = lambda a: a.astype(F32)
    x = _rms(h, lp["ln1"].scale, eps)
    h = h + _attention(hf, lp, x) + _mixer(hf, lp, x)
    x2 = _rms(h, lp["ln2"].scale, eps)
    m_gate, m_out = hf["mlp_multipliers"]
    gate = jax.nn.silu((x2 @ f(lp["gate"].w)) * m_gate)
    return h + ((gate * (x2 @ f(lp["up"].w))) @ f(lp["down"].w)) * m_out


def layers(hf: dict, params):
    """One kind of layer: every block holds both branches and its MLP."""
    for l in range(hf["num_hidden_layers"]):
        yield "block", jax.tree.map(lambda a: a[l], params["blocks"])


def control(params):
    """The negative control's one fault: the mixer's branch lost (its
    output projection zero, what ``ssm_out_multiplier`` = 0 computes), as a
    loader that skips leaves it does not know would leave a model whose
    attention and MLP still look right."""
    out = params["blocks"]["ssm_out"]
    blocks = {**params["blocks"], "ssm_out": out._replace(w=out.w * 0)}
    return "mixer_lost", {**params, "blocks": blocks}


def embed(hf: dict, params, ids):
    return params["wte"][ids].astype(F32) * hf["embedding_multiplier"]


def head(hf: dict, params, h):
    x = _rms(h, params["ln_f"].scale, hf["rms_norm_eps"])
    return (x @ params["head"].w.astype(F32)) * hf["lm_head_multiplier"]
