"""Plain float32 reference of the DeepSeek-V3 family (``model_type``
``deepseek_v3``) as ``kanana-2-30b-a3b-instruct-2601`` configures it, written
from the catalog's config keys and the published implementation as remembered
(no network here). It imports nothing of the program; it reads the engine's
parameter tree for the numbers only. The contract is in benchmark/README.md.

The equations (pre-norm, sequential residual, RMSNorm with ``rms_norm_eps``,
final RMSNorm, untied head, no biases):

*Attention (MLA, ``q_lora_rank`` null).* ``x = RMSNorm(h)``. ``q = x W_q`` ->
``H`` heads of ``qk_nope_head_dim + qk_rope_head_dim`` = ``q_nope || q_rope``.
``c = x W_kv_a`` -> ``kv_lora_rank + qk_rope_head_dim`` = ``c_kv || k_rope``;
``c_kv = RMSNorm(c_kv)`` with its own scale (``kv_a_layernorm``); ``k_rope =
RoPE(k_rope)``, ONE head shared by all; ``q_rope = RoPE(q_rope)``;
``rope_theta``, pairs ``(2i, 2i+1)`` rotate together (``rope_interleave``
true), no scaling. ``[k_nope || v] = c_kv W_kv_b`` -> ``H`` heads of
``qk_nope_head_dim + v_head_dim``. ``score = (q_nope . k_nope + q_rope .
k_rope) / sqrt(qk_nope_head_dim + qk_rope_head_dim)``, causal softmax, ``o =
(sum p v) W_o``. This file MATERIALISES ``k_nope`` and ``v`` of every head and
position; the program attends in the absorbed form (the query meets ``W_uk``,
the weighted sum of latents meets ``W_uv``) and caches ``c_kv || k_rope``
only, so the two forms check each other.

*The first ``first_k_dense_replace`` layers' MLP:* SwiGLU of
``intermediate_size``. *The others:* ``s = sigmoid(x W_g^T)``, the
``num_experts_per_tok`` experts with the largest ``s + b`` (``b`` =
``e_score_correction_bias``, in the SELECTION only; ``n_group`` =
``topk_group`` = 1, so no group step), ``w = s[chosen] / (sum s[chosen] +
1e-20) x routed_scaling_factor`` (``norm_topk_prob``), ``y = sum w_i E_i(x) +
S(x)``: ``E_i`` a SwiGLU of ``moe_intermediate_size``, ``S`` ONE SwiGLU of
``n_shared_experts x moe_intermediate_size``. Here the experts run ONE AT A
TIME under a ``lax.scan`` with a mask a token (every expert sees every token;
at 4 x 513 tokens all 128 experts at once would hold 0.8 GB of
activations).

Departures from the published implementation, each of no effect on the
numbers compared: the rotary layout (the published code de-interleaves the
pairs and rotates halves; the same rotation of the same pairs, the cached
rotary key in another order of columns); the router reads the normed input in
float32 as it is here, where the published bfloat16 model upcasts the rounded
one (configs/kanana-2-30b-a3b-1chip.json, ``assumed``).

``dims``: what is CACHED a token a layer is the latent, 576 numbers, so
``kv_heads`` 1 and ``head_dim`` 288 make ``lib/costs.py: kv_bytes_per_token``
price ``2 x 1 x 288`` and not the heads keys and values are never rebuilt
into. ``total_params`` counts ALL the routed experts of every expert layer
(held here; a step reads those its live rows hit: 105 of 128 at 36 rows, all
of them from about 100 rows), so ``decode_step_mfu_roofline``'s floor is up
to 18% high at 36 live rows and can only flatter by that much; it leaves out
the embedding table, which a step gathers ``rows`` rows of. ``matmul_params``
is what ONE token is multiplied with: attention, the dense MLP or the router,
the shared expert and the ``num_experts_per_tok`` chosen experts, the head.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _sizes(hf: dict) -> dict:
    E, H = hf["hidden_size"], hf["num_attention_heads"]
    C, Dn, Dr, Dv = (hf["kv_lora_rank"], hf["qk_nope_head_dim"],
                     hf["qk_rope_head_dim"], hf["v_head_dim"])
    Ie = hf["moe_intermediate_size"]
    attn = (E * H * (Dn + Dr) + E * (C + Dr) + C * H * (Dn + Dv)
            + H * Dv * E)
    return {
        "attn": attn, "attn_small": C + 2 * E,  # the three norm scales
        "dense": 3 * E * hf["intermediate_size"],
        "shared": 3 * E * hf["n_shared_experts"] * Ie,
        "router": hf["n_routed_experts"] * (E + 1),
        "expert": 3 * E * Ie,
        "n_dense": hf["first_k_dense_replace"],
        "n_moe": hf["num_hidden_layers"] - hf["first_k_dense_replace"],
    }


def dims(hf: dict) -> dict:
    z = _sizes(hf)
    E, V = hf["hidden_size"], hf["vocab_size"]
    N, K = hf["n_routed_experts"], hf["num_experts_per_tok"]
    moe_held = z["attn"] + z["shared"] + z["router"] + N * z["expert"]
    moe_token = z["attn"] + z["shared"] + z["router"] + K * z["expert"]
    return {
        "layers": hf["num_hidden_layers"], "hidden": E,
        "heads": hf["num_attention_heads"],
        # the cached latent: 2 x 1 x 288 = 576 numbers a token a layer
        "kv_heads": 1,
        "head_dim": (hf["kv_lora_rank"] + hf["qk_rope_head_dim"]) // 2,
        "inner": hf["intermediate_size"], "vocab": V,
        "matmul_params": (z["n_dense"] * (z["attn"] + z["dense"])
                          + z["n_moe"] * moe_token + E * V),
        "total_params": (
            z["n_dense"] * (z["attn"] + z["dense"] + z["attn_small"])
            + z["n_moe"] * (moe_held + z["attn_small"]) + E + E * V),
    }


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale.astype(F32)


def _rope(x, theta: float):
    """x [B, T, H, D] rotated by the position along T; features 2i and 2i+1
    are one pair."""
    T, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]  # [T, D/2]
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape)


def _attention(hf, lp, x):
    B, T, _ = x.shape
    H, C = hf["num_attention_heads"], hf["kv_lora_rank"]
    Dn, Dr, Dv = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]
    f = lambda a: a.astype(F32)
    theta = float(hf["rope_theta"])
    q = (x @ f(lp["q"].w).T).reshape(B, T, H, Dn + Dr)
    c = x @ f(lp["kv_a"].w)
    c_kv = _rms(c[..., :C], lp["kv_norm"].scale, hf["rms_norm_eps"])
    k_rope = _rope(c[..., None, C:], theta)  # [B, T, 1, Dr]
    q_nope, q_rope = q[..., :Dn], _rope(q[..., Dn:], theta)
    kv = (c_kv @ f(lp["kv_b"].w)).reshape(B, T, H, Dn + Dv)
    k_nope, v = kv[..., :Dn], kv[..., Dn:]
    s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
         + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope[:, :, 0])
         ) / jnp.sqrt(F32(Dn + Dr))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return a.reshape(B, T, H * Dv) @ f(lp["o"].w)


def _swiglu(x, gate, up, down):
    f = lambda a: a.astype(F32)
    return (jax.nn.silu(x @ f(gate)) * (x @ f(up))) @ f(down)


def _experts(hf, lp, x):
    """Router, then every expert in turn over every token, masked."""
    K, N = hf["num_experts_per_tok"], hf["n_routed_experts"]
    s = jax.nn.sigmoid(x @ lp["router"].w.astype(F32).T)  # [B, T, N]
    _, chosen = jax.lax.top_k(s + lp["router"].b.astype(F32), K)
    w = jnp.take_along_axis(s, chosen, -1)
    if hf["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * hf["routed_scaling_factor"]
    # [B, T, N]: a token's weight of each expert, 0 where not chosen
    dense_w = (jax.nn.one_hot(chosen, N, dtype=F32) * w[..., None]).sum(-2)

    def one(y, e):
        gate, up, down, w_e = e
        return y + w_e[..., None] * _swiglu(x, gate, up, down), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (lp["experts_gate"], lp["experts_up"], lp["experts_down"],
         jnp.moveaxis(dense_w, -1, 0)),
    )
    return y + _swiglu(
        x, lp["shared_gate"].w, lp["shared_up"].w, lp["shared_down"].w)


def layer(hf: dict, kind: str, lp, h):
    eps = hf["rms_norm_eps"]
    h = h + _attention(hf, lp, _rms(h, lp["ln1"].scale, eps))
    x = _rms(h, lp["ln2"].scale, eps)
    if kind == "moe":
        return h + _experts(hf, lp, x)
    return h + _swiglu(x, lp["gate"].w, lp["up"].w, lp["down"].w)


def layers(hf: dict, params):
    """Two kinds, a stack each in the engine's tree: the leading ``dense``
    layers (``params["lead"]``), then the ``moe`` layers."""
    n_dense = hf["first_k_dense_replace"]
    for l in range(n_dense):
        yield "dense", jax.tree.map(lambda a: a[l], params["lead"])
    for l in range(hf["num_hidden_layers"] - n_dense):
        yield "moe", jax.tree.map(lambda a: a[l], params["blocks"])


def control(params):
    """The negative control's one fault: ``e_score_correction_bias`` lost, as
    a loader that reads weights and skips buffers would leave it (zeros). The
    bias takes part in the selection only, so the fault is a wrong choice of
    experts for most tokens and the right weights for the wrong experts. (A
    fault in the experts' own matrices, ``routed_scaling_factor`` read as 1
    planted as every down-projection divided by it, needs a second copy of
    2.4 GB of experts beside the weights: the check ran out of memory on
    it, my chip run, PR 38.)"""
    router = params["blocks"]["router"]
    blocks = {**params["blocks"], "router": router._replace(b=router.b * 0)}
    return "selection_bias_lost", {**params, "blocks": blocks}


def embed(hf: dict, params, ids):
    return params["wte"][ids].astype(F32)


def head(hf: dict, params, h):
    x = _rms(h, params["ln_f"].scale, hf["rms_norm_eps"])
    return x @ params["head"].w.astype(F32)
