"""GPT-J forward pass in plain float32 ``jax.numpy``.

Written from the published description (Wang & Komatsuzaki, "GPT-J-6B",
2021; ``GPTJConfig``): token embedding, no position table; ``n_layer`` blocks
with ONE pre-LayerNorm feeding both branches and a parallel residual —
``h = h + attn(ln_1(h)) + mlp(ln_1(h))``; full multi-head attention whose
first ``rotary_dim`` features of every query and key head are rotated by
position, pairs interleaved (features 2i and 2i+1 form a pair, angle
``pos / 10000^(2i/rotary_dim)``); q/k/v/out projections without bias; MLP
``fc_out(gelu_new(fc_in(x)))`` with biases; final LayerNorm; an untied output
head with a bias.

Reads the engine's parameter tree only for the numbers and upcasts one layer
at a time (see ``gpt_bigcode.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def dims(hf: dict) -> dict:
    E, L, H = hf["n_embd"], hf["n_layer"], hf["n_head"]
    I = hf.get("n_inner") or 4 * E
    V = hf["vocab_size"]
    per_layer_mat = 4 * E * E + 2 * E * I
    per_layer_vec = 2 * E + I + E
    return {
        "layers": L, "hidden": E, "heads": H, "kv_heads": H,
        "head_dim": E // H, "inner": I, "vocab": V,
        "matmul_params": L * per_layer_mat + V * E,
        "total_params": (
            V * E + L * (per_layer_mat + per_layer_vec) + 2 * E + E * V + V
        ),
    }


def _act(name: str):
    """``gelu`` is the exact (erf) form; ``gelu_new``, ``gelu_pytorch_tanh`` and
    ``gelu_fast`` the tanh approximation."""
    if name == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=False)
    if name in ("gelu_new", "gelu_pytorch_tanh", "gelu_fast"):
        return lambda x: jax.nn.gelu(x, approximate=True)
    raise KeyError(f"activation {name!r} is not part of this reference")


def _ln(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p.scale.astype(F32) + p.bias.astype(F32)


def _rotate(x, rotary_dim: int):
    """Rotate the first ``rotary_dim`` features of x [B, T, H, D] by the
    position along T, pairs interleaved."""
    T = x.shape[1]
    inv = 1.0 / (10000.0 ** (jnp.arange(0, rotary_dim, 2, dtype=F32) / rotary_dim))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]  # [T, rd/2]
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    even, odd = rot[..., 0::2], rot[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return jnp.concatenate([out.reshape(rot.shape), rest], -1)


def layer(hf: dict, kind: str, lp, h):
    B, T, E = h.shape
    H = hf["n_head"]
    D = E // H
    rd = hf.get("rotary_dim") or D
    f = lambda a: a.astype(F32)
    x = _ln(h, lp["ln1"], hf.get("layer_norm_epsilon", 1e-5))
    q = _rotate((x @ f(lp["q"].w).T).reshape(B, T, H, D), rd)
    k = _rotate((x @ f(lp["k"].w).T).reshape(B, T, H, D), rd)
    v = (x @ f(lp["v"].w)).reshape(B, T, H, D)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(D))
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    attn = a.reshape(B, T, E) @ f(lp["o"].w)
    y = _act(hf["activation_function"])(x @ f(lp["fc_in"].w) + f(lp["fc_in"].b))
    return h + attn + y @ f(lp["fc_out"].w) + f(lp["fc_out"].b)


def layers(hf: dict, params):
    """This family's layers in order, ``(kind, lp)`` each: one kind, every
    leaf of ``params["blocks"]`` stacked over ``n_layer``."""
    for l in range(hf["n_layer"]):
        yield "block", jax.tree.map(lambda a: a[l], params["blocks"])


def control(params):
    """The negative control's one fault: every projection bias of the blocks
    dropped, as a loader that skips biases would leave them."""
    blocks = {
        k: p._replace(b=p.b * 0) if getattr(p, "b", None) is not None else p
        for k, p in params["blocks"].items()
    }
    return "dropped_bias", {**params, "blocks": blocks}


def embed(hf: dict, params, ids):
    return params["wte"][ids].astype(F32)


def head(hf: dict, params, h):
    x = _ln(h, params["ln_f"], hf.get("layer_norm_epsilon", 1e-5))
    return x @ params["head"].w.astype(F32) + params["head"].b.astype(F32)
