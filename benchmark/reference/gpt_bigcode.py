"""GPT-BigCode (StarCoder) forward pass in plain float32 ``jax.numpy``.

Written from the published description (Li et al., "StarCoder: may the
source be with you!", 2023; ``GPTBigCodeConfig``): token + learned position
embedding; ``n_layer`` pre-LayerNorm blocks, sequential residual —
``h += attn(ln_1(h))``, ``h += mlp(ln_2(h))``; multi-query attention (``n_head``
query heads share ONE key/value head of ``n_embd / n_head`` features),
softmax(q·k / sqrt(head_dim)) over a causal mask; MLP ``c_proj(act(c_fc(x)))``
with the tanh GELU; final LayerNorm; logits against the token embedding (tied
head). Every projection has a bias.

No kernel, no cache, no batching tricks: whole sequences, whole softmax. It
reads the engine's parameter tree only for the numbers (see ``layer``: the
program stores q and k as [out, in], v/o/fc as [in, out]) and upcasts ONE
layer at a time to float32, so nothing larger than one float32 layer is
added to the device. Callers run it under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def dims(hf: dict) -> dict:
    """Shapes of this family from the published config keys: what
    ``benchmark/lib/costs.py`` prices."""
    E, L, H = hf["n_embd"], hf["n_layer"], hf["n_head"]
    I = hf.get("n_inner") or 4 * E
    V, T = hf["vocab_size"], hf["n_positions"]
    D = E // H
    KV = 1 if hf.get("multi_query", True) else H
    per_layer_mat = E * E + 2 * E * KV * D + E * E + 2 * E * I
    per_layer_vec = 2 * E + E + 2 * KV * D + E + 2 * E + I + E
    return {
        "layers": L, "hidden": E, "heads": H, "kv_heads": KV, "head_dim": D,
        "inner": I, "vocab": V,
        # The tied head multiplies every token with the embedding matrix.
        "matmul_params": L * per_layer_mat + V * E,
        "total_params": (
            V * E + T * E + L * (per_layer_mat + per_layer_vec) + 2 * E
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


def layer(hf: dict, kind: str, lp, h):
    """One block on ``h`` [B, T, E] (float32); ``lp`` is one layer's slice of
    the engine's ``params["blocks"]``, still in its stored dtype."""
    B, T, E = h.shape
    H = hf["n_head"]
    D = E // H
    eps = hf.get("layer_norm_epsilon", 1e-5)
    f = lambda a: a.astype(F32)
    x = _ln(h, lp["ln1"], eps)
    q = (x @ f(lp["q"].w).T + f(lp["q"].b)).reshape(B, T, H, D)
    k = (x @ f(lp["k"].w).T + f(lp["k"].b)).reshape(B, T, -1, D)
    v = (x @ f(lp["v"].w) + f(lp["v"].b)).reshape(B, T, -1, D)
    if k.shape[2] == 1:  # multi-query: every head reads the one KV head
        k = jnp.broadcast_to(k, (B, T, H, D))
        v = jnp.broadcast_to(v, (B, T, H, D))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(D))
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    h = h + a.reshape(B, T, E) @ f(lp["o"].w) + f(lp["o"].b)
    x = _ln(h, lp["ln2"], eps)
    y = _act(hf["activation_function"])(x @ f(lp["fc_in"].w) + f(lp["fc_in"].b))
    return h + y @ f(lp["fc_out"].w) + f(lp["fc_out"].b)


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
    pos = jnp.arange(ids.shape[1])
    return params["wte"][ids].astype(F32) + params["wpe"][pos].astype(F32)[None]


def head(hf: dict, params, h):
    """Logits [B, V] of the rows ``h`` [B, E] taken from the last block."""
    x = _ln(h, params["ln_f"], hf.get("layer_norm_epsilon", 1e-5))
    return x @ params["wte"].astype(F32).T
