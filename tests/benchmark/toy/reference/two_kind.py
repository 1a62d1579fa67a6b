"""A made-up family for the harness's own tests: layers of TWO kinds in a
ragged tree, no bias anywhere, depth under a key that is not ``n_layer``.

``pattern`` (a string, one letter a layer) is the config's depth: ``M`` is a
pre-RMSNorm squared-ReLU MLP, ``h += relu(rms(h) @ w1)**2 @ w2``; ``A`` one
head of causal attention, ``h += softmax(q k^T / sqrt(E)) v @ o``. The tree
holds one stack a KIND (``params["mlp"]`` stacked over the M layers,
``params["attn"]`` over the A layers), so no index runs over all layers.
Nothing of the program can serve it; what it shows is that
``benchmark/lib/check.py`` can check such a family with this file alone."""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
KINDS = {"M": "mlp", "A": "attn"}


def dims(hf: dict) -> dict:
    E, I, V = hf["width"], hf["inner"], hf["vocab_size"]
    n_m, n_a = hf["pattern"].count("M"), hf["pattern"].count("A")
    weights = n_m * 2 * E * I + n_a * 4 * E * E + V * E
    return {
        "layers": len(hf["pattern"]), "kv_layers": n_a, "hidden": E,
        "heads": 1, "kv_heads": 1, "head_dim": E, "inner": I, "vocab": V,
        "matmul_params": weights, "total_params": weights,
        # the M layers' state is made up too: E float32 a row and layer
        "state_bytes_per_row": n_m * E * 4,
    }


def _rms(x):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + 1e-6)


def layers(hf: dict, params):
    seen = {kind: 0 for kind in KINDS.values()}
    for letter in hf["pattern"]:
        kind = KINDS[letter]
        i = seen[kind]
        seen[kind] += 1
        yield kind, jax.tree.map(lambda a: a[i], params[kind])


def layer(hf: dict, kind: str, lp, h):
    x = _rms(h)
    if kind == "mlp":
        y = jax.nn.relu(x @ lp["w1"].astype(F32)) ** 2
        return h + y @ lp["w2"].astype(F32)
    T, E = h.shape[1], h.shape[2]
    q, k, v = (x @ lp[n].astype(F32) for n in ("q", "k", "v"))
    s = jnp.einsum("bqe,bke->bqk", q, k) / jnp.sqrt(F32(E))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    return h + jax.nn.softmax(s, -1) @ v @ lp["o"].astype(F32)


def control(params):
    """The one attention layer's output projection lost."""
    attn = {**params["attn"], "o": params["attn"]["o"] * 0}
    return "attn_output_lost", {**params, "attn": attn}


def embed(hf: dict, params, ids):
    return params["wte"][ids].astype(F32)


def head(hf: dict, params, h):
    return _rms(h) @ params["wte"].astype(F32).T
