"""Core TP layers: dense, embedding, lm_head, norms — and their loaders.

Weight layout convention: **[in, out]** everywhere (the natural layout for
``x @ W`` on the MXU). Torch ``nn.Linear`` checkpoints ([out, in]) are
transpose-loaded with sliced reads; HF Conv1D checkpoints (GPT-2/BigCode,
already [in, out]) load directly.

Sharding convention (Megatron, parity with ``utils/layers.py``):

- column-parallel ≙ ``TensorParallelColumnLinear`` (``layers.py:138-153``):
  W: P(None, tp), b: P(tp) — output feature-sharded, no communication.
- row-parallel ≙ ``TensorParallelRowLinear`` (``layers.py:156-179``):
  W: P(tp, None), b replicated — the contraction over the sharded axis makes
  XLA insert the psum the reference issues by hand (``layers.py:178``); the
  replicated bias is added after the reduction, which also removes the
  reference's rank-0-only-bias trick (``layers.py:165-169``).
- vocab-parallel embedding ≙ ``TensorParallelEmbedding``
  (``layers.py:182-214``): table P(tp, None) on vocab; the reference's
  explicit out-of-range→null-row masking + allreduce is what GSPMD generates
  for a gather over a sharded dim (or exactly what the one-hot-matmul path
  computes).
- head ≙ ``TensorParallelHead`` (``layers.py:79-135``): W P(None, tp) on
  vocab; constraining the output replicated makes XLA emit the all-gather
  (``layers.py:125``). Non-divisible vocab needs no replicated fallback
  (``layers.py:85-98``): JAX shards unevenly with implicit padding.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from llmss_tpu.parallel.mesh import AXIS_TP
from llmss_tpu.weights.loader import CheckpointShards


class LinearParams(NamedTuple):
    w: jax.Array  # [in, out]
    b: jax.Array | None


class NormParams(NamedTuple):
    scale: jax.Array
    bias: jax.Array | None


# -- forward functions -------------------------------------------------------


def dense(x: jax.Array, p: LinearParams) -> jax.Array:
    """y = x @ W (+ b). ≙ FastLinear/SuperLayer.forward (layers.py:60-76)."""
    y = x @ p.w.astype(x.dtype)
    if p.b is not None:
        y = y + p.b.astype(y.dtype)
    return y


def dense_t(x: jax.Array, p: LinearParams) -> jax.Array:
    """y = x @ Wᵀ (+ b) for weights stored ``[out, in]``.

    Used for the q/k projections: their outputs feed rope's f32
    reshape/convert, and XLA's fusion there wants the weight with the
    contracting (in) dim minor. With ``[in, out]`` storage the decode step
    pays a per-layer-per-step relayout copy of each sliced scan weight
    (~18% of step time at 1B scale, measured on v5e); storing ``[out, in]``
    makes the stacked-parameter slice feed the fused matmul directly.
    """
    y = jnp.einsum("...e,oe->...o", x, p.w.astype(x.dtype))
    if p.b is not None:
        y = y + p.b.astype(y.dtype)
    return y


def embedding(ids: jax.Array, table: jax.Array, *, one_hot: bool = False) -> jax.Array:
    """Vocab-(possibly-)partitioned embedding lookup.

    ``one_hot=True`` computes the lookup as a one-hot matmul — on TPU this
    keeps the op on the MXU and partitions cleanly over a vocab-sharded table
    (the masked-matmul formulation *is* the reference's mask+psum scheme,
    ``layers.py:200-213``, expressed as algebra instead of collectives).
    """
    if one_hot:
        oh = jax.nn.one_hot(ids, table.shape[0], dtype=table.dtype)
        return oh @ table
    return jnp.take(table, ids, axis=0)


def lm_head(x: jax.Array, p: LinearParams) -> jax.Array:
    """Project to full-vocab logits, replicated on every device.

    fp32 logits for sampling parity with the reference
    (``gptj_modeling.py:609``).
    """
    logits = (x @ p.w.astype(x.dtype)).astype(jnp.float32)
    if p.b is not None:
        logits = logits + p.b.astype(jnp.float32)
    return logits


def layer_norm(x: jax.Array, p: NormParams, eps: float) -> jax.Array:
    """Replicated LayerNorm in fp32 islands (≙ nn.LayerNorm, replicated
    per layers.py:12-36)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p.scale.astype(jnp.float32)
    if p.bias is not None:
        y = y + p.bias.astype(jnp.float32)
    return y.astype(x.dtype)


def rms_norm(
    x: jax.Array, p: NormParams, eps: float, scale_offset: float = 0.0,
    out_dtype=None,
) -> jax.Array:
    """RMSNorm (Llama-family; no reference equivalent — new capability).
    ``scale_offset`` implements Gemma's (1 + weight) parameterization;
    ``out_dtype`` (default: x's) keeps the float32 result for a reader that
    must not see the rounding (a router)."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    y = y * (p.scale.astype(jnp.float32) + scale_offset)
    return y.astype(x.dtype if out_dtype is None else out_dtype)


# -- spec builders ------------------------------------------------------------


def linear_specs(kind: str) -> LinearParams:
    """PartitionSpecs for a linear of the given parallel kind."""
    if kind == "column":
        return LinearParams(w=P(None, AXIS_TP), b=P(AXIS_TP))
    if kind == "row":
        return LinearParams(w=P(AXIS_TP, None), b=P())
    if kind == "full":
        return LinearParams(w=P(), b=P())
    raise ValueError(f"unknown linear kind {kind!r}")


# -- loaders ------------------------------------------------------------------


def load_linear(
    ckpt: CheckpointShards,
    prefix: str | Sequence[str],
    mesh: Mesh,
    kind: str,
    *,
    transpose: bool = True,
    bias: bool = True,
) -> LinearParams:
    """Load a (possibly fused) linear with per-shard sliced reads.

    ``prefix`` may be a list for fused loads (e.g. q/k/v →
    ``get_multi_weights_col``, ``weights.py:108-111``). ``transpose=True`` for
    torch ``nn.Linear`` checkpoints; ``False`` for Conv1D ([in, out]) ones.
    """
    specs = linear_specs(kind)
    prefixes = [prefix] if isinstance(prefix, str) else list(prefix)
    wnames = [f"{p}.weight" for p in prefixes]
    # In [in, out] layout the output axis is 1; fused loads concat outputs.
    if len(wnames) == 1:
        w = ckpt.get_array(wnames[0], mesh, specs.w, transpose=transpose)
    else:
        w = ckpt.get_concat_array(
            wnames, 1, mesh, specs.w, transpose=transpose
        )
    b = None
    if bias:
        bnames = [f"{p}.bias" for p in prefixes]
        if all(n in ckpt for n in bnames):
            if len(bnames) == 1:
                b = ckpt.get_array(bnames[0], mesh, specs.b)
            else:
                b = ckpt.get_concat_array(bnames, 0, mesh, specs.b)
    return LinearParams(w=w, b=b)


def load_embedding(
    ckpt: CheckpointShards,
    name: str,
    mesh: Mesh,
    *,
    shard_vocab: bool = True,
) -> jax.Array:
    """Load an embedding table, vocab-partitioned over tp by default
    (≙ TensorParallelEmbedding.load, layers.py:183-201 — without the manual
    null-row pad: uneven shards are handled by the runtime)."""
    spec = P(AXIS_TP, None) if shard_vocab else P()
    return ckpt.get_array(name, mesh, spec)


def load_lm_head(
    ckpt: CheckpointShards,
    name: str,
    mesh: Mesh,
    *,
    transpose: bool,
    bias: bool = False,
) -> LinearParams:
    """Vocab-sharded head (≙ TensorParallelHead.load, layers.py:85-104).

    For tied embeddings (GPT-BigCode ``transformer.wte`` → head,
    ``gpt_bigcode_modeling.py:792-797``) pass the embedding's name with
    ``transpose=False`` semantics handled by the caller.
    """
    w = ckpt.get_array(name, mesh, P(None, AXIS_TP), transpose=transpose)
    b = None
    if bias:
        bname = name.rsplit(".", 1)[0] + ".bias"
        if bname in ckpt:
            b = ckpt.get_array(bname, mesh, P(AXIS_TP))
    return LinearParams(w=w, b=b)


def load_norm(
    ckpt: CheckpointShards, prefix: str, mesh: Mesh, *, bias: bool = True
) -> NormParams:
    """Replicated norm params (≙ LayerNorm.load monkey-patch,
    layers.py:12-36)."""
    scale = ckpt.get_array(f"{prefix}.weight", mesh, P())
    b = None
    if bias and f"{prefix}.bias" in ckpt:
        b = ckpt.get_array(f"{prefix}.bias", mesh, P())
    return NormParams(scale=scale, bias=b)
