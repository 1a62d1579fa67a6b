"""Routed experts (``MoEConfig``) of two families: a float32 router over ALL
the model's experts, sigmoid scores chosen by score plus a selection-only
bias (deepseek_v3, ``route``) or a softmax and its largest (qwen3_next,
``route_softmax``), and ONE dropless grouped matmul over the (token, expert)
pairs sorted by expert, of the experts HELD here: all of them, or one chip's
share of an expert-parallel deployment (``routed_experts``' ``first``). A
pair whose expert is held elsewhere is a dead pair like those of a token
that is not live, and what the absent experts would have added is left out:
the exchange that would fetch it is a mesh axis this repo does not have yet,
and nothing stands in for it.

The same function serves prefill, decode and the mixed step: it sees a flat
list of tokens and a ``live`` flag a token. A token that is not live (a row
that is done, a bucket's padding row, a chunk's padding column) adds NO
pair: its pairs carry the expert id ``n_experts``, sort behind the last
group, belong to no group and are multiplied with nothing, so no expert's
weights are read for a token nobody asked for, and what such a token gets
back is zero.

No capacity and no dropped token: the groups are as long as the routing
makes them. The grouped matmul is JAX's own Pallas kernel for the TPU
(``jax.experimental.pallas.ops.tpu.megablox.gmm``: the group sizes are data,
a tile of rows meets only the experts that own rows in it), compiled on the
chip and interpreted on the CPU like the repo's other kernels
(``ops.attention.pallas_interpret``). ``jax.lax.ragged_dot`` was measured
beside it on a v5e at the deployment's widths (128 experts of 2048 x 768,
three matmuls a layer): 2.73 ms against 1.80 at the 384 pairs of a 64-row
decode step (the experts' bytes alone take 1.2), 4.97 against 2.04 at 1,536
pairs, and at a prefill's 24,576 pairs XLA unrolled it into a 2.4 GB
executable that did not finish compiling in ten minutes (my chip run, PR
38). So there is one form, for every size.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def route(x, router_w, bias, *, top_k: int, norm: bool, scale: float):
    """``x`` [T, E] -> ``(experts [T, k] int32, weights [T, k] float32)``.

    Scores are ``sigmoid(x W^T)`` in float32 whatever the compute dtype (as
    published: a router in bfloat16 would tie scores that are not tied).
    ``bias`` (``e_score_correction_bias``) takes part in the SELECTION only;
    the weights are the chosen experts' own scores, renormalised over the
    chosen (``norm``) and times ``scale`` (``routed_scaling_factor``)."""
    f32 = jnp.float32
    s = jax.nn.sigmoid(
        jnp.einsum("te,ne->tn", x.astype(f32), router_w.astype(f32),
                   precision=jax.lax.Precision.HIGHEST)
    )
    _, idx = jax.lax.top_k(s + bias.astype(f32), top_k)
    w = jnp.take_along_axis(s, idx, axis=1)
    if norm:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scale


def route_softmax(x, router_w, *, top_k: int, norm: bool):
    """``x`` [T, E] -> ``(experts [T, k] int32, weights [T, k] float32)``:
    ``softmax(x W^T)`` over ALL the experts in float32 whatever the compute
    dtype, the ``top_k`` largest, their probabilities renormalised over the
    chosen (``norm``: ``norm_topk_prob``). No bias, no scaling factor."""
    f32 = jnp.float32
    p = jax.nn.softmax(
        jnp.einsum("te,ne->tn", x.astype(f32), router_w.astype(f32),
                   precision=jax.lax.Precision.HIGHEST),
        axis=-1,
    )
    w, idx = jax.lax.top_k(p, top_k)
    if norm:
        w = w / jnp.sum(w, axis=1, keepdims=True)
    return idx.astype(jnp.int32), w


#: Rows a tile of the grouped matmul holds: the pairs are padded up to a
#: multiple of it (dead rows, behind the last group).
TILE_M = 128


def _grouped_matmul(lhs, rhs, group_sizes):
    """``lhs`` [M, K] x ``rhs`` [G, K, N] by groups of rows -> [M, N] in
    lhs's dtype, accumulated in float32. Tiles: 128 rows, up to 1024 of K
    and 768 of N (the fastest of those tried at the deployment's widths; a
    smaller matrix is one tile)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from llmss_tpu.ops.attention import pallas_interpret

    tiling = (TILE_M, min(1024, rhs.shape[1]), min(768, rhs.shape[2]))
    return gmm(
        lhs, rhs.astype(lhs.dtype), group_sizes, lhs.dtype, tiling,
        interpret=pallas_interpret(),
    )


def grouped_ffn(xs, group_sizes, gate, up, down, act):
    """SwiGLU of every row of ``xs`` [M, E] with its group's expert: rows
    ``[sum(sizes[:g]), sum(sizes[:g+1]))`` meet ``gate[g]``, ``up[g]``
    [E, I] and ``down[g]`` [I, E]. Rows behind the last group meet nothing
    (their output is whatever the kernel leaves: the caller masks them)."""
    g = _grouped_matmul(xs, gate, group_sizes)
    u = _grouped_matmul(xs, up, group_sizes)
    return _grouped_matmul(act(g) * u, down, group_sizes)


def routed_experts(x, idx, w, live, gate, up, down, act, layer=None,
                   first=None):
    """The weighted sum of each token's chosen experts, of those held here.

    ``x`` [T, E]; ``idx``, ``w`` [T, k] from ``route``; ``live`` [T] bool;
    ``gate``/``up`` [N, E, I], ``down`` [N, I, E]. Returns ``(y [T, E] in
    x's dtype, counts [3] int32)``: ``counts`` is ``(pairs, experts_hit,
    pairs_elsewhere)``, the live (token, expert) pairs computed here, the
    experts with at least one, and the live pairs left out because their
    expert is held elsewhere.

    ``first`` None: the N experts of the weights are all the model has.
    Else they are the model's experts ``[first, first + N)``, one chip's
    share: ``idx`` still names experts of the whole model, a pair outside
    the range is dead (no weights read, zero back) and is counted.

    With ``layer`` (a traced scalar) the weights are the STACKED experts of
    all layers, ``[L, N, E, I]`` / ``[L, N, I, E]``, and the groups are the
    experts of that layer: the kernel sees ``L x N`` groups of which all
    but N are empty and reads the layer's weights in place. A layer scan
    that hands over a layer's slice makes XLA copy the slice out of the
    stack for the kernel first: three copies of 403 MB a layer a step at
    the deployment's widths, 27% of the device (my chip run, PR 38)."""
    T, K = idx.shape
    if layer is not None:
        L, N = gate.shape[:2]
        gate, up, down = (
            a.reshape((L * N,) + a.shape[2:]) for a in (gate, up, down)
        )
    else:
        N = gate.shape[0]
    live = live[:, None]
    elsewhere = jnp.zeros((), jnp.int32)
    if first is not None:
        idx = idx - first
        mine = (idx >= 0) & (idx < N)
        elsewhere = jnp.sum((live & ~mine).astype(jnp.int32))
        live = live & mine
    e = jnp.where(live, idx, N).reshape(T * K)
    # whole tiles of rows: the padding pairs are dead pairs too
    e = jnp.pad(e, (0, -(T * K) % TILE_M), constant_values=N)
    order = jnp.argsort(e, stable=True)  # pairs by expert, the dead last
    sizes = jnp.sum(
        e[:, None] == jnp.arange(N, dtype=e.dtype)[None, :], axis=0,
        dtype=jnp.int32,
    )
    pairs = jnp.sum(sizes)
    groups = sizes
    if layer is not None:
        groups = jax.lax.dynamic_update_slice(
            jnp.zeros(gate.shape[0], sizes.dtype), sizes, (layer * N,)
        )
    ys = grouped_ffn(
        x[jnp.minimum(order // K, T - 1)], groups, gate, up, down, act
    )
    ys = jnp.where((jnp.arange(e.shape[0]) < pairs)[:, None], ys, 0)
    # back to (token, choice) order: a gather through the inverse
    # permutation, then the weighted sum over the k choices in float32
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(e.shape[0], dtype=order.dtype)
    )
    y = jnp.einsum(
        "tk,tke->te", w,
        ys[inv[: T * K]].reshape(T, K, -1).astype(jnp.float32),
    )
    counts = jnp.stack(
        [pairs, jnp.sum((sizes > 0).astype(jnp.int32)), elsewhere]
    )
    return y.astype(x.dtype), counts
