"""The gated delta rule of a decode step or a mixed step, on the state pool
where it lies.

The XLA oracles (``ops.gdn.gdn_step`` at one position, ``ops.gdn.gdn_chunked``
at a chunk's few) are handed ONE LAYER of the pool, which the layer scan
slices out (a copy) and updates back (another pass), and between the two the
read ``S'^T k`` and the rank-one write each pass over the state again. This
kernel reads a live row's state once and writes it once, and leaves a row
that is done where it is:

* the pool stays in HBM as stored (``f32[L, rows, H, Dk, Dv]``) and is
  ALIASED to the kernel's result; the layer is a scalar-prefetched index of
  the pool's block map, so the other layers are never touched;
* the grid walks a scalar-prefetched LIST OF LIVE ROWS (``live_rows``): grid
  step ``i`` owns row ``rows[i]``'s block of ``Hb`` heads (``[Hb, Dk, Dv]``).
  Past the last live row the block map stays on the last live row's last
  block and the body does nothing: no fetch, no write-back, so a row of
  length 0 is never moved;
* on the tile, head by head, the row's OWN count ``lens[b]`` of positions
  (1 for a row that decodes, up to ``T`` for a row fed a chunk) of ::

      S' = exp(g_t) S        u = beta_t (v_t - S'^T k_t)
      S  = S' + k_t u^T      o_t = S^T q_t

  with the head's state held as a value from its first position to its last,
  ``_INTERLEAVE`` heads side by side (a position is a chain of two sums with
  a write between: one head alone waits on it). Positions at or after a
  row's length leave the state untouched and their ``o`` is zero, as is
  every ``o`` of a row that is done.

All float32, on the vector unit: the state, both reads of it (sums over
``Dk``, along sublanes) and the rank-one write. ``q`` and ``k`` arrive a
VALUE head each (grouped value heads: repeated by the caller, as the XLA
path does), one head a row as the mixer computes them, and meet the state as
columns: one ``[128, Dk]`` transpose a position for all the block's heads,
q's and k's together.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MAX_CHUNK = 8  # positions a row: a step's few, not an admission's scan
_LANES = 128
_MAX_HEADS = _LANES // 2  # a block's q and k heads share one transpose
# Heads whose positions run side by side in one loop: a position is two
# dependent sums over Dk with a rank-one write between, and one head alone
# waits on them (my chip run, PR 45, every row fed its whole chunk, ms a
# layer at 1 / 2 / 4 / 8 heads: 2.73 / 1.97 / 1.64 / 1.63 at 32 x 128 x 128
# and 8 positions, 1.33 / 1.04 / 0.98 / 1.02 at 30 x 96 x 192 and 4).
_INTERLEAVE = 4
_VMEM_BUDGET = 24 * 2**20  # of a v5e's 128 MiB; the call scopes 32
_VMEM_LIMIT = 32 * 2**20


def _pad(n: int, to: int) -> int:
    return -(-n // to) * to


def _vmem_bytes(hb: int, chunk: int, key_dim: int, value_dim: int) -> int:
    dv, dk = _pad(value_dim, _LANES), _pad(key_dim, _LANES)
    tile = hb * _pad(key_dim, 8) * dv * 4
    return (
        4 * tile  # the state's block in and out, double-buffered
        + 2 * 2 * chunk * _pad(hb, 8) * dk * 4  # q and k in
        + 2 * 2 * chunk * _pad(hb, 8) * dv * 4  # v in, o out
        + _LANES * dk * 4  # q and k padded to a whole transpose
        + chunk * _pad(key_dim, 8) * _LANES * 4  # their columns, a position
    )


def _head_block(n_heads: int, chunk: int, key_dim: int, value_dim: int) -> int:
    """Heads a grid step owns: the whole row where that fits the budget (one
    grid step a row, every index static), else the most whole sublane tiles
    of heads that do; 0 where not even 8 heads fit."""
    sizes = [n_heads] + list(range((n_heads - 1) // 8 * 8, 0, -8))
    for hb in sizes:
        if hb <= _MAX_HEADS and _vmem_bytes(
            hb, chunk, key_dim, value_dim
        ) <= _VMEM_BUDGET:
            return hb
    return 0


def supports(n_heads: int, key_dim: int, value_dim: int, chunk: int,
             dtype=jnp.float32) -> bool:
    """Whether the kernel takes these shapes: a float32 state whose
    ``key_dim`` tiles the sublanes and fits one transpose (``value_dim`` may
    be any width: the pool pads it to whole lane tiles as stored), at most
    ``_MAX_CHUNK`` positions a row, and a block of heads whose working set
    is within ``_VMEM_BUDGET``."""
    if jnp.dtype(dtype) != jnp.dtype(jnp.float32):
        return False
    return (
        n_heads > 0 and value_dim > 0
        and key_dim % 8 == 0
        and 0 < key_dim <= _LANES
        and 0 < chunk <= _MAX_CHUNK
        and _head_block(n_heads, chunk, key_dim, value_dim) > 0
    )


def live_rows(lens: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The rows with a position to run, in order, as the kernel's grid walks
    them: ``rows`` [B] (past the live ones, the last live row again; all 0
    where none is live) and how many are live, [1]. One list a step serves
    every layer."""
    B = lens.shape[0]
    live = lens > 0
    n = jnp.sum(live.astype(jnp.int32))
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    last = order[jnp.maximum(n - 1, 0)]
    rows = jnp.where(jnp.arange(B, dtype=jnp.int32) < n, order, last)
    return jnp.where(n > 0, rows, 0), n.reshape(1)


def _kernel(
    layer_ref,  # [1] — layer of the pool (read by the block maps)
    rows_ref,  # [B] — the live rows, then the last of them again
    n_live_ref,  # [1]
    lens_ref,  # [B] — positions of the chunk that are real, a row
    decay_ref,  # [B * T * H] f32 — exp(g)
    beta_ref,  # [B * T * H] f32
    q_ref,  # [1, T, Hb, Dk]
    k_ref,  # [1, T, Hb, Dk]
    v_ref,  # [1, T, Hb, Dv]
    s_ref,  # [1, 1, Hb, Dk, Dv] — the row's block of heads, as stored
    so_ref,  # [1, 1, Hb, Dk, Dv] — the same block of the aliased result
    o_ref,  # [1, T, Hb, Dv]
    pad_ref,  # [128, Dk]
    col_ref,  # [T, Dk, 128] — column h: q of head h; Hb + h: k of head h
    *,
    heads: int,
):
    del layer_ref
    i, j = pl.program_id(0), pl.program_id(1)
    T, Hb, _ = q_ref.shape[1:]
    n_live = n_live_ref[0]

    @pl.when((n_live == 0) & (i == 0) & (j == 0))
    def _():
        # no row is live: the one block the maps name is written back
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n_live)
    def _():
        b = rows_ref[i]
        n = lens_ref[b]
        o_ref[...] = jnp.zeros_like(o_ref)

        def columns(t, carry):
            pad_ref[:Hb] = q_ref[0, t]
            pad_ref[Hb:2 * Hb] = k_ref[0, t]
            col_ref[t] = pad_ref[...].T
            return carry

        jax.lax.fori_loop(0, n, columns, 0)

        for h0 in range(0, Hb, _INTERLEAVE):
            hs = range(h0, min(h0 + _INTERLEAVE, Hb))
            # the last block may hang over the heads: its overhang reads
            # the last head's scalars and is dropped on the way out
            at0 = [jnp.minimum(j * Hb + h, heads - 1) for h in hs]

            def position(t, states, hs=hs, at0=at0):
                new = []
                for h, head, s in zip(hs, at0, states):
                    at = (b * T + t) * heads + head
                    kc = col_ref[t, :, Hb + h:Hb + h + 1]  # [Dk, 1]
                    s = s * decay_ref[at]
                    u = beta_ref[at] * (
                        v_ref[0, t, h:h + 1, :]
                        - jnp.sum(s * kc, axis=0, keepdims=True)
                    )
                    s = s + kc * u
                    o_ref[0, t, h:h + 1, :] = jnp.sum(
                        s * col_ref[t, :, h:h + 1], axis=0, keepdims=True
                    )
                    new.append(s)
                return tuple(new)

            states = jax.lax.fori_loop(
                0, n, position, tuple(s_ref[0, 0, h] for h in hs)
            )
            for h, s in zip(hs, states):
                so_ref[0, 0, h] = s


@functools.partial(jax.jit, static_argnames=("interpret",))
def gdn_pool_update(
    pool: jax.Array,  # [L, rows, H, Dk, Dv] f32 — the state pool, as stored
    q: jax.Array,  # [B, T, H, Dk] — B == rows: batch row i IS pool row i
    k: jax.Array,  # [B, T, H, Dk] — a VALUE head each, as q
    v: jax.Array,  # [B, T, H, Dv]
    g: jax.Array,  # [B, T, H] — <= 0
    beta: jax.Array,  # [B, T, H]
    lens: jax.Array,  # [B] — positions that are real (0: the row is done)
    live: tuple[jax.Array, jax.Array],  # ``live_rows(lens)``
    layer: jax.Array,  # int32 scalar — layer of the pool to update
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """``lens[b]`` positions of the recurrence for every row ``b``, from and
    to layer ``layer`` of ``pool``; all float32. Returns ``o`` [B, T, H, Dv]
    and the pool (the operand's buffer where it was donated). The contract
    of ``ops.gdn.gdn_step`` (``T == 1``) and ``ops.gdn.gdn_chunked`` on
    ``pool[layer]`` with ``g`` and ``beta`` zero from ``lens`` on, on every
    position before ``lens``; the ``o`` of a later position is zero."""
    L, rows, H, Dk, Dv = pool.shape
    B, T = q.shape[:2]
    if B != rows or k.shape != q.shape or not supports(
        H, Dk, Dv, T, pool.dtype
    ):
        raise ValueError(
            "out of the delta rule kernel's envelope: pool "
            f"{pool.shape} {pool.dtype}, q {q.shape}, k {k.shape}"
        )
    Hb = _head_block(H, T, Dk, Dv)
    nj = -(-H // Hb)
    f32 = jnp.float32
    row_list, n_live = live

    def block(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)

    def at(i, j, rows_ref, n_ref):
        """The row and the block of heads grid step ``(i, j)`` owns: past
        the live rows, the last block of the last of them, again."""
        return rows_ref[i], jnp.where(i < n_ref[0], j, nj - 1)

    def heads(i, j, l, r, n, *_):
        b, j = at(i, j, r, n)
        return b, 0, j, 0

    def state(i, j, l, r, n, *_):
        b, j = at(i, j, r, n)
        return l[0], b, j, 0, 0

    key = block((1, T, Hb, Dk), heads)
    value = block((1, T, Hb, Dv), heads)
    tile = block((1, 1, Hb, Dk, Dv), state)

    pool, o = pl.pallas_call(
        functools.partial(_kernel, heads=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(B, nj),
            in_specs=[key, key, value, tile],
            out_specs=[tile, value],
            scratch_shapes=[
                pltpu.VMEM((_LANES, Dk), f32),
                pltpu.VMEM((T, Dk, _LANES), f32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
            jax.ShapeDtypeStruct((B, T, H, Dv), f32),
        ],
        # the pool is operand 9 of the call, the six prefetched included
        input_output_aliases={9: 0},
        compiler_params=pltpu.CompilerParams(
            # a block past the live rows is the last live one revisited
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        cost_estimate=pl.CostEstimate(
            flops=8 * B * T * H * Dk * Dv, transcendentals=0,
            bytes_accessed=2 * B * H * Dk * Dv * 4,
        ),
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        row_list.astype(jnp.int32), n_live.astype(jnp.int32),
        lens.astype(jnp.int32).reshape(B),
        jnp.exp(g.astype(f32)).reshape(-1), beta.astype(f32).reshape(-1),
        q.astype(f32), k.astype(f32), v.astype(f32), pool,
    )
    # a row that is done was never visited: what lies there is not a result
    return jnp.where((lens > 0)[:, None, None, None], o, 0.0), pool
