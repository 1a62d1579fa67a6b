"""The Mamba-2 recurrence of a decode step or a mixed step, on the state pool
where it lies.

The XLA oracles (``ops.ssm.ssm_step`` at one position, ``ops.ssm.ssd_scan``
at a chunk's few) are handed ONE LAYER of the pool, which the layer scan
slices out (a copy) and updates back (another pass), and between the two the
decay, the outer product and the read-out each pass over the state again.
This kernel reads a layer's state once and writes it once:

* the pool stays in HBM as stored (``f32[L, rows, H, P, N]``) and is ALIASED
  to the kernel's result; the layer is a scalar-prefetched index of the
  pool's block map, so the other layers are never touched;
* a grid step owns one row's block of ``Hb`` heads (``[Hb, P, N]``): the
  pipeline brings it into VMEM, the recurrence ::

      S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) (outer) B_t,   y_t = S_t C_t

  runs position by position on the tile, and the pipeline writes it back;
* the trip count is the ROW's: ``lens[b]`` positions (0 for a row that is
  done, 1 for a row that decodes in a mixed step, up to ``T`` for a row fed
  a chunk). Positions at or after a row's length leave the state untouched,
  as the oracle's ``dt == 0`` does, and their ``y`` is ZERO (the oracle's is
  ``S C_t``; neither is ever read: the position is padding). A row of length
  0 has its tile copied in VMEM, bit for bit, and written back: every row of
  the layer is still read and written each step.

All float32, on the vector unit: the state, both products, the sum over
``N``. ``x_t`` lies along lanes as the mixer computes it and meets the state
along sublanes, and ``y_t`` the other way round: one ``[128, P]`` transpose
a position each way, for all the block's heads at once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HEAD_BLOCK = 8  # heads a grid step owns: [8, 128, 256] float32 is 1 MiB
_MAX_CHUNK = 8  # positions a row: a step's few, not an admission's scan
_LANES = 128
_VMEM_BUDGET = 12 * 2**20  # under the 16 MiB a v5e kernel may scope


def _head_block(n_heads: int) -> int:
    return min(_HEAD_BLOCK, n_heads)


def _vmem_bytes(hb: int, chunk: int, head_dim: int, d_state: int,
                n_groups: int) -> int:
    tile = hb * head_dim * d_state * 4

    def sub(n):  # sublanes a block's second-minor axis pads to
        return -(-n // 8) * 8

    return (
        4 * tile  # the state's block in and out, double-buffered
        + 2 * 2 * chunk * sub(hb) * head_dim * 4  # x in, y out
        + 2 * 2 * chunk * sub(n_groups) * d_state * 4  # B and C
        + _LANES * head_dim * 4  # x padded to a whole transpose
    )


def supports(n_heads: int, head_dim: int, d_state: int, n_groups: int,
             chunk: int, dtype=jnp.float32) -> bool:
    """Whether the kernel takes these shapes: a float32 state whose rows are
    whole lanes (``d_state``) and whose ``head_dim`` tiles the sublanes and
    fits one transpose, heads in whole groups, at most ``_MAX_CHUNK``
    positions a row, and a working set within ``_VMEM_BUDGET``."""
    if jnp.dtype(dtype) != jnp.dtype(jnp.float32):
        return False
    return (
        d_state % _LANES == 0
        and head_dim % 8 == 0
        and 0 < head_dim <= _LANES
        and n_groups > 0
        and n_heads % n_groups == 0
        and 0 < chunk <= _MAX_CHUNK
        and _vmem_bytes(
            _head_block(n_heads), chunk, head_dim, d_state, n_groups
        ) <= _VMEM_BUDGET
    )


def _kernel(
    layer_ref,  # [1] — layer of the pool (read by the block maps)
    lens_ref,  # [B] — positions of the chunk that are real, a row
    decay_ref,  # [B * T * H] f32 — exp(dt A)
    x_ref,  # [1, T, Hb, P] — dt * x
    b_ref,  # [1, T, G, N]
    c_ref,  # [1, T, G, N]
    s_ref,  # [1, 1, Hb, P, N] — the row's block of heads, as stored
    o_ref,  # [1, 1, Hb, P, N] — the same block of the aliased result
    y_ref,  # [1, T, Hb, P]
    xpad_ref,  # [128, P]
    *,
    heads: int,
    groups: int,
):
    del layer_ref
    b, j = pl.program_id(0), pl.program_id(1)
    T, Hb, P = x_ref.shape[1:]
    n = lens_ref[b]
    lane = jax.lax.broadcasted_iota(jnp.int32, (P, _LANES), 1)

    def position(t, src):
        """Position ``t`` of the row: ``src`` (the block as it came, or as
        the position before left it) to ``o_ref``, and ``y_ref[0, t]``."""
        # the block's x, one head a row, as one head a column
        xpad_ref[:Hb] = x_ref[0, t]
        xT = xpad_ref[...].T  # [P, 128]
        yc = jnp.zeros((P, _LANES), jnp.float32)
        for h in range(Hb):
            # the last block may hang over the heads: its overhang reads
            # the last head's scalars and is dropped on the way out
            head = jnp.minimum(j * Hb + h, heads - 1)
            g = head // (heads // groups)
            a = decay_ref[(b * T + t) * heads + head]
            s = src[0, 0, h] * a + xT[:, h:h + 1] * b_ref[0, t, pl.ds(g, 1), :]
            o_ref[0, 0, h] = s
            y = jnp.sum(s * c_ref[0, t, pl.ds(g, 1), :], axis=1, keepdims=True)
            yc = jnp.where(lane == h, y, yc)
        y_ref[0, t] = yc.T[:Hb]

    y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(n == 0)
    def _():
        o_ref[...] = s_ref[...]

    @pl.when(n > 0)
    def _():
        position(0, s_ref)

        def later(t, carry):
            position(t, o_ref)
            return carry

        jax.lax.fori_loop(1, n, later, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_pool_update(
    pool: jax.Array,  # [L, rows, H, P, N] f32 — the state pool, as stored
    x: jax.Array,  # [B, T, H, P] — B == rows: batch row i IS pool row i
    dt: jax.Array,  # [B, T, H] — 0 where a position is padding
    A: jax.Array,  # [H]
    Bm: jax.Array,  # [B, T, G, N]
    Cm: jax.Array,  # [B, T, G, N]
    lens: jax.Array,  # [B] — positions that are real (0: the row is done)
    layer: jax.Array,  # int32 scalar — layer of the pool to update
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """``lens[b]`` positions of the recurrence for every row ``b``, from and
    to layer ``layer`` of ``pool``; all float32. Returns ``y`` [B, T, H, P]
    and the pool (the operand's buffer where it was donated). The contract
    of ``ops.ssm.ssm_step`` (``T == 1``) and ``ops.ssm.ssd_scan`` on
    ``pool[layer]`` with ``dt`` zero from ``lens`` on, on every position
    before ``lens``; the ``y`` of a later position is zero."""
    L, rows, H, P, N = pool.shape
    B, T = x.shape[:2]
    G = Bm.shape[2]
    if B != rows or not supports(H, P, N, G, T, pool.dtype):
        raise ValueError(
            "out of the state update kernel's envelope: pool "
            f"{pool.shape} {pool.dtype}, x {x.shape}, B {Bm.shape}"
        )
    Hb = _head_block(H)
    f32 = jnp.float32
    dt = dt.astype(f32)
    decay = jnp.exp(dt * A.astype(f32))
    dtx = x.astype(f32) * dt[..., None]

    def block(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)

    heads = block((1, T, Hb, P), lambda b, j, *_: (b, 0, j, 0))
    group = block((1, T, G, N), lambda b, j, *_: (b, 0, 0, 0))
    state = block((1, 1, Hb, P, N), lambda b, j, l, *_: (l[0], b, j, 0, 0))

    pool, y = pl.pallas_call(
        functools.partial(_kernel, heads=H, groups=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, -(-H // Hb)),
            in_specs=[heads, group, group, state],
            out_specs=[state, heads],
            scratch_shapes=[pltpu.VMEM((_LANES, P), f32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
            jax.ShapeDtypeStruct((B, T, H, P), f32),
        ],
        # the pool is operand 6 of the call, the three prefetched included
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=6 * B * T * H * P * N, transcendentals=0,
            bytes_accessed=2 * B * H * P * N * 4,
        ),
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        lens.astype(jnp.int32).reshape(B), decay.reshape(-1),
        dtx, Bm.astype(f32), Cm.astype(f32), pool,
    )
    return y, pool
