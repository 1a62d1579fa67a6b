"""The gated delta rule (Yang, Kautz, Hatamizadeh, "Gated Delta Networks",
2024): the one-step update that decode runs and the chunked form that prefill
and the mixed step run.

The recurrence, per head with state ``S`` of ``[Dk, Dv]``, keys of unit
length::

    S' = exp(g_t) * S_{t-1}                g_t <= 0
    u  = beta_t * (v_t - S'^T k_t)         what the state lacks of v_t
    S_t = S' + k_t u^T
    o_t = S_t^T q_t

A position with ``g_t == 0`` and ``beta_t == 0`` leaves the state untouched:
that is how padded positions of a bucketed prompt, columns of a mixed step
past a row's chunk, and rows that are done pass through both forms without a
branch. The state is float32 throughout, and every product that reads or
writes it is a float32 product: elementwise in the one-step form and in the
chunked form at a chunk of a few positions, matmuls at ``Precision.HIGHEST``
otherwise (the default would round their operands to bfloat16 on the chip,
which is a bfloat16 state by another name).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
# Positions a chunk of the chunked form (one triangular solve of this size)
CHUNK = 64
# Chunks up to this many positions multiply against the state elementwise
_ELEMENTWISE_CHUNK = 8


def l2_normalize(x, eps: float = 1e-6):
    """``x / sqrt(|x|^2 + eps)`` over the last axis."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def gdn_step(q, k, v, g, beta, state):
    """One position of the recurrence for every row: the decode update.
    ``q``/``k`` [B, H, Dk], ``v`` [B, H, Dv], ``g``/``beta`` [B, H] (both 0
    for a row that is done), ``state`` [B, H, Dk, Dv]; all float32. Two
    dependent passes over the state (``S'^T k``, then the rank-one write
    with the read-out): bandwidth-bound. Returns ``o`` [B, H, Dv] and the new
    state."""
    s = state * jnp.exp(g)[..., None, None]
    u = beta[..., None] * (v - jnp.sum(s * k[..., None], axis=-2))
    s = s + k[..., None] * u[..., None, :]
    return jnp.sum(s * q[..., None], axis=-2), s


def gdn_chunked(q, k, v, g, beta, state, chunk: int = CHUNK):
    """The recurrence over ``T`` positions, ``chunk`` at a time, in the WY
    form: inside a chunk the corrections ``u`` of all positions solve ONE
    unit lower-triangular system (``(I + tril(beta K K^T * decay, -1)) U =
    beta V`` against the incoming state), so a chunk is matmuls and a
    triangular solve; between chunks only the state is passed (a
    ``lax.scan``, unrolled).

    ``q``/``k`` [B, T, H, Dk], ``v`` [B, T, H, Dv], ``g``/``beta`` [B, T, H]
    (both 0 where the position is padding), ``state`` [B, H, Dk, Dv]; all
    float32. ``T`` need not be a multiple of ``chunk``: the tail is padded
    with no-ops. Returns ``o`` [B, T, H, Dv] and the state after the last
    position."""
    B, T, H, _ = q.shape
    C = min(chunk, T)
    N = -(-T // C)
    pad = N * C - T

    def chunks(a):
        """[B, T, H, ...] -> [N, B, H, C, ...]"""
        if pad:
            a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        a = a.reshape(B, N, C, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    q, k, v, g, beta = (chunks(a) for a in (q, k, v, g, beta))
    cum = jnp.cumsum(g, axis=-1)  # [N, B, H, C], <= 0
    tri = jnp.tril(jnp.ones((C, C), bool))
    # decay from position j (exclusive) to i (inclusive), j <= i; the mask
    # goes in before the exponential, whose argument is positive above the
    # diagonal
    decay = jnp.exp(
        jnp.where(tri, cum[..., :, None] - cum[..., None, :], -jnp.inf)
    )
    mm = lambda spec, a, b: jnp.einsum(spec, a, b, precision=_HI)
    kb = k * beta[..., None]
    A = jnp.where(
        jnp.tril(tri, -1), mm("...ik,...jk->...ij", kb, k) * decay, 0.0
    )
    rhs = jnp.concatenate(
        [kb * jnp.exp(cum)[..., None], v * beta[..., None]], axis=-1
    )
    wu = jax.lax.linalg.triangular_solve(
        A + jnp.eye(C, dtype=A.dtype), rhs, left_side=True, lower=True,
        unit_diagonal=True,
    )
    Dk = k.shape[-1]
    w, u = wu[..., :Dk], wu[..., Dk:]
    qk = mm("...ik,...jk->...ij", q, k) * decay  # zero above the diagonal
    # one read of the state for both products against it
    wq = jnp.concatenate([w, q * jnp.exp(cum)[..., None]], axis=-2)
    k_end = k * jnp.exp(cum[..., -1:] - cum)[..., None]
    a_end = jnp.exp(cum[..., -1])

    if C <= _ELEMENTWISE_CHUNK:
        # A few positions (a mixed step's chunk): the products against the
        # state as multiply-and-add on the vector unit, exact in float32 and
        # fused into one pass each (the write a plain sum of ``C`` outer
        # products, which fuses into the state's in-place update; as a
        # reduction it is a state-sized array of its own); a float32 matmul
        # of 6 bfloat16 passes splits the whole state into three arrays first.
        read = lambda a, S: jnp.sum(a[..., :, None] * S[..., None, :, :], -2)
        write = lambda a, b: sum(
            a[..., c, :, None] * b[..., c, None, :] for c in range(C)
        )
    else:
        read = lambda a, S: mm("bhck,bhkv->bhcv", a, S)
        write = lambda a, b: mm("bhck,bhcv->bhkv", a, b)

    def step(S, inp):  # S [B, H, Dk, Dv]
        wq_i, u_i, qk_i, ke_i, ae_i = inp
        ws = read(wq_i, S)
        v_new = u_i - ws[..., :C, :]
        o = ws[..., C:, :] + mm("bhij,bhjv->bhiv", qk_i, v_new)
        S = S * ae_i[..., None, None] + write(ke_i, v_new)
        return S, o

    chunked = (wq, u, qk, k_end, a_end)
    if N == 1:
        # no loop around one chunk: a loop's carry is a copy of the state
        state, o = step(state, jax.tree.map(lambda a: a[0], chunked))
        o = o[None]
    else:
        # unrolled: as a loop nested in the layer scan, eight chunks of 64
        # (a 512-token prefill) compiled to a program that never returned
        # on a v5e, where seven chunks, or one period of layers, ran
        state, o = jax.lax.scan(step, state, chunked, unroll=True)
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2)  # [B, N, C, H, Dv]
    return o.reshape(B, N * C, H, -1)[:, :T], state
