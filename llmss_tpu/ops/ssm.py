"""Mamba-2 mixer numerics (Dao & Gu, "Transformers are SSMs", 2024): the
causal depthwise convolution with a carried window, the chunked scan that
prefill runs, and the one-step update that decode runs.

The recurrence, per head with state ``S`` of ``[P, N]``::

    a_t = exp(dt_t * A)            A < 0, dt_t >= 0
    S_t = a_t * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t @ C_t

A position with ``dt_t == 0`` leaves the state untouched (``a_t = 1``, nothing
added): that is how padded positions of a bucketed prompt, and rows that are
done, pass through both forms without a branch. The state is float32
throughout; ``B`` and ``C`` are shared by the ``H / G`` heads of a group.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_conv(x, window, w, b, lens):
    """Depthwise causal convolution over ``K`` steps.

    ``x`` [B, S, C] new inputs, ``window`` [B, K-1, C] the ``K-1`` inputs
    before them, ``w`` [K, C] (``w[K-1]`` multiplies the current input),
    ``b`` [C] or None, ``lens`` [B] how many of the ``S`` inputs are real.
    Returns the pre-activation output [B, S, C] in float32 and the new
    window: the ``K-1`` inputs that end at each row's TRUE length, so a
    padded row carries the window of its last real token and a row with
    ``lens == 0`` keeps the one it had."""
    K, S = w.shape[0], x.shape[1]
    xp = jnp.concatenate([window.astype(x.dtype), x], axis=1)
    xf, wf = xp.astype(jnp.float32), w.astype(jnp.float32)
    y = sum(xf[:, k:k + S] * wf[k] for k in range(K))
    if b is not None:
        y = y + b.astype(jnp.float32)
    idx = lens[:, None] + jnp.arange(K - 1, dtype=lens.dtype)[None]
    return y, jnp.take_along_axis(xp, idx[:, :, None], axis=1)


def ssd_scan(x, dt, A, Bm, Cm, state, chunk: int):
    """The recurrence over ``S`` positions, ``chunk`` at a time: inside a
    chunk the outputs are matmuls against a decay-masked ``C B^T``; between
    chunks only the float32 state is passed (a ``lax.scan`` over chunks).

    ``x`` [B, S, H, P], ``dt`` [B, S, H] (0 where the position is padding),
    ``A`` [H], ``Bm``/``Cm`` [B, S, G, N], ``state`` [B, H, P, N]; all
    float32. ``S`` need not be a multiple of ``chunk``: the tail is padded
    with ``dt == 0``. Returns ``y`` [B, S, H, P] and the state after the
    last position."""
    B_, S, H, P = x.shape
    G, N = Bm.shape[2:]
    Hg = H // G
    Q = min(chunk, S)
    nC = -(-S // Q)
    pad = nC * Q - S

    def chunks(a, *feat):
        if pad:
            a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        return jnp.moveaxis(a.reshape(B_, nC, Q, *feat), 1, 0)

    xs = (chunks(x, G, Hg, P), chunks(dt, G, Hg), chunks(Bm, G, N),
          chunks(Cm, G, N))
    Ag = A.reshape(G, Hg)
    tri = jnp.tril(jnp.ones((Q, Q), bool))

    def step(S0, inp):  # S0 [B, G, Hg, P, N]
        xc, dtc, Bc, Cc = inp
        cum = jnp.cumsum(dtc * Ag, axis=1)  # [B, Q, G, Hg], <= 0
        cum_h = jnp.moveaxis(cum, 1, -1)  # [B, G, Hg, Q]
        # decay from position s (exclusive) to t (inclusive), s <= t; the
        # mask goes in before the exponential, whose argument is positive
        # above the diagonal
        seg = cum_h[..., :, None] - cum_h[..., None, :]
        L = jnp.exp(jnp.where(tri, seg, -jnp.inf))  # [B, G, Hg, Q, Q]
        CB = jnp.einsum("bqgn,bsgn->bgqs", Cc, Bc)
        dtx = jnp.moveaxis(xc * dtc[..., None], 1, 3)  # [B, G, Hg, Q, P]
        y = jnp.einsum("bghqs,bghsp->bghqp", L * CB[:, :, None], dtx)
        y = y + jnp.einsum("bqgn,bghpn->bghqp", Cc, S0) * jnp.exp(
            cum_h)[..., None]
        to_end = jnp.exp(cum_h[..., -1:] - cum_h)  # [B, G, Hg, Q]
        S1 = S0 * jnp.exp(cum_h[..., -1])[..., None, None] + jnp.einsum(
            "bghsp,bsgn->bghpn", dtx * to_end[..., None], Bc)
        return S1, jnp.moveaxis(y, 3, 1)  # [B, Q, G, Hg, P]

    state, ys = jax.lax.scan(step, state.reshape(B_, G, Hg, P, N), xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(B_, nC * Q, H, P)[:, :S]
    return y, state.reshape(B_, H, P, N)


def ssm_step(x, dt, A, Bm, Cm, state):
    """One position of the recurrence for every row: the decode update.
    ``x`` [B, H, P], ``dt`` [B, H] (0 for a row that is done), ``A`` [H],
    ``Bm``/``Cm`` [B, G, N], ``state`` [B, H, P, N]; all float32. Returns
    ``y`` [B, H, P] and the new state.

    The ORACLE of the decode update (the CPU path, the tests' reference, and
    what a step runs where ``models.decoder.state_update`` says ``xla``), not
    its one-pass form: as XLA compiles it inside the layer scan the state is
    passed over about 3.5 times a step (the layer's slice copied out of the
    pool, the decay and outer product, the read-out, the update back: ledger,
    PR 42, cell 2). The form that reads and writes the state once, where it
    lies in the pool, is ``ops.pallas_ssm.ssm_pool_update``."""
    B_, H, P, N = state.shape
    G = Bm.shape[1]
    s = state.reshape(B_, G, H // G, P, N)
    a = jnp.exp(dt * A).reshape(B_, G, H // G)
    dtx = (x * dt[..., None]).reshape(B_, G, H // G, P)
    s = s * a[..., None, None] + dtx[..., None] * Bm[:, :, None, None, :]
    y = jnp.einsum("bghpn,bgn->bghp", s, Cm)
    return y.reshape(B_, H, P), s.reshape(B_, H, P, N)
