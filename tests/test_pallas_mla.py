"""The latent pool's read kernel (``ops.pallas_mla``, interpret mode on the
CPU) against the XLA oracle ``ops.attention.ragged_paged_attention`` with
keys and values the same pool, at the latent's own shape: one 640-wide row a
token, 32 query heads, ``block_size`` 16."""

import importlib

import jax.numpy as jnp
import numpy as np
import pytest

from llmss_tpu.ops import pallas_mla

attn = importlib.import_module("llmss_tpu.ops.attention")

L, N, BS, W, H = 2, 96, 16, 640, 32
B, MB = 4, 72
RING = MB * BS  # 1,152 slots: two chunks of the walk and a quarter
SCALE = 192 ** -0.5
SENTINEL = N + 5

# Each case: the chunk budget, the tokens each row has been fed so far
# (``ctx``; more than RING: the ring has wrapped) and its live queries.
CASES = {
    # rows with q_len 0, 1 and 8 side by side, and one in between
    "cb8-qlen-0-1-8": dict(cb=8, ctx=[300, 45, 1040, 77], qlen=[0, 1, 8, 3]),
    # the decode step: the same call at CB == 1
    "cb1-decode": dict(cb=1, ctx=[300, 0, 1025, 512], qlen=[1, 1, 1, 1]),
    # rows with nothing cached: the fresh latents alone
    "nothing-cached": dict(cb=8, ctx=[0, 0, 5, 0], qlen=[8, 1, 2, 0]),
    # lengths around the chunk's 512 slots and the block's 16
    "odd-lengths": dict(cb=8, ctx=[511, 513, 1023, 1], qlen=[8, 8, 1, 1]),
    # wrapped rings: the pending slots hold older tokens, and slot order is
    # not position order
    "ring-wrapped": dict(
        cb=8, ctx=[1300, 2303, 1148, 1152], qlen=[8, 1, 8, 3]
    ),
    # rows 0 and 1 share their first four blocks, rows 2 and 3 two
    "shared-prefix": dict(
        cb=8, ctx=[100, 64, 200, 33], qlen=[3, 8, 1, 8], shared=True
    ),
    # the table past a row's blocks is the unmapped sentinel (>= N)
    "sentinel-entries": dict(
        cb=8, ctx=[300, 45, 0, 77], qlen=[8, 1, 8, 0], sentinel=True
    ),
    # a bucketed read: kv_pos narrower than the table, not a whole chunk
    "bucketed-read": dict(
        cb=1, ctx=[300, 45, 600, 77], qlen=[1, 1, 1, 1], t_bucket=608
    ),
    # the value is the row's leading 512 columns
    "v-dim-512": dict(
        cb=8, ctx=[300, 45, 1040, 77], qlen=[0, 1, 8, 3], v_dim=512
    ),
    # what the chip serves in; eight bits of mantissa: the probabilities
    # and the output are each rounded once, to 2**-8 relative
    "bfloat16": dict(
        cb=8, ctx=[300, 45, 1040, 1300], qlen=[2, 1, 8, 8],
        dtype=jnp.bfloat16, tol=2e-2,
    ),
    "bfloat16-cb1": dict(
        cb=1, ctx=[300, 0, 513, 1500], qlen=[1, 1, 1, 1],
        dtype=jnp.bfloat16, tol=2e-2, v_dim=512,
    ),
}


def _inputs(case, seed=0):
    rng = np.random.default_rng(seed)
    cb, dtype = case["cb"], case.get("dtype", jnp.float32)
    ctx, qlen = np.asarray(case["ctx"]), np.asarray(case["qlen"])
    # every row its own blocks, interleaved so a row's are not contiguous
    bt = (np.arange(MB)[None, :] * 2 + np.arange(B)[:, None] * 3) % N
    bt = bt.astype(np.int32)
    if case.get("shared"):
        bt[1, :4] = bt[0, :4]
        bt[3, :2] = bt[2, :2]
    kv_pos = np.full((B, RING), -1, np.int32)
    for b in range(B):
        # slot s holds the newest position p < ctx with p % RING == s
        pos = np.arange(max(ctx[b] - RING, 0), ctx[b])
        kv_pos[b, pos % RING] = pos
    used = -(-np.minimum(ctx, RING) // BS)
    if case.get("sentinel"):
        for b in range(B):
            bt[b, used[b]:] = SENTINEL
    pool = jnp.asarray(rng.normal(size=(L, N, BS, W)), dtype)
    q = jnp.asarray(rng.normal(size=(B, cb, H, W)), dtype)
    lat = jnp.asarray(rng.normal(size=(B, cb, 1, W)), dtype)
    T = case.get("t_bucket", RING)
    return dict(
        q=q, pool=pool, lat=lat, q_pos=jnp.asarray(ctx, jnp.int32),
        q_len=jnp.asarray(qlen, jnp.int32),
        kv_pos=jnp.asarray(kv_pos[:, :T]), bt=jnp.asarray(bt),
        nblk=jnp.asarray(used, jnp.int32),
        slot0=jnp.asarray(ctx % RING, jnp.int32),
    )


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_matches_the_oracle(name):
    case = CASES[name]
    x = _inputs(case)
    cb, v_dim = case["cb"], case.get("v_dim")
    tol = case.get("tol", 2e-5)  # float32: that of tests/test_ragged.py
    T = x["kv_pos"].shape[1]
    assert pallas_mla.supports(BS, H, W, cb, x["pool"].dtype, v_dim)
    for layer in range(L):
        got = pallas_mla.latent_paged_attention(
            x["q"], x["pool"], x["lat"], x["q_pos"], x["q_len"], x["kv_pos"],
            x["bt"], x["nblk"], x["slot0"], jnp.int32(layer), ring_len=RING,
            scale=SCALE, v_dim=v_dim, interpret=True,
        )
        view = x["pool"][layer][:, :, None, :]
        want = attn.ragged_paged_attention(
            x["q"], view, view, x["lat"], x["lat"], x["q_pos"], x["q_len"],
            x["kv_pos"], x["bt"], x["slot0"], RING, scale=SCALE,
            n_blocks=-(-T // BS) if T < RING else None,
        )
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert got.shape == (B, cb, H, v_dim or W)
        assert np.isfinite(got).all()  # padding rows too
        for b, n in enumerate(case["qlen"]):
            np.testing.assert_allclose(
                got[b, :n], want[b, :n, :, : got.shape[-1]],
                rtol=tol, atol=tol,
            )
        if cb == 1:  # the decode step's own oracle says the same
            dec = attn.paged_decode_attention(
                x["q"], view, view, x["lat"], x["lat"],
                x["q_pos"][:, None], x["kv_pos"], x["bt"],
                x["slot0"][:, None], scale=SCALE,
                n_blocks=-(-T // BS) if T < RING else None,
            )
            np.testing.assert_allclose(
                got, np.asarray(dec, np.float32)[..., : got.shape[-1]],
                rtol=tol, atol=tol,
            )


@pytest.mark.parametrize(
    "kwargs",
    [
        dict(pool_dim=576),  # a row that is not whole lanes
        dict(block_size=8),  # bfloat16 tiles 16 sublanes
        dict(block_size=48),  # does not divide a chunk of the walk
        dict(chunk=32),  # more fresh keys than the merge holds
        dict(n_heads=128, chunk=16),  # 2048 query rows: VMEM
        dict(v_dim=500),
        dict(dtype=jnp.int8),
    ],
    ids=lambda kw: "-".join(f"{k}={v}" for k, v in kw.items()),
)
def test_supports_refuses(kwargs):
    ok = dict(block_size=16, n_heads=32, pool_dim=640, chunk=8,
              dtype=jnp.bfloat16)
    assert pallas_mla.supports(**ok)
    assert not pallas_mla.supports(**{**ok, **kwargs})
