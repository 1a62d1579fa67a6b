"""Experiment: decode-mask variants for fresh_kv_decode_attention.

PROFILE.md (round 5, at commit e57f952) diagnoses a ~0.6 ms/step cost for
the *dynamic* decode score mask (the hoisted additive [B, T] penalty) over a compile-time-foldable
one. This measures candidate replacements on the real chip, all inside
the actual fused decode scan (engine._decode_many via forward):

- penalty   : shipped path — hoisted additive [B, T] f32 penalty
- nomask    : no masking at all (incorrect; the fusion floor)
- iota      : inline ``iota_t < q_pos`` comparison on the scores
              (no [B, T] HBM operand; valid only for no-wrap decode)
- postexp   : multiplicative [B, T] 0/1 mask applied to probs AFTER exp
              (exact: m is softmax-shift-invariant; masked slots' scores
              are finite since the cache is zero-init / holds stale reals)
- iota_postexp: iota comparison, applied post-exp as a multiply

Usage: python tools/exp_mask.py [variants...]
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import _MODEL_RUN, DECODE, PROMPT, flagship_cfg, slope_time  # noqa: E402

BATCH = int(os.environ.get("BENCH_BATCH", 0)) or _MODEL_RUN["1b2"]["batch"]

_NEG_INF = float(jnp.finfo(jnp.float32).min)


def make_attn_variant(variant: str):
    """Returns (decode_mask_penalty_fn, fresh_kv_decode_attention_fn)."""

    def penalty_fn(q_pos, kv_pos_old, slots, window=None):
        if variant in ("iota", "iota_postexp", "nomask", "postexp"):
            return None  # variants compute masking inline (or not at all)
        T = kv_pos_old.shape[1]
        slot_idx = jnp.arange(T, dtype=jnp.int32)
        mask = (
            (kv_pos_old <= q_pos)
            & (kv_pos_old >= 0)
            & (slot_idx[None, :] != slots)
        )
        if window is not None:
            mask &= kv_pos_old > q_pos - window
        return jnp.where(mask, 0.0, _NEG_INF).astype(jnp.float32)

    def attn(q, k_cache, v_cache, k_new, v_new, q_pos, kv_pos_old, slots, *,
             scale=None, window=None, penalty=None, k_scale=None,
             v_scale=None):
        B, S, Hq, D = q.shape
        T, Hkv = k_cache.shape[1], k_cache.shape[2]
        G = Hq // Hkv
        if scale is None:
            scale = 1.0 / (D ** 0.5)
        qf = q.astype(jnp.float32).reshape(B, S, Hkv, G, D) * scale
        s_c = jnp.einsum("bskgd,btkd->bkgst", qf, k_cache.astype(jnp.float32))
        iota = jnp.arange(T, dtype=jnp.int32)
        if variant == "penalty":
            if penalty is None:
                penalty = penalty_fn(q_pos, kv_pos_old, slots, window)
            s_c = s_c + penalty[:, None, None, None, :]
        elif variant == "iota":
            # no-wrap specialization: slot t visible iff t < q_pos
            vis = iota[None, :] < q_pos  # [B, T] (q_pos [B,1])
            s_c = jnp.where(vis[:, None, None, None, :], s_c, _NEG_INF)
        s_s = jnp.einsum(
            "bskgd,bskd->bkgs", qf, k_new.astype(jnp.float32)
        )[..., None]
        m = jnp.maximum(jnp.max(s_c, axis=-1, keepdims=True), s_s)
        p_c = jnp.exp(s_c - m)
        p_s = jnp.exp(s_s - m)
        if variant == "postexp":
            vis = (
                (kv_pos_old <= q_pos) & (kv_pos_old >= 0)
                & (iota[None, :] != slots)
            )
            p_c = p_c * vis[:, None, None, None, :].astype(jnp.float32)
        elif variant == "iota_postexp":
            vis = iota[None, :] < q_pos
            p_c = p_c * vis[:, None, None, None, :].astype(jnp.float32)
        denom = jnp.sum(p_c, axis=-1, keepdims=True) + p_s
        if G == 1 and S == 1:
            p_t = p_c[:, :, 0, 0, :]
            vterm = jnp.sum(
                p_t.transpose(0, 2, 1)[..., None]
                * v_cache.astype(jnp.float32),
                axis=1,
            )
            out_c = vterm[:, :, None, None, :]
        else:
            out_c = jnp.einsum(
                "bkgst,btkd->bkgsd", p_c, v_cache.astype(jnp.float32)
            )
        out = (
            out_c
            + p_s * v_new.astype(jnp.float32).transpose(0, 2, 1, 3)[:, :, None]
        ) / denom
        return out.transpose(0, 3, 1, 2, 4).reshape(B, S, Hq, D).astype(q.dtype)

    return penalty_fn, attn


def measure(variant: str) -> float:
    import llmss_tpu.models.decoder as dec
    from llmss_tpu.engine import DecodeEngine, GenerationParams
    from llmss_tpu.models.decoder import init_params
    from llmss_tpu.parallel import MeshPlan, make_mesh

    pen_fn, attn_fn = make_attn_variant(variant)
    dec.decode_mask_penalty = pen_fn
    dec.fresh_kv_decode_attention = attn_fn

    mesh = make_mesh(MeshPlan(tp=len(jax.devices())))
    cfg = flagship_cfg()
    params = init_params(cfg, mesh, jax.random.key(0))
    engine = DecodeEngine(cfg, params, mesh, max_seq_len=PROMPT + DECODE)
    gen = GenerationParams(max_new_tokens=DECODE, is_greedy=True)
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, cfg.vocab_size, PROMPT).tolist() for _ in range(BATCH)
    ]
    ids, lens = engine._pad_prompts(prompts)
    sa = engine._sample_args(gen, BATCH)
    eos = jnp.int32(-1)

    def prepare(n):
        cache = engine.new_cache(BATCH)
        tok, _, cache = engine._prefill(
            engine.params, jnp.asarray(ids), cache, jnp.asarray(lens), sa,
        )
        cur = jnp.asarray(lens)
        done = jnp.zeros(BATCH, bool)
        state = {"cache": cache}

        def run():
            out = engine._decode_many(
                engine.params, tok, state["cache"], cur, sa, done, eos,
                n_steps=n,
            )
            toks, state["cache"] = out[0], out[1]
            _ = float(jnp.sum(toks))

        return run

    return slope_time(prepare)[0]


def main():
    variants = sys.argv[1:] or [
        "penalty", "nomask", "iota", "postexp", "iota_postexp"
    ]
    out = {}
    for v in variants:
        ms = measure(v)
        out[v] = round(ms, 3)
        print(f"{v}: {ms:.3f} ms/step", flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
