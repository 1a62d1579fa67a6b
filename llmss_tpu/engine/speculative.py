"""Prompt-lookup speculative decoding: draft-free speculation for greedy
decode, fully on-device.

Each speculative step drafts ``gamma`` candidate tokens by n-gram lookup
in the row's OWN device-resident history (prompt + emitted tokens —
"prompt lookup decoding": repeated spans are common in summarization,
code, chat with shared context), then verifies the whole draft in ONE
forward of S = gamma+1 tokens against the KV cache. The model's greedy
choice at each draft position either confirms the next draft token
(accept, keep going) or replaces it (stop; the replacement is the step's
bonus token). Every step emits 1..gamma+1 tokens for ~2.5x the cost of a
single-token step (measured: 11.5 vs 4.5 ms at 1b2/batch16), so
workloads with lookup hits come out ahead — with no distribution drift:
every emitted token is the argmax of the model's logits given the true
prefix.

**Everything runs on device in fused groups**: the n-gram lookup, the
verify forward, acceptance, the history append, and EOS handling run
inside ONE jitted program of ``m`` scanned speculative steps per group
(``spec_group_impl``), with the group's choices/emits/state packed into
a single flat array inside the jit — one dispatch and one device→host
fetch per group, exactly the grouped-decode discipline of
``DecodeEngine._decode_group`` (a host-side draft loop pays one host
round-trip per accepted run of tokens; chained per-step dispatch pays
the host's dispatch overhead per verify). No time of it on the current
machine is on record (ROADMAP D4).

Exactness scope: verification is exact *under the verify forward's own
numerics*. When the S=gamma+1 forward and the S=1 decode step lower to
the same kernels (the CPU test mesh), output is token-identical to plain
``generate`` — asserted in tests/test_speculative.py. On TPU the two
paths use different attention kernels whose fp32 logits can resolve an
argmax tie differently, so the two valid greedy decodes may diverge at a
tie.

TPU design notes:
- ``gamma`` and the chunk length are static; drafts are data. Rows with
  no n-gram match draft a repeat of their last token — usually rejected,
  which degrades to a normal 1-token step, never to a wrong token.
- Rows advance by different amounts; per-row ``hist_len`` drives ring
  positions (the engine's ring addressing supports desynced rows).
- The verify forward writes all gamma+1 draft tokens' KV; slots of
  REJECTED draft tokens are invalidated in the same step (``positions``
  reset to -1) so later steps never attend them. Accepted tokens' KV is
  valid by construction: an accepted draft token IS the token the model
  chose at that position.

The reference has no speculation of any kind (one token per
``generate.py:99`` loop iteration).
"""

from __future__ import annotations

import time
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp


def lookup_draft(
    hist: list[int], gamma: int, ngram: int = 3,
) -> list[int]:
    """Host-side reference of the device draft rule: match the trailing
    n-gram (falling back to shorter n, then to repeating the last token)
    against the row's own past; propose the ``gamma`` tokens that
    followed the most recent match."""
    h = np.asarray(hist, np.int32)
    L = len(h)
    for n in range(min(ngram, L - 1), 0, -1):
        tail = h[L - n:]
        windows = np.lib.stride_tricks.sliding_window_view(h[:-1], n)
        hits = np.flatnonzero((windows == tail).all(axis=1))
        for s in hits[::-1]:
            cont = h[s + n: s + n + gamma]
            if len(cont) > 0:
                out = cont.tolist()
                while len(out) < gamma:
                    out.append(out[-1])
                return out
    return [int(h[-1])] * gamma


def _device_draft(hist: jax.Array, L: jax.Array, gamma: int, ngram: int):
    """Vectorized prompt-lookup draft for one row: ``hist`` [H], ``L``
    scalar live length. Mirrors ``lookup_draft``: longest n first, most
    recent match; the continuation may overlap the tail (self-extending
    periodic patterns). Falls back to repeating the last token."""
    H = hist.shape[0]
    iota = jnp.arange(H, dtype=jnp.int32)
    last = hist[jnp.clip(L - 1, 0, H - 1)]
    draft = jnp.full((gamma,), last, jnp.int32)
    found_any = jnp.zeros((), bool)
    for n in range(ngram, 0, -1):
        # window starting at s covers hist[s : s+n]; candidate iff it lies
        # strictly before the trailing occurrence (s + n <= L - 1) and the
        # history is long enough for an n-gram tail (L - n >= 1).
        tail = jax.lax.dynamic_slice(
            hist, (jnp.clip(L - n, 0, H - n),), (n,)
        )
        win = hist[jnp.clip(iota[:, None] + jnp.arange(n)[None, :], 0,
                            H - 1)]  # [H, n]
        valid = (iota + n <= L - 1) & (L - n >= 1)
        hit = valid & jnp.all(win == tail[None, :], axis=1)
        s_best = jnp.max(jnp.where(hit, iota, -1))
        found = s_best >= 0
        cont_idx = s_best + n + jnp.arange(gamma, dtype=jnp.int32)
        # Positions past the live history pad with the CONTINUATION's last
        # in-range element — the host reference's ``out.append(out[-1])``
        # rule, stated literally (a truncated continuation always ends at
        # ``hist[L-1]``, so this pad VALUE equals the row's last token; the
        # code now encodes the documented rule rather than relying on that
        # coincidence). A hit guarantees ``s_best + n < L``, so the pad
        # index is in range whenever ``found`` (and masked out otherwise).
        pad = hist[jnp.clip(jnp.minimum(cont_idx[-1] + 1, L) - 1, 0, H - 1)]
        cont = jnp.where(
            cont_idx < L, hist[jnp.clip(cont_idx, 0, H - 1)], pad
        )
        take = found & ~found_any
        draft = jnp.where(take, cont, draft)
        found_any = found_any | found
    return draft


def spec_step_impl(
    cfg, mesh, params, hist, hist_len, cache, done, eos,
    *, gamma: int, ngram: int = 3, t_bucket: int | None = None,
):
    """One speculative step as a single jit: device draft → verify
    forward → acceptance → EOS/ring handling → history append. Full-size
    groups of these run as ONE scanned program (``spec_group_impl`` —
    one dispatch + one packed fetch per group); the chained-dispatch
    form remains the ring-constrained partial-group path, where a
    bespoke grouped executable per residual group size would compile at
    every ring boundary.

    hist [B, H] int32 — prompt + emitted tokens (no EOS); hist_len [B].
    Returns (choice [B, gamma+1], n_emit [B], hist, hist_len, cache,
    done): the host emits ``choice[r, :n_emit[r]]`` in order. ``done``
    rows are frozen (n_emit 0, no live writes); the HOST must stop
    dispatching before any live row lacks ring headroom for a full
    window — a frozen-row write may wrap harmlessly over its own dead
    slots, but a live row's wrap would destroy its context.
    """
    from llmss_tpu.models.decoder import forward

    B, H = hist.shape
    S = gamma + 1
    b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]
    drafter = jax.vmap(
        partial(_device_draft, gamma=gamma, ngram=ngram)
    )

    cur = hist_len - 1  # position/index of each row's current token
    frozen = done
    cur_tok = hist[b_idx[:, 0], jnp.clip(cur, 0, H - 1)]
    draft = jnp.concatenate(
        [cur_tok[:, None], drafter(hist, hist_len)], axis=1
    )  # [B, S]
    positions = cur[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    slots = positions % cache.max_len
    logits, cache = forward(
        cfg, params, draft, positions, cache, slots, mesh=mesh,
        t_bucket=t_bucket,
    )
    choice = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, S]
    match = draft[:, 1:] == choice[:, :-1]
    n_acc = jnp.sum(
        jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1
    )
    n_emit = n_acc + 1  # accepted draft tokens + bonus/replacement

    col = jnp.arange(S, dtype=jnp.int32)[None, :]
    # EOS inside the emitted window truncates the emission (the EOS
    # itself is not emitted) and finishes the row.
    eos_hit = (choice == eos[:, None]) & (col < n_emit[:, None])
    any_eos = jnp.any(eos_hit, axis=1)
    first_eos = jnp.argmax(eos_hit, axis=1)
    n_emit = jnp.where(any_eos, first_eos, n_emit)
    n_emit = jnp.where(frozen, 0, n_emit)

    # Invalidate rejected draft KV; frozen (done) rows contribute nothing
    # live, so their whole window is invalidated too.
    keep = (col <= n_acc[:, None]) & ~frozen[:, None]
    fixed = jnp.where(keep, positions, -1)
    cache = cache._replace(
        positions=cache.positions.at[b_idx, slots].set(fixed)
    )

    # Append emitted tokens to the history (masked scatter).
    app_idx = hist_len[:, None] + col
    app_ok = col < n_emit[:, None]
    hist = hist.at[
        b_idx, jnp.clip(app_idx, 0, H - 1)
    ].set(jnp.where(app_ok, choice, hist[
        b_idx, jnp.clip(app_idx, 0, H - 1)
    ]))
    hist_len = hist_len + n_emit
    done = done | (any_eos & ~frozen)
    return choice, n_emit, hist, hist_len, cache, done


def spec_group_impl(
    cfg, mesh, params, hist, hist_len, cache, done, eos,
    *, m: int, gamma: int, ngram: int = 3, t_bucket: int | None = None,
):
    """A GROUP of ``m`` speculative steps as ONE jitted program: an outer
    ``lax.scan`` over ``spec_step_impl`` with the result packing moved
    inside the jit — the same grouped-dispatch discipline as the main
    decode path (``DecodeEngine._decode_group``). The host pays one
    dispatch and one packed fetch per group instead of one dispatch per
    verify forward, which is what deletes the per-verify host exec
    overhead the chained-dispatch loop still paid (its size is not
    measured on the current machine; the round-5 0.82x wall-clock was put
    down to it).

    Returns ``(packed, hist, hist_len, cache, done)`` where ``packed`` is
    the flat int32 array ``[m·B·S choices | m·B emits | B hist_len |
    B done]`` — byte-identical to the layout the host previously
    concatenated from chained step outputs, so the unpack code is shared.
    """
    # Pin the stacked ys to a replicated sharding: GSPMD otherwise
    # propagates an unreduced partial-sum layout from the tp-sharded
    # logits into the scan's stacked outputs, and the host reads choices
    # summed over the tp axis (tp× their true value — the same hazard
    # fixed in DecodeEngine._decode_group_impl). The carry is immune;
    # only the ys leave the loop unconstrained (parallel/sharding.ys_pin
    # documents the hazard; shardcheck's partial-sum-leak rule gates it).
    from llmss_tpu.parallel.sharding import ys_pin

    pin = ys_pin(mesh)

    def body(carry, _):
        hist, hist_len, cache, done = carry
        choice, n_emit, hist, hist_len, cache, done = spec_step_impl(
            cfg, mesh, params, hist, hist_len, cache, done, eos,
            gamma=gamma, ngram=ngram, t_bucket=t_bucket,
        )
        return (hist, hist_len, cache, done), (pin(choice), pin(n_emit))

    (hist, hist_len, cache, done), (choices, emits) = jax.lax.scan(
        body, (hist, hist_len, cache, done), None, length=m,
    )
    packed = jnp.concatenate([
        choices.reshape(-1), emits.reshape(-1), hist_len,
        done.astype(jnp.int32),
    ])
    return packed, hist, hist_len, cache, done


def generate_speculative(
    engine,
    prompts: list[list[int]],
    gen,
    *,
    gamma: int = 4,
    ngram: int = 3,
    chunk_steps: int = 8,
) -> list[list[int]]:
    """Greedy generation with fused-chunk prompt-lookup speculation (see
    module docstring). Emits a valid greedy decode — token-identical to
    ``generate`` whenever both lower to the same kernels — in roughly
    ``1/mean_accepted`` of the forwards and ``1/(chunk·mean_accepted)``
    of the host round-trips. When ring headroom for a full speculative
    window runs out, the tail finishes on plain single-token steps.

    Records acceptance stats on ``engine.metrics.spec_stats``."""
    if engine.cfg.mla is not None:
        raise ValueError(
            "speculative decoding is not carried over a latent pool: the "
            "windowed verify step has no absorbed form yet "
            "(docs/latent-cache.md)"
        )
    if engine.cfg.indexer is not None:
        raise ValueError(
            "speculative decoding is not carried for a model with an "
            "indexer: the windowed verify step has no selection a draft "
            "position yet (docs/sparse-attention.md)"
        )
    if engine.cfg.has_state:
        raise ValueError(
            "speculative decoding does not carry a recurrent state: a "
            "rejected draft would have to roll the state back "
            "(docs/recurrent-state.md)"
        )
    gen.validate()
    if not gen.is_greedy:
        raise ValueError(
            "speculative decoding verifies greedy argmax choices; "
            "sampled requests must use generate()"
        )
    B = len(prompts)
    S = gamma + 1
    lens_probe = max(len(p) for p in prompts)
    if lens_probe + S + 1 > engine.max_seq_len:
        # No ring headroom for even one speculative window (or the prompt
        # fills the ring outright): plain greedy serves the identical
        # contract. Stats reflect THIS call (zero speculation).
        engine.metrics.spec_stats = {
            "verify_forwards": 0, "tokens_via_speculation": 0,
            "mean_tokens_per_forward_per_row": 0.0,
            "gamma": gamma, "chunk_steps": chunk_steps,
        }
        return engine.generate(prompts, gen)

    def get_step(t_bucket):
        key = ("_spec_step", gamma, ngram, t_bucket)
        fn = engine.__dict__.get(key)
        if fn is None:
            fn = jax.jit(
                partial(
                    spec_step_impl, engine.cfg, engine.mesh,
                    gamma=gamma, ngram=ngram, t_bucket=t_bucket,
                ),
                donate_argnums=(3,),
            )
            engine.__dict__[key] = fn
        return fn

    def get_group(t_bucket):
        # One grouped program per (group size, draft params, bucket) —
        # cached on the engine like the step jits so CompileGuard sees it.
        # Only the FULL group size compiles (partial groups near the ring
        # chain the step jit instead), bounding the executable count.
        key = ("_spec_group", chunk_steps, gamma, ngram, t_bucket)
        fn = engine.__dict__.get(key)
        if fn is None:
            fn = jax.jit(
                partial(
                    spec_group_impl, engine.cfg, engine.mesh,
                    m=chunk_steps, gamma=gamma, ngram=ngram,
                    t_bucket=t_bucket,
                ),
                donate_argnums=(1, 3),  # hist, cache
            )
            engine.__dict__[key] = fn
        return fn

    ids, lens = engine._pad_prompts(prompts)
    cache = engine.new_cache(B)
    sa = engine._sample_args(gen, B)
    tok, _, cache = engine.timed_prefill(
        engine._prefill, engine.params, jnp.asarray(ids), cache,
        jnp.asarray(lens), sa, batch=B,
    )
    tok_np = np.asarray(tok)
    cache = engine.canon_cache(cache)

    eos_val = gen.eos_token_id if gen.eos_token_id is not None else -1
    out: list[list[int]] = [[] for _ in range(B)]
    done_np = np.zeros(B, bool)

    def emit(r: int, t: int) -> bool:
        """Append token t to row r; returns True iff it was appended
        (the row may complete in the same call). (Device-side EOS/done
        handling already excludes EOS tokens and frozen rows; max_new is
        enforced here on the host.)"""
        if done_np[r]:
            return False
        out[r].append(t)
        if len(out[r]) >= gen.max_new_tokens:
            done_np[r] = True
        return True

    H = engine.max_seq_len
    hist_np = np.zeros((B, H), np.int32)
    for r, p in enumerate(prompts):
        hist_np[r, : len(p)] = p
    first_live = ~(tok_np == eos_val)
    for r in range(B):
        if first_live[r]:
            emit(r, int(tok_np[r]))
        else:
            done_np[r] = True
        if not done_np[r]:
            hist_np[r, lens[r]] = tok_np[r]
    hist = engine.canon_vec(jnp.asarray(hist_np))
    hist_len = engine.canon_vec(
        jnp.asarray(lens + first_live.astype(np.int32), jnp.int32)
    )
    done = engine.canon_vec(jnp.asarray(done_np))
    eos = engine.canon_vec(jnp.full(B, eos_val, jnp.int32))

    n_forwards = 0
    n_emitted = 0
    # Speculative phase: groups of ``chunk_steps`` back-to-back step
    # dispatches (async — the host blocks only on the group's fetch).
    # Each LIVE row must have headroom for chunk_steps full windows
    # (worst case all-accept); done rows' windows wrap harmlessly over
    # their own dead slots. Host-side completions (max_new) are pushed
    # back into the device ``done`` each group so finished rows neither
    # advance the guard nor burn verify work.
    hl_host = np.asarray(hist_len)
    while not done_np.all():
        live_hi = int(hl_host[~done_np].max())
        # Shrink the group near the ring so speculation keeps running
        # while a worthwhile number of windows fits (worst-case-all-accept
        # bound per group). Below half a group, the per-group fetch
        # round-trip outweighs the speculative win — finish on the
        # chunked plain tail instead.
        m = min(chunk_steps, (engine.max_seq_len - live_hi) // S)
        if m < max(1, chunk_steps // 2):
            break
        # Bucketed cache reads for the whole group: every live row's
        # positions stay under live_hi + m·S by the guard above.
        # (Frozen rows' dead windows may read truncated garbage — unread.)
        tb = engine.decode_bucket(live_hi + m * S)
        t0 = time.perf_counter()
        if m == chunk_steps:
            # Full group: ONE jitted program covers all m verify steps
            # with the packing inside the jit (spec_group_impl) — one
            # dispatch + one fetch per group; per-verify host exec
            # overhead disappears.
            packed_dev, hist, hist_len, cache, done = get_group(tb)(
                engine.params, hist, hist_len, cache, done, eos,
            )
        else:
            # Ring-constrained partial group: chain the per-step jit (a
            # grouped program per residual m would compile a bespoke
            # executable near every ring boundary) and pack on the host.
            step = get_step(tb)
            group = []
            for _ in range(m):
                # Raw jit outputs feed straight back in — a canon rewrap
                # per carried array here costs a host round-trip EACH on
                # remote backends (4/step × 8 steps ≈ the whole group's
                # device time). The executable set stabilizes after at
                # most one extra compile per bucket (self-consistent
                # output→input cycle).
                choice, n_emit, hist, hist_len, cache, done = step(
                    engine.params, hist, hist_len, cache, done, eos,
                )
                group.append((choice, n_emit))
            packed_dev = jnp.concatenate(
                [jnp.stack([c for c, _ in group]).reshape(-1)]
                + [jnp.stack([e for _, e in group]).reshape(-1)]
                + [hist_len, done.astype(jnp.int32)]
            )
        n_forwards += m
        engine.metrics.host_dispatch.record(time.perf_counter() - t0)
        engine.metrics.add_group()
        # Deliberate single fetch per speculative group: the packed layout
        # exists precisely so the whole group's choices/emits/state cross
        # the host link in ONE transfer instead of per-step fetches.
        with engine.metrics.host_fetch.time():
            packed = np.asarray(packed_dev)  # lint: ignore[host-sync-in-loop]
        engine.metrics.add_host_sync()
        t_cb = time.perf_counter()
        ch_np = packed[: m * B * S].reshape(m, B, S)
        ne_np = packed[m * B * S: m * B * (S + 1)].reshape(m, B)
        hl_host = packed[m * B * (S + 1): m * B * (S + 1) + B]
        dev_done = packed[m * B * (S + 1) + B:].astype(bool)
        for s in range(m):
            for r in range(B):
                for c in range(int(ne_np[s, r])):
                    if emit(r, int(ch_np[s, r, c])):
                        n_emitted += 1
                    if done_np[r]:
                        break
        # Device-side EOS completions never show in the emitted tokens
        # (the EOS is truncated out) — adopt them, or the host would keep
        # dispatching for rows the device already finished.
        done_np |= dev_done
        # Push host-side (max_new) completions into the device done mask.
        if (done_np & ~dev_done).any():
            done = engine.canon_vec(jnp.asarray(dev_done | done_np))
        engine.metrics.host_callback.record(time.perf_counter() - t_cb)

    # Ring-constrained tail (a full speculative window no longer fits):
    # plain CHUNKED decode via _decode_group — including past the ring
    # boundary, where generate()'s sliding-window wrap semantics apply
    # identically (each row is bounded by max_new_tokens).
    if not done_np.all():
        hl_np = np.asarray(hist_len)
        h_np = np.asarray(hist)
        pos_hi = int(hl_np.max())
        tok_cur = engine.canon_vec(jnp.asarray(
            [int(h_np[r, min(int(hl_np[r]) - 1, H - 1)]) for r in range(B)],
            jnp.int32,
        ))
        cur = engine.canon_vec(jnp.asarray(hl_np - 1, jnp.int32))
        eos_dev = engine.canon_vec(jnp.full(B, eos_val, jnp.int32))
        k = 16
        while not done_np.all():
            # The group donates the token and position carries.
            packed, tok_cur, cache, cur, _ = engine._decode_group(
                engine.params, tok_cur, cache, cur, sa,
                engine.canon_vec(jnp.asarray(done_np)), eos_dev,
                n_chunks=1, n_steps=k,
                t_bucket=engine.decode_bucket(pos_hi + k),
            )
            cache = engine.canon_cache(cache)
            cur = engine.canon_vec(cur)
            tok_cur = engine.canon_vec(tok_cur)
            pos_hi += k
            # One fetch per k-step tail chunk (same amortization as
            # engine.generate's chunked decode loop).
            t_np = np.asarray(packed)[: B * k].reshape(B, k)  # lint: ignore[host-sync-in-loop]
            for col in range(k):
                for r in range(B):
                    if not done_np[r]:
                        t = int(t_np[r, col])
                        if t == eos_val:
                            done_np[r] = True
                        else:
                            emit(r, t)

    engine.metrics.add_tokens(sum(len(o) for o in out))
    # Always overwrite: stale stats from a previous call must not be
    # misattributed to this one.
    engine.metrics.spec_stats = {
        "verify_forwards": n_forwards,
        "tokens_via_speculation": n_emitted,
        "mean_tokens_per_forward_per_row": round(
            n_emitted / n_forwards / B, 3
        ) if n_forwards else 0.0,
        "gamma": gamma,
        "chunk_steps": chunk_steps,
    }
    return out
