"""On-device token sampling: temperature → top-k → top-p → categorical.

The reference constructs HF logits warpers but never applies them due to an
inverted condition (``generate.py:120-124``, ``consumer_server.py:141-145`` —
SURVEY.md §2.11.1), so its "sampling" is multinomial over raw-logit softmax.
This module implements *correct* sampling as a deliberate behavior fix, with
the conventional order (temperature first, then top-k, then top-p), entirely
on device — no per-token host round-trip, which is what deletes the
reference's per-token ``dist.broadcast`` (``generate.py:144``).

All warper parameters are per-request arrays (dynamic under jit) so a batch
can mix greedy and sampled requests — required for continuous batching.
``sample`` has three branches, chosen from those parameters and never from
the logits: argmax alone, one categorical, or the filtered draw. The
filtered draw finds each row's top-k / top-p keep-set exactly, from a
threshold on the value (``_keep_set``: two bisections of a fixed length,
each step a pass over ``[B, V]``), with no sort of the vocabulary, no
scatter and no candidate bucket: its cost is the same whatever the
distributions look like, and it is compiled into every step program.

Randomness is **per-row and stateless**: each draw uses
``fold_in(key(seed_row), counter_row)`` where the counter is the absolute
position of the token being sampled. Same request + same seed → identical
sampled tokens, regardless of what else shares the batch, which generation
mode runs it (streaming / fused / continuous), or admission order — the
reproducibility the serving protocol's ``seed`` field promises.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# The per-row key derivation below assumes the partitionable threefry
# key semantics (the default from jax 0.5). On older runtimes the legacy
# non-partitionable streams produce different draws for the same
# (seed, counter), breaking the cross-mode reproducibility promised in
# the docstring — so pin the flag explicitly rather than inheriting a
# version-dependent default.
jax.config.update("jax_threefry_partitionable", True)


def nonfinite_rows(logits: jax.Array) -> jax.Array:
    """[B] bool poison flags: True where a row's logits contain NaN/inf.

    A single overflowed matmul (bad weights, a corrupted KV row, an fp8
    overflow upstream) turns that row's distribution into garbage — argmax
    over NaN is backend-defined and categorical draws from nothing — but
    only *that* row: batch rows never mix. This check runs inside the
    jitted decode chunk so the serving layer can error out exactly the
    poisoned row while co-batched rows keep their exact solo tokens,
    instead of crashing (and re-crashing, on redelivery) the whole batch.
    """
    return ~jnp.all(jnp.isfinite(logits), axis=-1)


def fold_step_outcome(
    logits: jax.Array,  # [B, V] the step's last-position logits
    tok: jax.Array,  # [B] int32 the step's sampled token
    done: jax.Array,  # [B] bool carry
    poisoned: jax.Array,  # [B] bool carry
    eos: jax.Array,  # [B] int32 per-row EOS id (-1 = none)
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fold one decode step's EOS / non-finite outcome into the scanned
    decode carry: rows that were already done — or whose logits just went
    non-finite — emit an EOS fill instead of a sampled token, poisoned
    rows are forced done (their later steps are fills the host discards),
    and a row sampling its EOS finishes. One definition for every fused
    decode scan (``_decode_group``, ``_ragged_group``) so the grouped
    and ragged paths share the carry semantics bit-for-bit.

    Returns the updated ``(tok, done, poisoned)``.
    """
    bad = nonfinite_rows(logits) & ~done
    poisoned = poisoned | bad
    tok = jnp.where(done | bad, eos, tok)
    done = done | bad | (tok == eos)
    return tok, done, poisoned


def row_keys(seeds: jax.Array, counters: jax.Array) -> jax.Array:
    """[B] PRNG keys, one per batch row: fold the token counter into the
    request seed's key stream."""
    return jax.vmap(
        lambda s, c: jax.random.fold_in(jax.random.key(s), c)
    )(seeds, counters)


def _value_at(place: jax.Array) -> jax.Array:
    """The fp32 value at a place in the order of all fp32 values: uint32
    places ascend as the values do (the inverse of the usual sort key: flip
    the sign bit, and the rest too where it was set). The places below
    ``-inf`` and above ``+inf`` are NaN patterns; those below read ``-inf``,
    so that a comparison with them is one with the least value there is."""
    s = jax.lax.bitcast_convert_type(place ^ jnp.uint32(1 << 31), jnp.int32)
    v = jax.lax.bitcast_convert_type(
        s ^ ((s >> 31) & jnp.int32(0x7FFFFFFF)), jnp.float32
    )
    return jnp.where(jnp.isnan(v) & (s < 0), -jnp.inf, v)


def _keep_set(
    scaled: jax.Array,  # [B, V] fp32
    k_eff: jax.Array,  # [B, 1] int32 in [1, V]; V where top-k is off
    p_eff: jax.Array,  # [B, 1] f32; 2.0 where top-p is off
) -> jax.Array:
    """[B, V] bool: what top-k then top-p keep of each row.

    Order a row by value descending, equal values by lower id first; rank
    ``r`` is kept iff ``r < k_eff`` and the probability mass strictly
    before it (softmax over the WHOLE vocabulary) is ``< p_eff``; rank 0
    always. Such a set is ``{scaled > tau}`` plus the lowest ids of
    ``{scaled == tau}``, so it is found from a threshold on the value and
    not from a permutation of the vocabulary. ``tau`` is the least value
    whose strictly-greater set stays within both limits: a bisection over
    the 32 bits of a value's place in the order of fp32 values, each step
    one compare-and-sum pass over ``[B, V]``. The tie group at ``tau`` is
    cut the same way, by a bisection over the ids. Both loops have a fixed
    length, so the cost does not depend on what the distributions look
    like. A row with no filter gets its least value for ``tau`` and keeps
    everything. Inside the loops everything is computed from ``scaled`` and
    the loop's own threshold, so that ``scaled`` is the one ``[B, V]``
    array they read (an ``exp(scaled - lse)`` would be hoisted and stored
    as a second).
    """
    V = scaled.shape[-1]
    lse = jax.nn.logsumexp(scaled, axis=-1, keepdims=True)

    def count(members):
        return jnp.sum(members, axis=-1, keepdims=True, dtype=jnp.int32)

    def mass(members):
        return jnp.sum(
            jnp.exp(jnp.where(members, scaled, -jnp.inf) - lse),
            axis=-1, keepdims=True,
        )

    def fits(n_before, mass_before):  # is the rank after these kept
        return ((n_before < k_eff) & (mass_before < p_eff)) | (n_before == 0)

    def narrow(i, lo):
        # ``lo`` holds tau's place down to ``bit``. With this bit clear the
        # greatest candidate is ``lo | (bit - 1)``: where the rank below
        # even that is kept, tau has the bit clear.
        bit = jnp.uint32(1 << 31) >> i.astype(jnp.uint32)
        above = scaled > _value_at(lo | (bit - 1))
        return jnp.where(fits(count(above), mass(above)), lo, lo | bit)

    tau = _value_at(jax.lax.fori_loop(
        0, 32, narrow, jnp.zeros(k_eff.shape, jnp.uint32)
    ))
    above, tie = scaled > tau, scaled == tau
    n_above, mass_above, p_tau = count(above), mass(above), jnp.exp(tau - lse)
    ids = jnp.arange(V, dtype=jnp.int32)[None, :]
    id_bits = max(1, (V - 1).bit_length())

    def admit(i, last):
        # The greatest id whose tie members before it leave it a kept rank.
        j = last | (jnp.int32(1 << (id_bits - 1)) >> i)
        n = count(tie & (ids < j))
        ok = fits(n_above + n, mass_above + n.astype(jnp.float32) * p_tau)
        return jnp.where(ok, j, last)

    last = jax.lax.fori_loop(
        0, id_bits, admit, jnp.zeros(k_eff.shape, jnp.int32)
    )
    return above | (tie & (ids <= last))


def sample(
    logits: jax.Array,  # [B, V] fp32
    *,
    seeds: jax.Array,  # [B] int32 per-request seed
    counters: jax.Array,  # [B] int32 position of the token being sampled
    temperature: jax.Array,  # [B] f32; ignored where greedy
    top_k: jax.Array,  # [B] int32; <=0 disables
    top_p: jax.Array,  # [B] f32; 1.0 disables
    greedy: jax.Array,  # [B] bool
) -> jax.Array:
    """Sample next token ids [B] int32.

    Three branches, chosen batch-wide from the requests' parameters and
    never from the logits: a batch with no sampled row pays the argmax; a
    batch whose sampled rows have no active top-k/top-p pays one
    ``categorical`` over the temperature-scaled logits; otherwise every
    row's keep-set comes from ``_keep_set`` (a threshold search: no sort of
    the vocabulary, no scatter, and a cost that does not depend on what
    the distributions look like) and the draw runs over the filtered
    logits in vocabulary order. The Gumbel noise thus pairs with token
    *ids*, and the same (seed, counter) yields the same token whichever
    branch - or batch mix - executes it. One compiled program serves
    every mix; the conditions are data, not shapes.
    """
    V = logits.shape[-1]
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / temp
    keys = row_keys(seeds, counters)
    categorical_rows = jax.vmap(jax.random.categorical)

    def _filtered_sample() -> jax.Array:
        k_eff = jnp.where(top_k <= 0, V, jnp.minimum(top_k, V))[:, None]
        # top_p >= 1.0 means disabled: compare against 2.0 so fp32 rounding
        # of the mass (reaching exactly 1.0 at a tail token) can never mask
        # a token a plain categorical could draw - keeping the
        # keep-everything row *exactly* equal to _plain_sample.
        p_eff = jnp.where(top_p >= 1.0, 2.0, top_p)[:, None]
        filtered = jnp.where(
            _keep_set(scaled, k_eff, p_eff), scaled,
            float(jnp.finfo(jnp.float32).min),
        )
        return categorical_rows(keys, filtered).astype(jnp.int32)

    def _plain_sample() -> jax.Array:
        # No top-k/top-p anywhere in the batch: categorical over the
        # temperature-scaled logits needs no keep-set.
        return categorical_rows(keys, scaled).astype(jnp.int32)

    any_sampled = jnp.any(~greedy)
    needs_filter = jnp.any(
        (~greedy) & ((top_k > 0) | (top_p < 1.0))
    )
    sampled_tok = jax.lax.cond(
        any_sampled,
        lambda: jax.lax.cond(needs_filter, _filtered_sample, _plain_sample),
        lambda: greedy_tok,
    )
    return jnp.where(greedy, greedy_tok, sampled_tok)
