"""The comparison that decides the numerical half of ``correct``: the
engine's prefill logits and one cached decode step through its cache,
against the configuration's plain float32 reference. Runs in the server
process, after the window, outside every timing.

What is the yardstick's is here: the prompts, the two engine calls, the
float32 forward of prompt + first token one layer at a time, the error
statistic, the tolerance, and the rule that a control must FAIL it. What is a
model family's is in ``benchmark/reference/<model_type>.py``: the config's
keys, the parameter tree's layout, the layers and their kinds, and the one
fault its control plants (the contract is in benchmark/README.md).

``engine_logits``, ``logits_error`` and the tolerance are taken over from
``chip_smoke.py`` (copied, not imported: a later PR may change the smoke,
not the yardstick)."""

from __future__ import annotations

# Tolerance in units of the reference logits' standard deviation over the
# vocabulary (max |engine - reference| / std(reference), worst prompt). The
# reference computes in float32 from the same stored weights, so the engine's
# whole error is the rounding of its own compute dtype. bfloat16: 8 bits of
# mantissa, rounded once per matmul output and residual add - some dozens of
# layers of relative 2^-9 errors adding in quadrature, and the worst of 4 x
# ~50k vocabulary entries is taken. chip_smoke's bound is 0.1, measured at
# 0.055-0.063 on an HF-initialised checkpoint (PR 21). On the weights the
# benchmark serves (init_params: every leaf N(0, 0.02), norm scales + 1) the
# same statistic reads 0.081-0.114 on the v5e over 16 checks of 7 seeds
# (mean 0.092, s.d. 0.009; my chip runs, PR 23), so 0.1 would fail every
# other run of a correct engine; 0.15 is six of those deviations above the
# mean. It still sits far below what a wrong model does: one dropped bias
# reads 0.18-0.23, the negative control every check makes (the reference
# module's ``control``: for the two families with biases, all of the blocks'
# projection biases dropped, as a loader that skips biases would) more, and
# a compute type with fewer mantissa bits than bfloat16 several times the
# bound. float32: accumulation-order noise, measured near 1e-6.
# Two bf16 engines compared with each other get twice the bf16 bound.
LOGITS_TOL = {"float32": 2e-3, "bfloat16": 0.15}

def load_reference(model_type: str):
    from benchmark.lib import manifest

    return manifest.load_module("reference", model_type)


def engine_logits(engine, prompts, *, params=None):
    """Next-token logits from the engine's prefill, and from one decode step
    through the engine's cache on the token the prefill picked (greedy).
    Returns ``(prefill [B, V], decode [B, V], first [B])``."""
    import jax.numpy as jnp
    import numpy as np

    from llmss_tpu.engine import GenerationParams

    params = engine.params if params is None else params
    B = len(prompts)
    ids, lens = engine._pad_prompts(prompts)
    sa = engine._sample_args(GenerationParams(is_greedy=True), B)
    cache = engine.new_paged_cache(B)
    tok, logits0, cache = engine._prefill(
        params, jnp.asarray(ids), cache, jnp.asarray(lens), sa,
    )
    _, logits1, _ = engine._decode(
        params, engine.canon_vec(tok), engine.canon_cache(cache),
        engine.canon_vec(jnp.asarray(lens)), sa,
    )
    return (np.asarray(logits0, np.float32), np.asarray(logits1, np.float32),
            np.asarray(tok).tolist())


def reference_logits(ref, hf: dict, params, prompts, first):
    """The reference's logits at each prompt's last position and at the
    position of ``first`` appended to it: one full forward of prompt+[first],
    sequences padded at the END to one length (causal, so padding cannot
    reach an earlier position). The module yields its own layers in order,
    ``(kind, lp)`` each; one layer at a time is upcast and run, and one
    program is jitted a kind."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    B = len(prompts)
    lens = np.asarray([len(p) for p in prompts])
    ids = np.zeros((B, int(lens.max()) + 1), np.int32)
    for i, (p, t) in enumerate(zip(prompts, first)):
        ids[i, : len(p) + 1] = list(p) + [t]
    steps = {}
    with jax.default_matmul_precision("highest"):
        h = ref.embed(hf, params, jnp.asarray(ids))
        for kind, lp in ref.layers(hf, params):
            if kind not in steps:
                steps[kind] = jax.jit(
                    lambda lp, x, kind=kind: ref.layer(hf, kind, lp, x))
            h = steps[kind](lp, h)
        rows = jnp.arange(B)
        pre = ref.head(hf, params, h[rows, lens - 1])
        dec = ref.head(hf, params, h[rows, lens])
    return np.asarray(pre, np.float32), np.asarray(dec, np.float32)


def logits_error(got, ref, rms: bool = False) -> float:
    """max |got - ref| over the vocabulary in units of std(ref), worst row
    (``rms``: the root mean square over the vocabulary instead of the max -
    printed beside it, judged by nothing yet)."""
    import numpy as np

    if got.shape != ref.shape or not np.isfinite(got).all():
        raise RuntimeError(f"logits {got.shape} vs reference {ref.shape}, "
                           f"finite: {bool(np.isfinite(got).all())}")
    d = got - ref
    per_row = np.sqrt((d * d).mean(-1)) if rms else np.abs(d).max(-1)
    return float(np.max(per_row / ref.std(-1)))


def check_prompts(vocab: int, seed: int, lo: int, hi: int, n: int = 4):
    import numpy as np

    rng = np.random.default_rng([seed, 0xC4EC])
    return [
        rng.integers(0, vocab, int(k)).tolist()
        for k in rng.integers(lo, hi + 1, n)
    ]


def compare(ref, hf: dict, params, prompts, run, tol: float) -> dict:
    """``run(params)`` - the system under test: ``(prefill [B, V], decode
    [B, V], first [B])`` on ``prompts`` - against the reference on the same
    parameters, and the negative control: ``run`` on the parameters with the
    reference module's one fault planted must FAIL the tolerance. A control
    that passes (a fault that changes nothing, a comparison that cannot see
    it) makes ``ok`` false."""
    got_pre, got_dec, first = run(params)
    ref_pre, ref_dec = reference_logits(ref, hf, params, prompts, first)
    errs = {"prefill": logits_error(got_pre, ref_pre),
            "decode": logits_error(got_dec, ref_dec)}
    fault, faulty = ref.control(params)
    ctl_pre, _, _ = run(faulty)
    control = logits_error(ctl_pre, ref_pre)
    worst = max(errs.values())
    return {
        "tolerance": tol, **errs, "control": control, "control_fault": fault,
        "rms": {"prefill": logits_error(got_pre, ref_pre, rms=True),
                "decode": logits_error(got_dec, ref_dec, rms=True),
                "control": logits_error(ctl_pre, ref_pre, rms=True)},
        "prompt_lens": [len(p) for p in prompts],
        "ok": bool(worst < tol and control > tol),
    }


def reference_check(engine, hf: dict, seed: int, lo: int, hi: int) -> dict:
    """Engine against reference on 4 seeded prompts of ``lo``..``hi``
    tokens, with the family's negative control."""
    import jax

    ref = load_reference(hf["model_type"])
    prompts = check_prompts(hf["vocab_size"], seed, lo, hi)

    def run(params):
        jax.block_until_ready(params)
        return engine_logits(engine, prompts, params=params)

    return compare(ref, hf, engine.params, prompts, run,
                   LOGITS_TOL[str(engine.cfg.compute_dtype)])
