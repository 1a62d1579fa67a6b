"""The builder's comparisons for a model with linear-attention layers, on the
device the process finds (PR 40): what the benchmark's own check
(``benchmark/lib/check.py``: the engine's ``_prefill`` and one ``_decode``)
does not run, and the precision controls that say what its tolerance can see.

One set of seeded weights (the family's draw, norm scales + 1 as
``benchmark/server.py`` makes them) and one float32 reference
(``benchmark/reference/<model_type>.py``); every reading is
``check.logits_error``: max |logits - reference| over the vocabulary in units
of the reference's deviation, worst prompt, ``[at the prompt's end, at the
token decoded after it]``.

* ``mixed``: prompts fed through the MIXED step (``forward_ragged``, the only
  step program a cell with ``chunked_prefill`` times) ``--chunk`` tokens a row
  a step, then each row's first token decoded through the same program.
* ``prefill``: the engine's ``_prefill`` (the delta rule's chunked form at its
  chunk of 64) and one ``_decode``, on prompts of ``--prefill-len`` tokens.

Each path for the program as it is, with the delta rule's matmuls at DEFAULT
precision (one bfloat16 pass on a TPU), and with the state rounded to bfloat16
after every call of the delta rule (``lax.reduce_precision``, which a
compiler may not elide: every step of ``mixed``, the prompt's end and the
decoded token in ``prefill``); ``mixed`` also with the embedding a quarter the
size (size 1: why the draw has 4 is in ``decoder._gdn_family_draw``). And the
REFERENCE in the program's place with its residual stream rounded to 7
mantissa bits (bfloat16's) and to 3 (float8 e4m3's, the nearest precision
below) after the embedding and every layer, every product still float32: the
first is part of what any bfloat16 program must read, the second has to come
out NOT correct.

    python3 tools/olmo_hybrid_check.py benchmark/configs/olmo-hybrid-7b-1chip.json \\
        --seed 4100000009 --out chiprun_out/olmo_check.jsonl
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import sys
import types
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.lib import check, manifest  # noqa: E402
from benchmark.server import _unit_norm_scales  # noqa: E402
from llmss_tpu.engine import DecodeEngine  # noqa: E402
from llmss_tpu.models import decoder  # noqa: E402
from llmss_tpu.models.registry import config_from_hf  # noqa: E402
from llmss_tpu.ops import gdn  # noqa: E402
from llmss_tpu.parallel import MeshPlan, make_mesh  # noqa: E402


def mixed_logits(engine, params, prompts, chunk, cap=None):
    """``(logits at each prompt's end, logits at the token decoded after it,
    that token)`` through ``forward_ragged`` alone: every row advances
    ``chunk`` tokens a step until its prompt is in, then one more step of one
    token (the greedy pick), columns past a row's chunk no-ops. ``cap``: no
    more rows than that feed several tokens in one step, the others wait
    (``models/decoder.py: feed_rows``)."""
    cfg, mesh, max_len = engine.cfg, engine.mesh, engine.max_seq_len
    step = jax.jit(
        functools.partial(decoder.forward_ragged, cfg, mesh=mesh),
        donate_argnums=(3,),
    )
    B = len(prompts)
    cache = engine.new_paged_cache(B)
    seqs, fed, pre, dec = [list(p) for p in prompts], [0] * B, {}, {}
    rel = np.arange(chunk)[None]
    while len(dec) < B:
        ids = np.zeros((B, chunk), np.int32)
        q = np.zeros((B,), np.int32)
        feeding = 0
        for i, s in enumerate(seqs):
            c = s[fed[i]: fed[i] + chunk]
            if len(c) > 1:
                if feeding == cap:
                    continue  # waits its turn
                feeding += 1
            ids[i, :len(c)], q[i] = c, len(c)
        live = rel < q[:, None]
        pos = np.asarray(fed)[:, None] + rel
        logits, cache = step(
            params, jnp.asarray(ids), jnp.asarray(pos, jnp.int32), cache,
            jnp.asarray(np.where(live, pos, max_len), jnp.int32),
            jnp.asarray(np.maximum(q, 1)),
            kv_write_positions=jnp.asarray(np.where(live, pos, -1), jnp.int32),
        )
        got = None
        for i in range(B):
            fed[i] += int(q[i])
            if not q[i] or fed[i] < len(seqs[i]):
                continue
            got = np.asarray(logits, np.float32) if got is None else got
            if len(seqs[i]) == len(prompts[i]):
                pre[i] = got[i, 0]
                seqs[i].append(int(np.argmax(got[i, 0])))
            else:
                dec[i] = got[i, 0]
    rows = range(B)
    return (np.stack([pre[i] for i in rows]), np.stack([dec[i] for i in rows]),
            [seqs[i][-1] for i in rows])


@contextlib.contextmanager
def delta_rule(precision=None, state_bits=None):
    """The program's delta rule with its matmuls at ``precision`` and the
    state it returns rounded to ``state_bits`` of mantissa. Programs traced
    inside read the patched functions."""
    def rounded(fn):
        def wrapped(*a, **k):
            o, s = fn(*a, **k)
            return o, jax.lax.reduce_precision(s, 8, state_bits)
        return wrapped if state_bits else fn

    was = gdn._HI, decoder.gdn_step, decoder.gdn_chunked
    gdn._HI = was[0] if precision is None else precision
    decoder.gdn_step = rounded(gdn.gdn_step)
    decoder.gdn_chunked = rounded(gdn.gdn_chunked)
    try:
        yield
    finally:
        gdn._HI, decoder.gdn_step, decoder.gdn_chunked = was


def rounded_reference(ref, bits):
    """``ref`` with its residual stream rounded to ``bits`` of mantissa after
    the embedding and after every layer."""
    cut = lambda h: jax.lax.reduce_precision(h, 8, bits)
    return types.SimpleNamespace(
        embed=lambda hf, params, ids: cut(ref.embed(hf, params, ids)),
        layer=lambda hf, kind, lp, h: cut(ref.layer(hf, kind, lp, h)),
        layers=ref.layers, head=ref.head,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chunk", type=int, default=4)
    ap.add_argument("--mixed-lens", type=int, nargs=2, default=(96, 256))
    ap.add_argument("--prefill-len", type=int, default=512)
    ap.add_argument("--paths", nargs="+", default=["mixed", "prefill"],
                    choices=["mixed", "prefill"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    conf = json.loads(Path(args.config).read_text())
    hf = {k: v for k, v in conf.items() if k not in manifest.HARNESS_KEYS}
    cfg = config_from_hf(types.SimpleNamespace(**hf), dtype=conf["dtype"])
    mesh = make_mesh(MeshPlan(tp=1), devices=jax.devices()[:1])
    params = _unit_norm_scales(decoder.init_params(
        cfg, mesh, jax.random.key(args.seed)))
    small = {**params, "wte": params["wte"] * 0.25}
    ref = check.load_reference(hf["model_type"])
    tol = check.LOGITS_TOL[conf["dtype"]]
    vocab = hf["vocab_size"]
    sets = {
        "mixed": check.check_prompts(vocab, args.seed, *args.mixed_lens),
        "prefill": check.check_prompts(
            vocab, args.seed, args.prefill_len, args.prefill_len),
    }
    out = open(args.out, "w") if args.out else None

    def say(row):
        line = json.dumps(row)
        print("OLMO_CHECK", line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    say({"device": jax.devices()[0].device_kind, "dtype": conf["dtype"],
         "tolerance": tol, "seed": args.seed, "chunk": args.chunk,
         "prompt_lens": {k: [len(p) for p in v] for k, v in sets.items()}})

    wanted = {}

    def read(what, path, got, weights=params):
        """One reading: ``got`` = (prefill, decode, first) of the thing in
        the program's place, against the exact reference on the same weights
        and the same first tokens."""
        pre, dec, first = got
        key = (path, id(weights), tuple(first))
        if key not in wanted:
            print(f"[olmo_check] {what}: the reference", file=sys.stderr, flush=True)
            wanted[key] = check.reference_logits(
                ref, hf, weights, sets[path], first)
        want = wanted[key]
        errs = [check.logits_error(pre, want[0]), check.logits_error(dec, want[1])]
        rms = [check.logits_error(pre, want[0], rms=True),
               check.logits_error(dec, want[1], rms=True)]
        say({"what": what, "path": path, "logits": errs, "rms": rms,
             "correct": bool(max(errs) < tol)})
        return first

    def program(what, path, weights=params):
        print(f"[olmo_check] {what}: {path}", file=sys.stderr, flush=True)
        engine = DecodeEngine(cfg, weights, mesh, kv_layout="paged",
                              max_seq_len=conf["serve"]["max_seq_len"])
        got = (mixed_logits(engine, weights, sets[path], args.chunk)
               if path == "mixed"
               else check.engine_logits(engine, sets[path], params=weights))
        return read(what, path, got, weights)

    variants = [
        ("program", contextlib.nullcontext),
        ("delta rule's matmuls at default precision",
         lambda: delta_rule(precision=jax.lax.Precision.DEFAULT)),
        ("state rounded to bfloat16 after every call",
         lambda: delta_rule(state_bits=7)),
    ]
    # the path no other file runs on the device first, whole; then the
    # engine's own prefill and decode, which benchmark/lib/check.py runs too
    for path in args.paths:
        for what, patched in variants:
            with patched():
                first = program(what, path)
            if what != "program":
                continue
            for bits in (7, 3):
                low = check.reference_logits(
                    rounded_reference(ref, bits), hf, params, sets[path], first)
                read(f"reference, residual at {bits} mantissa bits", path,
                     (*low, first))
        if path == "mixed":
            program("embedding at size 1", path, small)


if __name__ == "__main__":
    main()
