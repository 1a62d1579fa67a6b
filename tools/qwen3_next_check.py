"""The builder's comparisons for ``qwen3_next`` on the device the process
finds (PR 44), beside what ``benchmark/lib/check.py`` runs (the engine's
``_prefill`` and one ``_decode``): the MIXED step, which is the only step
program a cell with ``chunked_prefill`` times, against the float32 reference;
the three loader-style faults the reference's control was chosen among; and
the reference itself with its residual stream rounded, which says what the
tolerance can see. ``tools/olmo_hybrid_check.py`` has the machinery (one set
of seeded weights, ``check.logits_error`` of ``[the prompt's end, the token
decoded after it]``); this file adds the family's faults and, with
``--scale``, a draw with some leaves rescaled (how ``_routed_family_draw``'s
sizes for this family were chosen).

    python3 tools/qwen3_next_check.py benchmark/configs/qwen3-next-80b-a3b-1chip.json \\
        --seed 4100000009 --out chiprun_out/qwen3_next_check.jsonl
"""

from __future__ import annotations

import argparse
import json
import sys
import types
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.lib import check, manifest  # noqa: E402
from benchmark.server import _unit_norm_scales  # noqa: E402
from llmss_tpu.engine import DecodeEngine  # noqa: E402
from llmss_tpu.models import decoder  # noqa: E402
from llmss_tpu.models.registry import config_from_hf  # noqa: E402
from llmss_tpu.ops.layers import NormParams  # noqa: E402
from llmss_tpu.parallel import MeshPlan, initialize_runtime, make_mesh  # noqa: E402
from tools.olmo_hybrid_check import mixed_logits, rounded_reference  # noqa: E402


def faults(ref, params) -> dict:
    """The three loader-style faults of this family, each as the tree a
    loader that made it would leave; the first is the reference's own
    control."""
    blocks, linear = params["blocks"], params["linear"]

    def attn_gate_lost(s):  # the gate half of q_proj filled with zeros
        w = s["q"].w
        L, Q2, E = w.shape
        D = s["q_norm"].scale.shape[-1]
        w = w.reshape(L, Q2 // (2 * D), 2, D, E).at[:, :, 1].set(0)
        return {**s, "q": s["q"]._replace(w=w.reshape(L, Q2, E))}

    def centre_lost(s):  # the zero-centred scales read as plain ones
        return {k: v._replace(scale=v.scale - 1)
                if isinstance(v, NormParams) and k != "gdn_norm" else v
                for k, v in s.items()}

    name, gate_lost = ref.control(params)
    return {
        name: gate_lost,
        "attention_gate_lost": {**params, "blocks": attn_gate_lost(blocks)},
        "zero_centred_one_lost": {
            **params, "blocks": centre_lost(blocks),
            "linear": centre_lost(linear),
            "ln_f": centre_lost({"ln_f": params["ln_f"]})["ln_f"],
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--mixed-lens", type=int, nargs=2, default=(96, 256))
    ap.add_argument("--prefill-len", type=int, default=512)
    ap.add_argument("--paths", nargs="+", default=["mixed", "prefill"],
                    choices=["mixed", "prefill"])
    ap.add_argument("--scale", default="[{}]",
                    help='JSON list of draws to read, each leaf name -> '
                         'factor on the seeded draw, e.g. [{}, {"gdn_o": 0.5}]')
    ap.add_argument("--controls", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    conf = json.loads(Path(args.config).read_text())
    hf = {k: v for k, v in conf.items() if k not in manifest.HARNESS_KEYS}
    initialize_runtime()  # the persistent compile cache, as the server has it
    cfg = config_from_hf(types.SimpleNamespace(**hf), dtype=conf["dtype"])
    mesh = make_mesh(MeshPlan(tp=1), devices=jax.devices()[:1])
    drawn = _unit_norm_scales(decoder.init_params(
        cfg, mesh, jax.random.key(args.seed)))
    ref = check.load_reference(hf["model_type"])
    tol = check.LOGITS_TOL[conf["dtype"]]
    vocab = hf["vocab_size"]
    sets = {
        "mixed": check.check_prompts(vocab, args.seed, *args.mixed_lens),
        "prefill": check.check_prompts(
            vocab, args.seed, args.prefill_len, args.prefill_len),
    }
    out = open(args.out, "a") if args.out else None

    def say(row):
        line = json.dumps(row)
        print("QWEN3_NEXT_CHECK", line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for scale in json.loads(args.scale):
        read_draw(args, conf, hf, cfg, mesh, ref, tol, sets, say,
                  rescaled(drawn, scale), scale)


def rescaled(params, scale: dict):
    """``params`` with every leaf under a name of ``scale`` times its
    factor (multiplied in float32), the others shared."""
    def one(path, a):
        for p in reversed(path):
            if getattr(p, "key", None) in scale:
                f = scale[p.key]
                return jax.jit(
                    lambda x: (x.astype(jnp.float32) * f).astype(x.dtype))(a)
        return a

    return jax.tree_util.tree_map_with_path(one, params) if scale else params


def read_draw(args, conf, hf, cfg, mesh, ref, tol, sets, say, params, scale):
    say({"device": jax.devices()[0].device_kind, "dtype": conf["dtype"],
         "tolerance": tol, "seed": args.seed, "chunk": args.chunk,
         "scale": scale,
         "prompt_lens": {k: [len(p) for p in v] for k, v in sets.items()}})
    engine = DecodeEngine(cfg, params, mesh, kv_layout="paged",
                          max_seq_len=conf["serve"]["max_seq_len"])

    def run(path, weights):
        if path == "mixed":
            return mixed_logits(engine, weights, sets[path], args.chunk)
        return check.engine_logits(engine, sets[path], params=weights)

    for path in args.paths:
        pre, dec, first = run(path, params)
        want = check.reference_logits(ref, hf, params, sets[path], first)

        def read(what, got, path=path, want=want):
            errs = [check.logits_error(got[0], want[0]),
                    check.logits_error(got[1], want[1])]
            rms = [check.logits_error(got[0], want[0], rms=True),
                   check.logits_error(got[1], want[1], rms=True)]
            say({"what": what, "path": path, "scale": scale, "logits": errs,
                 "rms": rms, "correct": bool(max(errs) < tol)})

        read("program", (pre, dec))
        if not args.controls:
            continue
        for bits in (7, 3):
            low = check.reference_logits(
                rounded_reference(ref, bits), hf, params, sets[path], first)
            read(f"reference, residual at {bits} mantissa bits", low)
        for name, faulty in faults(ref, params).items():
            jax.block_until_ready(faulty)
            read(f"control: {name}", run(path, faulty)[:2])
            del faulty


if __name__ == "__main__":
    main()
