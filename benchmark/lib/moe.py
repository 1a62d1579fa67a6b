"""What the per-layer metrics of routed experts and of a latent (MLA) cache
share: which ops of a reduced profile are theirs, and the bytes and
operations a decode step of each needs, from a configuration's keys alone.

``lib/xplane.py`` keeps an op's NAME, which carries the shapes of its output
and first operands and no scope (``lib/ssm.py`` has the whole story), and only
the 40 ops with most self time reach a reader. So ops are told by shapes that
only these layers have:

- the grouped matmul over the experts (``GROUPED``): the stacked experts'
  ``[experts, hidden, width]`` / ``[experts, width, hidden]`` among the
  operands, or an output of ``[pairs, width]`` / ``[pairs, hidden]`` with
  ``pairs`` a whole number of 128-row tiles (``ops/moe.py``), or the kernel's
  own name (``gmm``);
- the rest of the expert layer (``MOE``): the router's ``[tokens, experts]``
  (2-D: a head's 128 is the last of four), the shared expert's width, and the
  sort and gathers over ``[pairs]`` / ``[pairs, hidden]``;
- what READS the latent pool (``POOL``): the pool itself, a gathered view
  ``[rows, context, row]`` or ``[rows, blocks, block, row]`` of it, and the
  scores ``[rows, .., heads, .., context]``;
- the rest of latent attention (``MLA``): anything else that shows the pool's
  row, the latent beside the rotary key, or the latent's width under the
  head count.

An op whose name shows none of them is NOT counted: every share of busy time
here is a floor, and every roofline share a ceiling, by that much. A program
without experts or a latent pool (every configuration without the
``n_routed_experts`` / ``kv_lora_rank`` keys, and the parent of the PR that
added this) gives ``None`` everywhere.

The five readers at the end (``moe_pct``, ``moe_decode_roofline``,
``moe_tokens_per_expert``, ``mla_pct``, ``mla_decode_roofline``: each takes a
reader's ``ctx``) are NOT per-layer metrics of the manifest yet. The accepted
``tests/benchmark/test_bench_sampler_search.py`` pins ``per_layer[-1]``, so
no PR but a ``benchmark`` PR can append an entry, and
``test_bench_manifest.py`` wants every file under ``layer_metrics/``
declared, so they cannot lie there undeclared. Declaring one is a file
``layer_metrics/<name>.py`` of one line (``from benchmark.lib.moe import
<name> as read``) and its entry. They read on the chip as PERF.md section 5
gives them (my chip runs, PR 38, when they were declared).
"""

from __future__ import annotations

from benchmark.lib import reduce, spans
from benchmark.lib.costs import DTYPE_BYTES
from benchmark.lib.ssm import _dims

TILE = 128  # rows of one tile of the grouped matmul (ops/moe.py: TILE_M)


def sizes(hf: dict) -> dict | None:
    """The layers' sizes from a configuration's keys; None without them."""
    if "n_routed_experts" not in hf or "kv_lora_rank" not in hf:
        return None
    latent = hf["kv_lora_rank"] + hf["qk_rope_head_dim"]
    return {
        "hidden": hf["hidden_size"], "experts": hf["n_routed_experts"],
        "top_k": hf["num_experts_per_tok"],
        "width": hf["moe_intermediate_size"],
        "shared": hf["n_shared_experts"] * hf["moe_intermediate_size"],
        "moe_layers": hf["num_hidden_layers"] - hf["first_k_dense_replace"],
        "layers": hf["num_hidden_layers"],
        "heads": hf["num_attention_heads"], "lora": hf["kv_lora_rank"],
        "latent": latent, "row": -(-latent // 128) * 128,
    }


def _is_context(d: int, block: int = 16) -> bool:
    """A dimension that can be a row's context: whole blocks, 256 and up."""
    return d >= 256 and d % block == 0


def shape_kind(dims: list[int], z: dict) -> str | None:
    """``GROUPED`` / ``MOE`` / ``POOL`` / ``MLA`` for one shape, else None."""
    E, N, I, H = z["hidden"], z["experts"], z["width"], z["heads"]
    if dims[-3:] in ([N, E, I], [N, I, E]):
        return "GROUPED"
    if len(dims) == 2 and dims[0] % TILE == 0 and dims[0] % z["top_k"] == 0 \
            and dims[1] in (I, E):
        # [pairs, width] is the grouped matmul's alone; [pairs, hidden] is
        # also the gathers before and after it
        return "GROUPED" if dims[1] == I else "MOE"
    if dims[-1] == z["shared"] or dims[-2:] in ([E, z["shared"]], [z["shared"], E]):
        return "MOE"
    if len(dims) == 2 and dims[1] == N and dims[0] != E:
        return "MOE"
    if dims[-1] in (z["row"], z["latent"]):
        # the pool, a gathered view of it (4,096 slots and up), or the
        # weighted sum over one: [.., heads, row]
        slots = 1
        for d in dims[:-1]:
            slots *= d
        return "POOL" if slots >= 4096 or H in dims[:-1] else "MLA"
    if len(dims) >= 3 and H in dims[:-1] and _is_context(dims[-1]) \
            and dims[-1] not in (E, z["lora"]):
        return "POOL"  # scores [rows, .., heads, .., context]
    if dims[-2:] == [H, z["lora"]]:
        return "MLA"
    return None


_RANK = {"GROUPED": 3, "POOL": 3, "MOE": 2, "MLA": 1}


def op_kind(name: str, z: dict) -> str | None:
    """The kind of the op ``name``: the strongest that any of its shapes
    says (the grouped matmul and the pool's readers before the rest)."""
    if "gmm" in name:
        return "GROUPED"
    kinds = {shape_kind(d, z) for d in _dims(name) if d} - {None}
    return max(kinds, key=_RANK.get) if kinds else None


def op_seconds(ctx: dict) -> dict | None:
    """Device self-time in the traced window by kind; None without a trace
    or the configuration's keys."""
    trace, cell = ctx.get("trace"), ctx.get("cell") or {}
    z = sizes(cell.get("model") or {})
    if not z or not trace or not trace.get("ops"):
        return None
    out = {"GROUPED": 0.0, "MOE": 0.0, "POOL": 0.0, "MLA": 0.0}
    for name, seconds in trace["ops"]:
        kind = op_kind(name, z)
        if kind:
            out[kind] += seconds
    return out


def moe_delta(ctx: dict) -> dict | None:
    """``moe.pairs``, ``moe.experts_hit`` and ``moe.layer_steps`` of the
    ``loop`` block of /metrics over the window; None where the program does
    not count them or counted no layer step."""
    d = spans.loop_delta(ctx)
    if d is None or not d.get("moe.layer_steps"):
        return None
    return {k: d[f"moe.{k}"] for k in ("pairs", "experts_hit", "layer_steps")}


def steps_in_trace(ctx: dict) -> float | None:
    """Decode steps dispatched inside the traced interval (the loop track's
    ``sched.dispatch`` spans, ``chunks`` x ``k``), else the window's
    ``loop.decode_steps`` scaled by the traced share of the window."""
    steps = sum(k for _t, k in reduce.dispatches_in_trace(ctx))
    if steps:
        return steps
    trace = ctx.get("trace") or {}
    d, w = spans.loop_delta(ctx), ctx.get("window") or {}
    if d is None or not d.get("decode_steps") or "t_stop" not in trace \
            or not w.get("w1", 0) > w.get("w0", 0):
        return None
    return d["decode_steps"] * (trace["t_stop"] - trace["t_start"]) / (w["w1"] - w["w0"])


def expert_bytes(z: dict, dtype: str) -> int:
    """One expert's three matrices."""
    return 3 * z["hidden"] * z["width"] * DTYPE_BYTES[dtype]


def grouped_floor_s(z: dict, dtype: str, peaks: dict, *, hit: float,
                    pairs: float) -> float:
    """The least time the grouped matmuls of ONE step can take: in every
    expert layer the ``hit`` experts' weights read once at the chip's
    bandwidth, or the ``pairs`` (token, expert) pairs' operations (two a
    weight) at its peak, whichever is longer."""
    t_mem = hit * expert_bytes(z, dtype) / peaks["hbm_bytes_per_s"]
    t_op = pairs * 6 * z["hidden"] * z["width"] / peaks["bf16_flops_per_s"]
    return z["moe_layers"] * max(t_mem, t_op)


def latent_bytes_per_token(z: dict, dtype: str) -> int:
    """What one token's latents hold over all layers (the numbers the
    architecture caches, not the pool's padded row)."""
    return z["layers"] * z["latent"] * DTYPE_BYTES[dtype]


# -- the five readers ---------------------------------------------------------


def _has_latent_pool(ctx: dict) -> bool:
    return "latent_bytes_per_token" in (
        (ctx.get("metrics_after") or {}).get("cache") or {})


def moe_pct(ctx: dict) -> float | None:
    """Kernels, %, lower: the expert layers' share of the device's busy time
    in the traced window - ``GROUPED`` + ``MOE`` self time over ``busy_s``
    (a floor). None for a program or a configuration without routed
    experts."""
    seconds = op_seconds(ctx)
    busy = (ctx.get("trace") or {}).get("busy_s")
    if seconds is None or not busy or not seconds["GROUPED"] + seconds["MOE"]:
        return None
    return 100.0 * (seconds["GROUPED"] + seconds["MOE"]) / busy


def moe_decode_roofline(ctx: dict) -> float | None:
    """Kernels, %, higher: the grouped matmul over the experts as a share of
    its roofline. ``grouped_floor_s`` at the window's ``experts_hit`` and
    ``pairs`` a layer step, over the measured time a step of the
    grouped-matmul ops ALONE (their self time in the traced window over the
    steps dispatched meanwhile). Router, sort and shared expert are left out
    on BOTH sides, so an op of theirs that is missed cannot push the share
    up; a grouped matmul that is missed can: a ceiling by what ``op_kind``
    does not recognise. Mixed steps are steps like any other on both sides.
    None without the counters, a trace, or a counted step."""
    seconds, d = op_seconds(ctx), moe_delta(ctx)
    if seconds is None or d is None or not seconds["GROUPED"] \
            or ctx.get("peaks") is None:
        return None
    steps = steps_in_trace(ctx)
    if not steps:
        return None
    cell = ctx["cell"]
    floor = grouped_floor_s(
        sizes(cell["model"]), cell["config"]["dtype"], ctx["peaks"],
        hit=d["experts_hit"] / d["layer_steps"],
        pairs=d["pairs"] / d["layer_steps"],
    )
    return 100.0 * floor / (seconds["GROUPED"] / steps)


def moe_tokens_per_expert(ctx: dict) -> float | None:
    """Step programs, tokens, higher: live tokens an expert that is hit sees
    in a step, ``moe.pairs`` over ``moe.experts_hit`` over the window. How
    near the batch comes to a deployment's: at 1 every expert's weights are
    read for one token. None where the program does not count them."""
    d = moe_delta(ctx)
    if d is None or not d["experts_hit"]:
        return None
    return d["pairs"] / d["experts_hit"]


def mla_pct(ctx: dict) -> float | None:
    """Kernels, %, lower: latent attention's share of the device's busy time
    in the traced window - ``POOL`` + ``MLA`` self time over ``busy_s`` (a
    floor). None for a program or a configuration without a latent pool."""
    seconds = op_seconds(ctx)
    busy = (ctx.get("trace") or {}).get("busy_s")
    if seconds is None or not busy or not seconds["POOL"] + seconds["MLA"] \
            or not _has_latent_pool(ctx):
        return None
    return 100.0 * (seconds["POOL"] + seconds["MLA"]) / busy


def mla_decode_roofline(ctx: dict) -> float | None:
    """Kernels, %, higher: the read of the latent pool as a share of its
    roofline. The latents of every live row's context (rows and context from
    the harness's request log while the trace was taken) read once at the
    chip's bandwidth, over the measured time a step of the ``POOL`` ops
    (gather, scores, weighted sum). The projections are left out on both
    sides; a reader of the pool that is missed pushes the share up. None
    without a latent pool, a trace, a live row or a counted step."""
    seconds = op_seconds(ctx)
    if seconds is None or not seconds["POOL"] or ctx.get("peaks") is None \
            or not _has_latent_pool(ctx):
        return None
    steps = steps_in_trace(ctx)
    trace, cell = ctx["trace"], ctx["cell"]
    batch = reduce.batch_between(ctx["records"], trace["t_start"], trace["t_stop"])
    if not steps or not batch["rows"]:
        return None
    bytes_ = (batch["rows"] * batch["context"] * latent_bytes_per_token(
        sizes(cell["model"]), cell["config"]["dtype"]))
    return 100.0 * (bytes_ / ctx["peaks"]["hbm_bytes_per_s"]) / (
        seconds["POOL"] / steps)
