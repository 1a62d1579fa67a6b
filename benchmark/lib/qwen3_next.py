"""Per-layer readers for ``qwen3_next`` (PR 44): the routed experts held as
one chip's share, and the gated delta rule with grouped value heads. What
``lib/moe.py`` and ``lib/gdn.py`` are for their families, for a
configuration whose keys they do not read: ``lib/moe.py: sizes`` wants
``n_routed_experts`` and ``kv_lora_rank`` and ``lib/gdn.py: sizes`` wants
``layer_types`` and sizes the values by the KEY heads, so both return
``None`` (or the wrong widths) here; neither is edited.

``lib/xplane.py`` keeps an op's NAME, which carries the shapes of its output
and first operands and no scope, and only the 40 ops with most self time
reach a reader. So ops are told by shapes, and this family's widths collide:
8,192 is the convolution's channels AND the gated query projection, 4,096 the
value heads' width AND the attention heads', 512 an expert's width, the
shared expert's, the router's outputs and the two KV heads'. What is
counted is what cannot be mistaken:

- ``GROUPED``: the grouped matmul's kernel by its name (``gmm``), an operand
  or result ``[.., held, hidden, width]`` / ``[.., held, width, hidden]``
  (the stacked experts), or ``[pairs, width]`` with ``pairs`` whole 128-row
  tiles of ``top_k`` choices;
- ``ROUTED``: the gathers around it, ``[pairs, hidden]``, and the router's
  top-k ``[tokens, top_k]`` (NOT a 1-D ``[pairs]``: the block tables'
  ``[rows x blocks]`` is 20,480 here, whole tiles of ten too, and it rides
  on the keys' and values' gather, 8 ms a step);
- ``STATE``: the delta rule's update, anything that shows a value head's
  state ``[.., Hv, Dk, Dv]``;
- ``MIXER``: the rest of the linear mixer that only it has: the window
  ``(K-1) x conv_dim``, ``[.., Hv, Dv]`` / ``[.., Hv, Dk]`` of rank 3 and up,
  and a projection whose stacked weight shows the LINEAR layers' count beside
  the hidden size and ``conv_dim`` or ``value_dim``.

The router's matmul, the shared expert and its gate are NOT counted (their
shapes are everyone's): every share of busy time here is a floor, every
roofline share a ceiling, by that much. A program or a configuration
without these layers gives ``None`` everywhere.

The six readers at the end each take a reader's ``ctx``. They are NOT
per-layer metrics of the manifest, for the reason ``lib/moe.py`` gives: the
accepted tests pin the last ``per_layer`` entry and want every file under
``layer_metrics/`` declared, so only a ``benchmark`` PR can declare one
(``layer_metrics/<name>.py``: ``from benchmark.lib.qwen3_next import <name>
as read``; layer ``kernels``, moves ``tpot_p90_ms``). They read on the chip
as PERF.md section 5 gives them (my chip run, PR 44).
"""

from __future__ import annotations

from benchmark.lib import moe, spans
from benchmark.lib.moe import grouped_floor_s, steps_in_trace
from benchmark.lib.ssm import _dims

TILE = 128  # rows of one tile of the grouped matmul (ops/moe.py: TILE_M)
LINEAR = "linear_attention"


def sizes(hf: dict) -> dict | None:
    """The layers' sizes from a configuration's keys; None without them.
    ``num_experts`` is what is HELD here (the router's width is
    ``expert_parallel.num_experts`` where the file states a share)."""
    if "num_experts" not in hf or "linear_num_value_heads" not in hf:
        return None
    every = hf.get("full_attention_interval", 4)
    n = hf["num_hidden_layers"]
    kinds = hf.get("layer_types") or [
        "full_attention" if (i + 1) % every == 0 else LINEAR for i in range(n)
    ]
    Hk, Hv = hf["linear_num_key_heads"], hf["linear_num_value_heads"]
    Dk, Dv = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    return {
        "hidden": hf["hidden_size"], "held": hf["num_experts"],
        "routed": (hf.get("expert_parallel") or hf)["num_experts"],
        "top_k": hf["num_experts_per_tok"],
        # every layer is an expert layer (``lib/moe.py: grouped_floor_s``)
        "width": hf["moe_intermediate_size"], "moe_layers": n,
        "linear_layers": kinds.count(LINEAR),
        "hk": Hk, "hv": Hv, "dk": Dk, "dv": Dv, "value": Hv * Dv,
        "conv": 2 * Hk * Dk + Hv * Dv, "taps": hf["linear_conv_kernel_dim"],
    }


def shape_kind(dims: list[int], z: dict) -> str | None:
    """``GROUPED`` / ``ROUTED`` / ``STATE`` / ``MIXER`` for one shape."""
    E, N, I, K = z["hidden"], z["held"], z["width"], z["top_k"]
    Hv, Dk, Dv = z["hv"], z["dk"], z["dv"]
    if dims[-3:] in ([N, E, I], [N, I, E]):
        return "GROUPED"
    tiles = dims[0] % TILE == 0 and dims[0] % K == 0
    if len(dims) == 2 and tiles and dims[1] in (I, E):
        return "GROUPED" if dims[1] == I else "ROUTED"
    if len(dims) == 2 and dims[1] == K and dims[0] > K:
        return "ROUTED"
    if dims[-3:] == [Hv, Dk, Dv] and len(dims) > 3:
        return "STATE"
    if dims[-1] == (z["taps"] - 1) * z["conv"]:
        return "MIXER"
    if len(dims) > 2 and dims[-2:] in ([Hv, Dv], [Hv, Dk]):
        return "MIXER"
    if len(dims) == 3 and dims[0] == z["linear_layers"] and (
            dims[1:] in ([E, z["conv"]], [E, z["value"]], [z["value"], E])):
        return "MIXER"
    return None


_RANK = {"GROUPED": 4, "STATE": 3, "ROUTED": 2, "MIXER": 1}


def op_kind(name: str, z: dict) -> str | None:
    """The kind of the op ``name``: the strongest that any of its shapes
    says."""
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
    out = dict.fromkeys(_RANK, 0.0)
    for name, seconds in trace["ops"]:
        kind = op_kind(name, z)
        if kind:
            out[kind] += seconds
    return out


def moe_delta(ctx: dict) -> dict | None:
    """``lib/moe.py: moe_delta`` with ``moe.pairs_elsewhere`` beside its
    three; None where the program does not count that one (the parent of
    the PR that added it) or counted no layer step."""
    d, counts = spans.loop_delta(ctx), moe.moe_delta(ctx)
    if counts is None or "moe.pairs_elsewhere" not in d:
        return None
    return {**counts, "pairs_elsewhere": d["moe.pairs_elsewhere"]}


def state_bytes_per_row(z: dict) -> int:
    """The float32 state of every value head over the linear layers."""
    return z["linear_layers"] * z["hv"] * z["dk"] * z["dv"] * 4


def state_update_floor_s(z: dict, peaks: dict, rows: float) -> float:
    """The least time the delta rule's update can take in one step over
    ``rows`` rows: every row's state read once and written once at the
    chip's bandwidth (8 operations a state element are far under the
    peak)."""
    return 2 * rows * state_bytes_per_row(z) / peaks["hbm_bytes_per_s"]


# -- the six readers ----------------------------------------------------------


def _busy(ctx: dict) -> float | None:
    return (ctx.get("trace") or {}).get("busy_s")


def experts_pct(ctx: dict) -> float | None:
    """Kernels, %, lower: the routed experts' share of the device's busy
    time in the traced window - ``GROUPED`` + ``ROUTED`` self time over
    ``busy_s`` (a floor: the router's matmul and the shared expert are left
    out). None for a program or a configuration without them."""
    seconds, busy = op_seconds(ctx), _busy(ctx)
    if seconds is None or not busy or moe_delta(ctx) is None \
            or not seconds["GROUPED"] + seconds["ROUTED"]:
        return None
    return 100.0 * (seconds["GROUPED"] + seconds["ROUTED"]) / busy


def experts_grouped_roofline(ctx: dict) -> float | None:
    """Kernels, %, higher: the grouped matmul over the held experts as a
    share of its roofline at the experts ACTUALLY hit: ``grouped_floor_s``
    at the window's ``experts_hit`` and ``pairs`` a layer step, over the
    measured time a step of the ``GROUPED`` ops alone. A grouped matmul
    that is missed pushes the share up: a ceiling. None without the
    counters, a trace, or a counted step."""
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


def experts_tokens_per_expert(ctx: dict) -> float | None:
    """Step programs, tokens, higher: live tokens an expert that is hit sees
    in a step, ``moe.pairs`` over ``moe.experts_hit`` over the window: how
    near the batch comes to the deployment's load an expert."""
    d = moe_delta(ctx)
    if d is None or not d["experts_hit"]:
        return None
    return d["pairs"] / d["experts_hit"]


def experts_elsewhere_pct(ctx: dict) -> float | None:
    """Step programs, %: the live pairs routed to experts held on other
    chips, of all live pairs: what shows the share (three quarters at a
    quarter of the experts under uniform routing)."""
    d = moe_delta(ctx)
    if d is None or not d["pairs"] + d["pairs_elsewhere"]:
        return None
    return 100.0 * d["pairs_elsewhere"] / (d["pairs"] + d["pairs_elsewhere"])


def gdn_pct(ctx: dict) -> float | None:
    """Kernels, %, lower: the linear mixers' share of the device's busy time
    in the traced window - ``STATE`` + ``MIXER`` self time over ``busy_s``
    (a floor)."""
    seconds, busy = op_seconds(ctx), _busy(ctx)
    if seconds is None or not busy or not seconds["STATE"] + seconds["MIXER"]:
        return None
    return 100.0 * (seconds["STATE"] + seconds["MIXER"]) / busy


def gdn_update_roofline(ctx: dict) -> float | None:
    """Kernels, %, higher: the delta rule's update as a share of its
    roofline. ``state_update_floor_s`` over ALL the cell's rows (the program
    updates every row's state, live or done) over the measured time a step
    of the ``STATE`` ops: the slices, the reads, the write. An op of the
    update that is missed pushes the share up: a ceiling. None without such
    layers, a trace or a counted step."""
    seconds = op_seconds(ctx)
    if seconds is None or not seconds["STATE"] or ctx.get("peaks") is None:
        return None
    steps = steps_in_trace(ctx)
    if not steps:
        return None
    cell = ctx["cell"]
    floor = state_update_floor_s(
        sizes(cell["model"]), ctx["peaks"], rows=cell["serve"]["rows"])
    return 100.0 * floor / (seconds["STATE"] / steps)
