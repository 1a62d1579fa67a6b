"""Per-layer readers for a model that selects what attention reads
(``KeyeVL2``, PR 46: an indexer scores a row's context, the ``topk`` best
are attended): what the selection costs on the device, what it keeps, and how
near its read comes to the least the step must read.

``lib/xplane.py`` keeps an op's NAME, which carries the shapes of its output
and first operands and no scope, and only the 40 ops with most self time
reach a reader. So the selection's ops are told by shapes, by those that
cannot be mistaken in this family: a dimension that is the row's ring
(``serve.max_seq_len``: the views of keys, values and indexer keys gathered
for a step, the indexer's scores, the selection's passes and the attention
scores over them all show it), the ring plus a step's fresh tokens (plus 1,
plus ``serve.chunked_prefill``), or the rows' block count (``rows x ring /
16``: the gather's indices). The hidden size, the experts' widths, ``topk``
(2,048 is also the hidden size and a 32 x 64 mixed step's tokens) and the
indexer's small projections are NOT counted: the share of busy time is a
floor, the roofline share a ceiling, by that much. A program or a
configuration without an indexer gives ``None`` everywhere.

The three readers at the end each take a reader's ``ctx``. They are NOT
per-layer metrics of the manifest, for the reason ``lib/moe.py`` gives: the
accepted tests pin the last ``per_layer`` entry and want every file under
``layer_metrics/`` declared, so only a ``benchmark`` PR can declare one
(``layer_metrics/<name>.py``: ``from benchmark.lib.dsa import <name> as
read``; ``dsa_pct`` and ``dsa_read_roofline``: layer ``kernels``, moves
``tpot_p90_ms``, ``device_trace``; ``dsa_kept_share``: layer ``step
programs``, ``program_counter``). They read on the chip as PERF.md section 5
gives them (my chip run, PR 46).
"""

from __future__ import annotations

from benchmark.lib import spans
from benchmark.lib.costs import DTYPE_BYTES
from benchmark.lib.moe import steps_in_trace
from benchmark.lib.ssm import _dims

BLOCK = 16  # slots a block of the paged pools holds (the engine's default)


def sizes(cell: dict) -> dict | None:
    """What tells the selection's ops and prices its read, from a cell's
    configuration and envelope; None without an indexer's keys."""
    hf, serve = cell.get("model") or {}, cell.get("serve") or {}
    sa = hf.get("sa_config")
    if not sa or "max_seq_len" not in serve:
        return None
    ring = serve["max_seq_len"]
    return {
        "ring": ring, "rows": serve["rows"],
        "chunk": serve.get("chunked_prefill") or 1,
        "topk": sa["topk"], "layers": hf["num_hidden_layers"],
        # what a step must read of a cached token: its indexer key (the
        # key's own float32, not the lane tile the pool pads it to), and, if
        # it is kept, its keys and values
        "index_bytes": sa["indexer_head_dim"] * 4,
        "kv_bytes": 2 * hf["num_key_value_heads"] * hf["head_dim"]
        * DTYPE_BYTES[(cell.get("config") or {}).get("dtype", "bfloat16")],
    }


def is_selection_op(name: str, z: dict) -> bool:
    """Whether one of the op's shapes shows the ring, the ring plus a step's
    fresh tokens, or the rows' blocks."""
    T = z["ring"]
    marks = {T, T + 1, T + z["chunk"], z["rows"] * T // BLOCK}
    return any(marks & set(dims) for dims in _dims(name))


def op_seconds(ctx: dict) -> float | None:
    """Device self time of the selection's ops in the traced window; None
    without a trace or an indexer."""
    trace, z = ctx.get("trace"), sizes(ctx.get("cell") or {})
    if not z or not trace or not trace.get("ops"):
        return None
    return sum(s for name, s in trace["ops"] if is_selection_op(name, z))


def dsa_delta(ctx: dict) -> dict | None:
    """The window's ``loop.dsa.*`` counters (``scored``, ``kept``,
    ``dense_rows``, ``rows``) and its steps; None where the program counts
    none (the parent of the PR that added them, another family) or counted
    no row."""
    d = spans.loop_delta(ctx)
    if d is None or not d.get("dsa.rows"):
        return None
    return {k: d[f"dsa.{k}"] for k in ("scored", "kept", "dense_rows", "rows")
            } | {"steps": d.get("decode_steps", 0)}


def read_floor_s(z: dict, peaks: dict, *, scored: float, kept: float) -> float:
    """The least time to read what the selection must: the indexer key of
    every position scored and the keys and values of every position kept,
    at the chip's bandwidth. From the two counters, which sum over the LIVE
    rows alone: a row that is done is not charged (PR 45 (1))."""
    return (scored * z["index_bytes"] + kept * z["kv_bytes"]) / (
        peaks["hbm_bytes_per_s"])


# -- the three readers --------------------------------------------------------


def dsa_pct(ctx: dict) -> float | None:
    """Kernels, %, lower: the selection's share of the device's busy time in
    the traced window - the views' gathers, the indexer's scores, the
    selection and the attention over it (a floor: its small projections are
    left out)."""
    seconds = op_seconds(ctx)
    busy = (ctx.get("trace") or {}).get("busy_s")
    if not seconds or not busy or dsa_delta(ctx) is None:
        return None
    return 100.0 * seconds / busy


def dsa_kept_share(ctx: dict) -> float | None:
    """Step programs, %: positions kept of positions scored over the
    window's live rows, layers and steps: ``topk`` over the mean context
    where every context is longer than ``topk``."""
    d = dsa_delta(ctx)
    if d is None or not d["scored"]:
        return None
    return 100.0 * d["kept"] / d["scored"]


def dsa_read_roofline(ctx: dict) -> float | None:
    """Kernels, %, higher: ``read_floor_s`` at the window's mean ``scored``
    and ``kept`` a step, over the measured time a step of the selection's
    ops. The floor counts live rows only and the least bytes a position, so
    it stays under 100 however few rows are live; an op of the selection
    that is missed pushes the share up: a ceiling. None without the
    counters, a trace or a counted step."""
    seconds, d = op_seconds(ctx), dsa_delta(ctx)
    if not seconds or d is None or not d["steps"] or ctx.get("peaks") is None:
        return None
    steps = steps_in_trace(ctx)
    if not steps:
        return None
    floor = read_floor_s(
        sizes(ctx["cell"]), ctx["peaks"],
        scored=d["scored"] / d["steps"], kept=d["kept"] / d["steps"])
    return 100.0 * floor / (seconds / steps)
