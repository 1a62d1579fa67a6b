"""What the per-layer metrics of linear-attention (gated delta rule) layers
share: which ops of a reduced profile are theirs, and the bytes and operations
a decode step of their mixer needs, from a configuration's keys alone.

``lib/xplane.py`` keeps an op's NAME, which carries the shapes of its output
and first operands and no scope (``lib/ssm.py`` has the whole story), and only
the 40 ops with most self time reach a reader. So the mixer's ops are told by
shapes that only these layers have: a head's state ``[.., heads, Dk, Dv]`` or
its flattened form ``[.., heads, Dk * Dv]`` as the pool holds it; the
convolution's ``conv_dim`` channels (q, k and v side by side: 11,520) and the
window ``(K-1) * conv_dim``; the values' ``heads * Dv`` (the output gate, the
gated norm, the output projection: 5,760) and the keys' ``heads * Dk``
(2,880); ``[.., heads, Dk]`` and ``[.., heads, Dv]``; the ``2 * heads``
columns of a and b. The layers' MLPs and the attention layers show none of
these. An op of the mixer whose name shows none of them is NOT counted:
``gdn_pct`` is a floor of the mixer's share and ``gdn_decode_roofline`` a
ceiling of its, by that much.

A program or a configuration without such layers (no ``linear_*`` keys; the
parent of the PR that added this, whose ``/metrics`` has no
``cache.state_layers``) gives ``None`` everywhere.

The two readers at the end (``gdn_pct``, ``gdn_decode_roofline``: each takes a
reader's ``ctx``) are NOT per-layer metrics of the manifest yet, for the
reason ``lib/moe.py`` gives for its five: the accepted tests pin the last
``per_layer`` entry and want every file under ``layer_metrics/`` declared, so
only a ``benchmark`` PR can declare a reader. Declaring one is a file
``layer_metrics/<name>.py`` of one line (``from benchmark.lib.gdn import
<name> as read``) and its entry (layer ``kernels``, moves ``tpot_p90_ms``).
"""

from __future__ import annotations

from benchmark.lib.costs import DTYPE_BYTES
from benchmark.lib.moe import steps_in_trace
from benchmark.lib.ssm import _dims


def sizes(hf: dict) -> dict | None:
    """The mixer's sizes from a configuration's keys; None without them."""
    if "linear_key_head_dim" not in hf or "layer_types" not in hf:
        return None
    H, Dk, Dv = (hf["linear_num_key_heads"], hf["linear_key_head_dim"],
                 hf["linear_value_head_dim"])
    return {
        "layers": hf["layer_types"].count("linear_attention"),
        "hidden": hf["hidden_size"], "heads": H, "dk": Dk, "dv": Dv,
        "key": H * Dk, "value": H * Dv, "conv": 2 * H * Dk + H * Dv,
        "taps": hf["linear_conv_kernel_dim"],
    }


def is_mixer_shape(dims: list[int], z: dict) -> bool:
    """Whether one shape can only be the linear-attention mixer's."""
    H, Dk, Dv = z["heads"], z["dk"], z["dv"]
    if dims[-3:] == [H, Dk, Dv] or dims[-2:] in (
            [H, Dk * Dv], [H, Dk], [H, Dv]):
        return len(dims) > 2
    return dims[-1] in (
        z["conv"], (z["taps"] - 1) * z["conv"], z["value"], z["key"], 2 * H,
    ) or dims[-2:] == [z["value"], z["hidden"]]


def is_mixer_op(name: str, z: dict) -> bool:
    return any(is_mixer_shape(d, z) for d in _dims(name) if d)


def mixer_seconds(ctx: dict) -> float | None:
    """Device self-time of the mixer's ops in the traced window; None
    without a trace or the configuration's keys."""
    trace, cell = ctx.get("trace"), ctx.get("cell") or {}
    z = sizes(cell.get("model") or {})
    if not z or not trace or not trace.get("ops"):
        return None
    return sum(s for name, s in trace["ops"] if is_mixer_op(name, z))


def weight_bytes(z: dict, dtype: str) -> int:
    """The mixer's weights over all linear-attention layers: the q, k, v,
    a, b and gate projections, the output projection, the convolutions,
    ``A_log``, ``dt_bias`` and the gated norm's scale."""
    per_layer = (
        z["hidden"] * (z["conv"] + 2 * z["heads"] + z["value"])
        + z["value"] * z["hidden"] + z["taps"] * z["conv"]
        + 2 * z["heads"] + z["dv"]
    )
    return z["layers"] * per_layer * DTYPE_BYTES[dtype]


def state_bytes_per_row(z: dict, dtype: str) -> int:
    """Float32 state and the window in the compute dtype, over all
    linear-attention layers."""
    return z["layers"] * (
        z["heads"] * z["dk"] * z["dv"] * 4
        + (z["taps"] - 1) * z["conv"] * DTYPE_BYTES[dtype]
    )


def decode_update_floor_s(z: dict, dtype: str, peaks: dict, rows: float) -> float:
    """The least time the mixers can take in one decode step over ``rows``
    rows: every row's state read and written once and the mixers' weights
    read once, at the chip's bandwidth (their operations, 8 a state element
    (decay, ``S^T k``, the rank-one write, ``S^T q``) and 2 a weight and
    row, are far under the peak at these rows)."""
    bytes_ = 2 * rows * state_bytes_per_row(z, dtype) + weight_bytes(z, dtype)
    flops = rows * z["layers"] * (
        8 * z["heads"] * z["dk"] * z["dv"]
        + 2 * weight_bytes(z, dtype) / DTYPE_BYTES[dtype] / z["layers"]
    )
    return max(bytes_ / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops_per_s"])


# -- the two readers ----------------------------------------------------------


def _has_state_layers(ctx: dict) -> bool:
    return "state_layers" in (
        (ctx.get("metrics_after") or {}).get("cache") or {})


def gdn_pct(ctx: dict) -> float | None:
    """Kernels, %, lower: the linear-attention mixers' share of the device's
    busy time in the traced window - the self time of the ops told as theirs
    by shape over ``busy_s`` (a floor). None for a program or a
    configuration without such layers."""
    seconds = mixer_seconds(ctx)
    busy = (ctx.get("trace") or {}).get("busy_s")
    if not seconds or not busy or not _has_state_layers(ctx):
        return None
    return 100.0 * seconds / busy


def gdn_decode_roofline(ctx: dict) -> float | None:
    """Kernels, %, higher: the mixers' step as a share of its roofline.
    ``decode_update_floor_s`` over ALL the cell's rows (the program updates
    every row's state, live or done, so the share follows the kernel and not
    the occupancy) over the measured time a step of the mixer's ops: their
    self time in the traced window over the steps dispatched meanwhile. A
    mixed step is a step like any other on both sides (its prompt tokens add
    operations, not bytes). An op of the mixer that is missed pushes the
    share up: a ceiling. None without such layers, a trace or a counted
    step."""
    seconds = mixer_seconds(ctx)
    if not seconds or ctx.get("peaks") is None or not _has_state_layers(ctx):
        return None
    steps = steps_in_trace(ctx)
    if not steps:
        return None
    cell = ctx["cell"]
    floor = decode_update_floor_s(
        sizes(cell["model"]), cell["config"]["dtype"], ctx["peaks"],
        rows=cell["serve"]["rows"],
    )
    return 100.0 * floor / (seconds / steps)
