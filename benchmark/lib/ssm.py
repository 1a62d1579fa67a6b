"""What the per-layer metrics of a Mamba-2 mixer share: which ops of a reduced
profile are the mixer's, and the operations and bytes of its decode update,
from a configuration's keys alone. (The scan of a dedicated admission has no
metric: its ops never reach the 40 a reader sees, PERF.md section 7. Where a
cell admits through the mixed step, ``serve.chunked_prefill``, the scan over a
chunk IS every row's update of that step and is counted as ``decode``.)

``lib/xplane.py`` keeps an op's NAME, which carries the shapes of its output
and first operands (``%fusion.383 = f32[5,64,32,128,256]{...} fusion(...``), and
no scope. So the mixer's ops are told by shapes that only the mixer has: the
recurrent state's ``[.., heads, head_dim, d_state]`` (also as ``[.., groups,
heads/groups, head_dim, d_state]``), the width of its input projection (z, x,
B, C, dt), the convolution window ``[.., d_conv-1, conv_dim]``, the output
projection's weight ``[d_ssm, hidden]`` and a row's ``[rows, d_ssm]``. An op of
the mixer whose name shows none of them is NOT counted: ``ssm_pct`` and the
time under the roofline are floors of the mixer's time, and the roofline a
ceiling of its share, by that much. Only the 40 ops with most self
time reach a reader (``benchmark/server.py``), which cuts the same way.

A program without a mixer (every configuration but those with the ``mamba_*``
keys, and the parent of the PR that added this) gives ``None`` everywhere.
"""

from __future__ import annotations

import re

from benchmark.lib.costs import DTYPE_BYTES


def sizes(hf: dict) -> dict | None:
    """The mixer's sizes from a configuration's keys; None without one."""
    if "mamba_d_state" not in hf:
        return None
    d_ssm = hf.get("mamba_d_ssm") or hf["mamba_expand"] * hf["hidden_size"]
    H, G, N = hf["mamba_n_heads"], hf["mamba_n_groups"], hf["mamba_d_state"]
    bc = G * N
    return {
        "layers": hf["num_hidden_layers"], "hidden": hf["hidden_size"],
        "d_ssm": d_ssm, "heads": H, "head_dim": hf["mamba_d_head"],
        "groups": G, "d_state": N, "d_conv": hf["mamba_d_conv"],
        "chunk": hf["mamba_chunk_size"], "conv_dim": d_ssm + 2 * bc,
        "proj": 2 * d_ssm + 2 * bc + H,
    }


_SHAPE = re.compile(r"(?:f32|bf16|f16|s32|u32|s8|u8|pred)[\[_](\d+(?:[,_]\d+)*)")


def _dims(name: str) -> list[list[int]]:
    """Every shape in an op's name, as lists of dimensions: ``f32[5,64,32]``
    (raw) or ``f32_5_64_32__`` (as the ledger keeps names) alike."""
    return [[int(d) for d in re.split(r"[,_]", m.group(1))]
            for m in _SHAPE.finditer(name)]


def _shape_kind(dims: list[int], z: dict, rows: int) -> str | None:
    """What one shape says: ``decode`` / ``prefill`` (an activation or a
    state of all ``rows`` rows, or of an admission's), ``held`` (the state
    pool or a weight of the mixer: whose step it is, the name's other shapes
    say), or None (not the mixer's)."""
    H, G, P, N = z["heads"], z["groups"], z["head_dim"], z["d_state"]
    for tail in ([H, P, N], [G, H // G, P, N], [z["d_conv"] - 1, z["conv_dim"]]):
        if len(dims) > len(tail) and dims[-len(tail):] == tail:
            lead = dims[:-len(tail)]
            if lead == [z["layers"], rows]:
                return "held"
            return "decode" if lead[-1] == rows else "prefill"
    if dims[-1] == z["proj"]:
        if dims[-2:] == [z["hidden"], z["proj"]]:
            return "held"
        return "decode" if dims[0] == rows else "prefill"
    if dims[-2:] == [z["d_ssm"], z["hidden"]]:
        return "held"
    if dims[-1] == z["d_ssm"] and dims[0] == rows and all(
            d == 1 for d in dims[1:-1]):
        return "decode"
    return None


def op_kind(name: str, z: dict, rows: int) -> str | None:
    """``"decode"`` / ``"prefill"`` for an op of the mixer, told by its name
    (see the module's docstring), else None. An op that shows only the pool
    or a weight is the decode update's: hundreds of steps to an admission."""
    kinds = {_shape_kind(d, z, rows) for d in _dims(name)} - {None}
    if not kinds:
        return None
    return "prefill" if "prefill" in kinds else "decode"


def mixer_seconds(ctx: dict) -> dict | None:
    """Device self-time of the mixer's ops in the traced window, by kind:
    ``{"decode": s, "prefill": s}``; None without a trace or a mixer."""
    trace, cell = ctx.get("trace"), ctx.get("cell") or {}
    z = sizes(cell.get("model") or {})
    if not z or not trace or not trace.get("ops"):
        return None
    rows = cell["serve"]["rows"]
    out = {"decode": 0.0, "prefill": 0.0}
    for name, seconds in trace["ops"]:
        kind = op_kind(name, z, rows)
        if kind:
            out[kind] += seconds
    return out


def weight_bytes(z: dict, dtype: str) -> int:
    """The mixer's weights over all layers: both projections, the
    convolution, ``A_log``, ``dt_bias``, ``D`` and the gated norm's scale."""
    per_layer = (
        z["hidden"] * z["proj"] + z["d_ssm"] * z["hidden"]
        + z["conv_dim"] * (z["d_conv"] + 1) + 3 * z["heads"] + z["d_ssm"]
    )
    return z["layers"] * per_layer * DTYPE_BYTES[dtype]


def state_bytes_per_row(z: dict, dtype: str) -> int:
    """Float32 state and the window in the compute dtype, over all layers."""
    return z["layers"] * (
        z["heads"] * z["head_dim"] * z["d_state"] * 4
        + (z["d_conv"] - 1) * z["conv_dim"] * DTYPE_BYTES[dtype]
    )


def decode_update_floor_s(z: dict, dtype: str, peaks: dict, rows: float) -> float:
    """The least time the mixer can take in one decode step over ``rows``
    rows: every row's state read and written once and the mixer's weights
    read once, at the chip's bandwidth (its operations, 6 a state element
    and 2 a weight and row, are far under the peak at these rows)."""
    bytes_ = 2 * rows * state_bytes_per_row(z, dtype) + weight_bytes(z, dtype)
    flops = rows * z["layers"] * (
        6 * z["heads"] * z["head_dim"] * z["d_state"]
        + 2 * (z["hidden"] * z["proj"] + z["d_ssm"] * z["hidden"])
    )
    return max(bytes_ / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops_per_s"])
