"""Operations and bytes of a decode or prefill step, from a configuration's
shapes alone. ``dims`` comes from the configuration's reference module
(``benchmark/reference/<model_type>.py: dims``): ``total_params`` is what is
held here and read every step, ``matmul_params`` what one token is multiplied
with (a family whose experts are sparse says so through these two);
``kv_layers`` the layers that hold keys and values (default: ``layers``);
``state_bytes_per_row`` the recurrent state a row reads AND writes every step
(default: 0)."""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def param_bytes(dims: dict, dtype: str) -> int:
    return dims["total_params"] * DTYPE_BYTES[dtype]


def kv_bytes_per_token(dims: dict, dtype: str) -> int:
    """Keys and values of one token over every layer that holds any."""
    return (
        dims.get("kv_layers", dims["layers"]) * 2 * dims["kv_heads"]
        * dims["head_dim"] * DTYPE_BYTES[dtype]
    )


def flops_per_token(dims: dict, context: float) -> float:
    """Forward operations one token needs: two per weight of every matrix
    it is multiplied with (the embedding gather is not one; the output head
    is), and the attention scores and weighted sum over ``context`` cached
    positions (2·D per head and position, twice) in every layer that
    attends."""
    attn = (4.0 * context * dims["heads"] * dims["head_dim"]
            * dims.get("kv_layers", dims["layers"]))
    return 2.0 * dims["matmul_params"] + attn


def decode_step_floor_s(
    dims: dict, dtype: str, peaks: dict, *, rows: float, context: float,
    chips: int = 1,
) -> dict:
    """The least time one decode step of ``rows`` sequences with ``context``
    cached tokens each can take on ``chips`` chips: every parameter and every
    cached key and value is read once and every row's recurrent state read
    and written once (bytes over bandwidth), every row does its operations
    (operations over peak). Which of the two bounds it is said. With tensor
    parallelism the parameters and the recurrent state are split over the
    chips; a replicated cache (one KV head) is read by every chip."""
    kv = kv_bytes_per_token(dims, dtype) * rows * context
    if dims["kv_heads"] % chips == 0:
        kv /= chips
    state = 2 * rows * dims.get("state_bytes_per_row", 0) / chips
    bytes_ = param_bytes(dims, dtype) / chips + kv + state
    flops = rows * flops_per_token(dims, context) / chips
    t_mem = bytes_ / peaks["hbm_bytes_per_s"]
    t_op = flops / peaks["bf16_flops_per_s"]
    return {
        "bytes": bytes_, "flops": flops, "floor_s": max(t_mem, t_op),
        "bound_by": "memory" if t_mem >= t_op else "compute",
    }
