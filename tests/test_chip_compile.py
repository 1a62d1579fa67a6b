"""Ask the chip's compiler, without the chip.

The TPU compiler is installed in the sandbox and compiles for a chip that is
described, not attached (``topologies.get_topology_desc``). Interpret mode
cannot show what it shows: a block shape the TPU cannot tile, a kernel that
wants more VMEM than it may use, a bf16 operand reaching an f32 vector op.
The Pallas kernels are compiled here with ``interpret=False`` for v5e
at the widths of three models the repo serves (the latent pool's read and the
state pool's update at their cells' shapes), at the engine's default
``block_size`` and a real cache length. A compile that passes is a compile,
not a chip run.

Two whole step programs are compiled too, the decode group and the ragged
group at the widths and envelopes the benchmark serves (``starcoderbase-1b``,
``falcon-h1-34b-1chip``, ``kanana-2-30b-a3b-1chip``, ``olmo-hybrid-7b-1chip``): what the compiler does
to the block pool the step loop carries shows only in the compiled text (a
transpose of the whole pool every step, once; a copy of a whole layer out of
the stack for every layer of every step, once: docs/paged-kv.md).

Plus: where ``initialize_runtime()`` puts the persistent compile cache.
"""

import dataclasses
import functools
import importlib
import inspect
import json
import math
import os
import re
import types

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from llmss_tpu.engine import DecodeEngine
from llmss_tpu.engine.cache import PagedKVCache, ssm_state_shapes
from llmss_tpu.models.decoder import (
    attn_form, attn_read, index_read, param_shapes, param_specs,
)
from llmss_tpu.models.registry import config_from_hf
from llmss_tpu.ops import (
    pallas_attention, pallas_dsa, pallas_gdn, pallas_kv, pallas_mla,
    pallas_ssm,
)
from llmss_tpu.parallel import mesh as mesh_mod

# (n_heads, n_kv_heads, head_dim)
WIDTHS = {
    "starcoderbase-1b": (16, 1, 128),  # MQA — the chip_smoke model
    "mistral-7b": (32, 8, 128),  # GQA
    "gpt-j-6b": (16, 16, 256),  # MHA, head_dim 256: Hkv*D = 4096
}
B, S, T = 4, 512, 1024
BS = 16  # DecodeEngine's default block_size
DT = jnp.bfloat16  # what the chip serves in


@pytest.fixture(scope="module")
def v5e():
    """One described v5e device; the persistent compile cache is switched
    off around these compiles (an entry written for a described device
    cannot be read back without a chip, and the next run would warn)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernel_call(kernel: str, Hq: int, Hkv: int, D: int):
    """(function, argument shapes) of one kernel at one width set."""
    i32 = jnp.int32
    if kernel == "flash":
        assert pallas_attention.supports(S, T, Hq, Hkv)
        return pallas_attention.flash_attention, [
            ((B, S, Hq, D), DT), ((B, T, Hkv, D), DT), ((B, T, Hkv, D), DT),
            ((B, S), i32), ((B, T), i32),
        ]
    if kernel == "latent_read":
        # the latent pool's own kernel at the shapes of the benchmark's
        # third cell: 64 rows of 320 blocks over [7, 20480, 16, 640], 32
        # heads, a mixed step's 8 tokens a row (``Hkv``) or a decode step's 1
        rows, mb, chunk, v_dim = 64, 320, Hkv, 512
        assert pallas_mla.supports(BS, Hq, D, chunk, DT, v_dim)
        row = ((rows,), i32)
        return functools.partial(
            pallas_mla.latent_paged_attention, ring_len=mb * BS,
            scale=192 ** -0.5, v_dim=v_dim,
        ), [
            ((rows, chunk, Hq, D), DT), ((7, rows * mb, BS, D), DT),
            ((rows, chunk, 1, D), DT), row, row, ((rows, mb * BS), i32),
            ((rows, mb), i32), row, row, ((), i32),
        ]
    if kernel == "kv_read":
        # the read of a pool of keys and values at the shapes of the
        # benchmark's first, second, fourth and fifth cells: 64 rows of
        # ``mb`` blocks, a mixed step's ``chunk`` tokens a row or a decode
        # step's 1 (``D`` carries the three beside the head size)
        rows, (D, layers, mb, chunk) = 64, D
        assert pallas_kv.supports(BS, Hq, Hkv, D, chunk, DT)
        row = ((rows,), i32)
        pool = ((layers, rows * mb, BS, Hkv, D), DT)
        fresh = ((rows, chunk, Hkv, D), DT)
        return functools.partial(
            pallas_kv.kv_paged_attention, ring_len=mb * BS, scale=D ** -0.5,
        ), [
            ((rows, chunk, Hq, D), DT), pool, pool, fresh, fresh, row, row,
            ((rows, mb * BS), i32), ((rows, mb), i32), row, row, ((), i32),
        ]
    if kernel == "selected_read":
        # the read of both pools under a selection at the shapes of the
        # benchmark's sixth cell: 32 rows of 1,056 blocks over [6, 33792, 16,
        # 4, 128], a mixed step's 32 tokens a row or a decode step's 1 (``D``
        # carries them beside the head size), a keep word a slot
        rows, (D, layers, mb, chunk) = 32, D
        assert pallas_dsa.supports(BS, Hq, Hkv, D, chunk, DT)
        row = ((rows,), i32)
        pool = ((layers, rows * mb, BS, Hkv, D), DT)
        fresh = ((rows, chunk, Hkv, D), DT)
        return functools.partial(
            pallas_dsa.dsa_paged_attention, scale=D ** -0.5,
        ), [
            ((rows, chunk, Hq, D), DT), pool, pool, fresh, fresh,
            ((rows, mb * BS), i32), ((rows, chunk), i32), row,
            ((rows, mb), i32), row, ((), i32),
        ]
    if kernel == "index_scores":
        # the indexer's scores over its pool at the shapes of the benchmark's
        # sixth cell: [6, 33792, 16, 128] float32 under the same table, every
        # row's first query (32 rows x 1) or the feeding slots' chunk (7 x 32)
        rows, (heads, Di, W, layers, mb), chunk = Hq, D, Hkv
        assert pallas_dsa.index_supports(BS, heads, W, chunk, mb * BS, jnp.float32)
        row = ((rows,), i32)
        return functools.partial(
            pallas_dsa.idx_paged_scores, n_slots=mb * BS,
        ), [
            ((rows, chunk, heads, Di), jnp.float32),
            ((rows, chunk, heads), jnp.float32),
            ((layers, 32 * mb, BS, W), jnp.float32), row, ((rows, mb), i32),
            row, ((), i32),
        ]
    if kernel == "state_update":
        # the Mamba-2 state pool's update at the shapes of the benchmark's
        # second cell: 64 rows of 32 heads x [128, 256] float32 over 5
        # layers, two groups, a mixed step's 4 positions a row (``Hkv``) or
        # a decode step's 1
        rows, chunk, f32 = 64, Hkv, jnp.float32
        P_, N_, G = 128, D, 2
        assert pallas_ssm.supports(Hq, P_, N_, G, chunk)
        return pallas_ssm.ssm_pool_update, [
            ((5, rows, Hq, P_, N_), f32), ((rows, chunk, Hq, P_), f32),
            ((rows, chunk, Hq), f32), ((Hq,), f32),
            ((rows, chunk, G, N_), f32), ((rows, chunk, G, N_), f32),
            ((rows,), i32), ((), i32),
        ]
    if kernel == "delta_update":
        # the delta rule's state pool's update at the shapes of the
        # benchmark's fourth and fifth cells: 64 rows of 30 heads x [96, 192]
        # over 9 layers and of 32 x [128, 128] over 6, a mixed step's 4 or 8
        # positions a row (``Hkv``) or a decode step's 1
        rows, chunk, f32, (Dk, Dv) = 64, Hkv, jnp.float32, D
        assert pallas_gdn.supports(Hq, Dk, Dv, chunk)
        key = ((rows, chunk, Hq, Dk), f32)
        lens = ((rows,), i32)

        def update(pool, q, k, v, g, beta, lens, layer, interpret):
            return pallas_gdn.gdn_pool_update(
                pool, q, k, v, g, beta, lens, pallas_gdn.live_rows(lens),
                layer, interpret=interpret,
            )

        return update, [
            ((9, rows, Hq, Dk, Dv), f32), key, key,
            ((rows, chunk, Hq, Dv), f32), ((rows, chunk, Hq), f32),
            ((rows, chunk, Hq), f32), lens, ((), i32),
        ]
    raise AssertionError(kernel)


# (heads, tokens a row a step, row width) of the latent pool's read
LATENT_READS = {"mixed-step": (32, 8, 640), "decode-step": (32, 1, 640)}
# (heads, positions a row a step, d_state) of the state pool's update
STATE_UPDATES = {"mixed-step-of-4": (32, 4, 256), "one-step": (32, 1, 256)}
# (value heads, positions a row a step, (Dk, Dv)) of the delta rule's update
DELTA_UPDATES = {
    "olmo-hybrid-step-of-4": (30, 4, (96, 192)),
    "olmo-hybrid-one-step": (30, 1, (96, 192)),
    "qwen3-next-step-of-8": (32, 8, (128, 128)),
    "qwen3-next-one-step": (32, 1, (128, 128)),
}


# (query heads, KV heads, (head size, layers, blocks a row, tokens a row a
# step)) of the read of a pool of keys and values
KV_READS = {
    "kv-starcoderbase-decode": (16, 1, (128, 24, 128, 1)),
    "kv-starcoderbase-step-of-4": (16, 1, (128, 24, 128, 4)),
    "kv-falcon-h1-decode": (20, 4, (128, 5, 64, 1)),
    "kv-falcon-h1-step-of-4": (20, 4, (128, 5, 64, 4)),
    "kv-olmo-hybrid-decode": (32, 32, (128, 3, 64, 1)),
    "kv-olmo-hybrid-step-of-4": (32, 32, (128, 3, 64, 4)),
    "kv-qwen3-next-decode": (16, 2, (256, 2, 320, 1)),
    "kv-qwen3-next-step-of-8": (16, 2, (256, 2, 320, 8)),
} | {
    # the other two width sets above, at 64 rows of 1,024 slots over 2 layers
    f"kv-{model}-{step}": (Hq, Hkv, (D, 2, 64, chunk))
    for model, (Hq, Hkv, D) in WIDTHS.items() if model != "starcoderbase-1b"
    for step, chunk in (("decode", 1), ("step-of-4", 4))
}

# the reads above whose chunks are worked all heads at once (``attn_form``):
# a KV head a query head, whole tiles of them; the others a head at a time
ALL_HEADS = {
    "kv-olmo-hybrid-decode", "kv-olmo-hybrid-step-of-4", "kv-gpt-j-6b-decode",
    "kv-gpt-j-6b-step-of-4",
}

# (query heads, KV heads, (head size, layers, blocks a row, tokens a row a
# step)) of the read of both pools under a selection
SELECTED_READS = {
    "dsa-keye-vl2-decode": (32, 4, (128, 6, 1056, 1)),
    "dsa-keye-vl2-step-of-32": (32, 4, (128, 6, 1056, 32)),
}

# (rows of the call, queries a row, (indexer heads, key width, the pool's row,
# layers, blocks a row)) of the indexer's scores by the walk
INDEX_SCORES = {
    "idx-keye-vl2-first-query": (32, 1, (16, 64, 128, 6, 1056)),
    "idx-keye-vl2-fed-chunk-of-32": (7, 32, (16, 64, 128, 6, 1056)),
}


@pytest.mark.parametrize(
    "kernel,model",
    [("flash", model) for model in WIDTHS]
    + [("latent_read", step) for step in LATENT_READS]
    + [("state_update", step) for step in STATE_UPDATES]
    + [("delta_update", step) for step in DELTA_UPDATES]
    + [("kv_read", step) for step in KV_READS]
    + [("selected_read", step) for step in SELECTED_READS]
    + [("index_scores", step) for step in INDEX_SCORES],
)
def test_kernel_compiles_for_v5e(v5e, kernel, model):
    """Each kernel at its cells' shapes, compiled as the chip compiles it;
    none asks for more VMEM than a v5e kernel may scope by default (no
    ``vmem_limit_bytes`` but ``pallas_gdn``'s), so the compile holds that
    too: the read of keys and values in both its forms."""
    if kernel == "kv_read":
        Hq, Hkv, (*_, chunk) = KV_READS[model]
        assert pallas_kv.attn_form(Hq, Hkv, chunk, DT) == (
            "heads" if model in ALL_HEADS else "head")
        assert "vmem_limit_bytes" not in inspect.getsource(pallas_kv)
    fn, shapes = _kernel_call(
        kernel,
        *(WIDTHS | LATENT_READS | STATE_UPDATES | DELTA_UPDATES | KV_READS
          | SELECTED_READS | INDEX_SCORES)[model],
    )
    on_chip = SingleDeviceSharding(v5e)
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)
        for shape, dtype in shapes
    ]
    compiled = jax.jit(
        functools.partial(fn, interpret=False)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# bigcode/starcoderbase-1b's config.json, and the envelope the benchmark's
# first cell serves it in (benchmark/configs/starcoderbase-1b.json).
STARCODERBASE_1B = dict(
    model_type="gpt_bigcode", vocab_size=49152, n_positions=8192,
    n_embd=2048, n_layer=24, n_head=16, n_inner=8192, multi_query=True,
    activation_function="gelu_pytorch_tanh", layer_norm_epsilon=1e-5,
)
ROWS, POSITIONS = 64, 2048


def _bench_config(name: str):
    """``(hf, cfg)`` of ``benchmark/configs/<name>.json``."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "configs", name + ".json",
    )
    with open(path) as f:
        hf = json.load(f)
    return hf, config_from_hf(types.SimpleNamespace(**hf), dtype=hf["dtype"])


def _widths_config(Hq: int, Hkv: int, D: int = 128):
    """``starcoderbase-1b``'s configuration with other attention widths."""
    return dataclasses.replace(
        config_from_hf(
            types.SimpleNamespace(**STARCODERBASE_1B), dtype="bfloat16"
        ),
        n_heads=Hq, n_kv_heads=Hkv, head_dim=D,
    )


# tokens a row a mixed step, where a configuration's cell sets other than 4
# (``serve.chunked_prefill`` of its file under benchmark/cells/)
CELL_CHUNK = {
    "kanana-2-30b-a3b-1chip": 8, "qwen3-next-80b-a3b-1chip": 8,
    "keye-vl-2.0-30b-a3b-1chip": 32,
}


def _compile_group(device, program: str, widths, **kw):
    """``_compile_step`` at ``starcoderbase-1b``'s widths and envelope with
    ``widths`` KV heads, or, for the name of one of the benchmark's
    configurations (``falcon-h1-34b-1chip``: GQA with 4 KV heads beside a
    recurrent state, whose leaves ride in the cache), at that file's widths
    in the envelope its cell serves, a mixed step at its cell's chunk."""
    if isinstance(widths, int):
        return _compile_step(
            device, program, _widths_config(16, widths), POSITIONS, **kw
        )
    hf, cfg = _bench_config(widths)
    kw.setdefault("chunk", CELL_CHUNK.get(widths, 4))
    return _compile_step(
        device, program, cfg, hf["serve"]["max_seq_len"],
        ROWS=hf["serve"]["rows"], **kw
    )


def _cache_shapes(cfg, arr, POSITIONS: int, ROWS: int):
    """``(cache, pool shape)``: a paged cache of ``arr(shape, dtype)``s in
    the envelope ``ROWS`` x ``POSITIONS``, every pool the configuration has."""
    mb = POSITIONS // BS
    pool = (cfg.n_kv_layers, ROWS * mb, BS) + cfg.cache_row
    return PagedKVCache(
        k=arr(pool, DT), v=None if cfg.mla is not None else arr(pool, DT),
        block_tables=arr((ROWS, mb), jnp.int32),
        positions=arr((ROWS, POSITIONS), jnp.int32),
        **dict(zip(("ssm", "conv"), (
            arr((cfg.n_state_layers, ROWS) + shape, dtype)
            for shape, dtype in ssm_state_shapes(cfg) or ()
        ))),
        idx=None if cfg.indexer is None else arr(
            pool[:3] + (cfg.indexer.pool_dim,), jnp.float32),
    ), pool


# every step program this file compiled, by what ``_compile_step`` was called
# with: each is compiled once and every test reads its text
_STEP_PROGRAMS: dict = {}


def _compile_step(device, program: str, cfg, POSITIONS: int, ROWS: int = ROWS,
                  chunk: int = 4, t_bucket: int | None = 512,
                  as_tpu: bool = False):
    """``(compiled, pool shape)`` of one step program of the engine on
    shapes alone: 4 decode steps at a ``t_bucket``-slot read (None: the whole
    ring), or 4 mixed steps with a ``chunk``-token chunk a row; ``as_tpu``:
    traced as a TPU traces it (the program asks ``jax.default_backend()``,
    which is the CPU here, so ``pallas_interpret`` answers for it)."""
    key = (cfg, POSITIONS, ROWS, as_tpu) + (
        ("decode", t_bucket) if program == "decode" else ("ragged", chunk)
    )
    if key not in _STEP_PROGRAMS:
        with pytest.MonkeyPatch.context() as mp:
            if as_tpu:
                mp.setattr(
                    importlib.import_module("llmss_tpu.ops.attention"),
                    "pallas_interpret", lambda: False,
                )
            _STEP_PROGRAMS[key] = _lower_step(
                device, program, cfg, POSITIONS, ROWS, chunk, t_bucket
            )
    return _STEP_PROGRAMS[key]


def _lower_step(device, program, cfg, POSITIONS, ROWS, chunk, t_bucket):
    mesh = mesh_mod.make_mesh(mesh_mod.MeshPlan(tp=1), devices=[device])

    def arr(shape, dtype, spec=PartitionSpec()):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec)
        )

    params = jax.tree.map(
        lambda s, spec: arr(s.shape, s.dtype, spec),
        param_shapes(cfg), param_specs(cfg, 1),
    )
    cache, pool = _cache_shapes(cfg, arr, POSITIONS, ROWS)
    row = functools.partial(arr, (ROWS,))
    sample_args = dict(
        seeds=row(jnp.int32), temperature=row(jnp.float32),
        top_k=row(jnp.int32), top_p=row(jnp.float32), greedy=row(jnp.bool_),
    )
    state = (
        params, row(jnp.int32), cache, row(jnp.int32), sample_args,
        row(jnp.bool_), row(jnp.int32),
    )
    if program == "decode":
        lowered = jax.jit(
            functools.partial(DecodeEngine._decode_group_impl, cfg, mesh),
            donate_argnums=(1, 2, 3),
            static_argnames=("n_chunks", "n_steps", "t_bucket"),
        ).lower(*state, n_chunks=1, n_steps=4, t_bucket=t_bucket)
    else:
        steps = functools.partial(arr, (4, ROWS))
        lowered = jax.jit(
            functools.partial(DecodeEngine._ragged_group_impl, cfg, mesh),
            donate_argnums=(1, 2, 3),
        ).lower(
            *state, arr((4, ROWS, chunk), jnp.int32), steps(jnp.int32),
            steps(jnp.bool_), steps(jnp.bool_),
        )
    return lowered.compile(), pool


def _results(hlo_text: str, name: str):
    """``(line, result dimensions)`` of the instructions, fused ones too,
    whose name matches ``name``."""
    for line in hlo_text.splitlines():
        m = re.match(rf"\s*(?:ROOT )?%(?:{name})\S* = \w+\[([\d,]+)\]", line)
        if m:
            yield line.strip()[:120], tuple(map(int, m[1].split(",")))


def _pool_sized_copies(hlo_text: str, pool: tuple, ops: str = "copy") -> list[str]:
    """The ``copy`` instructions (or those named ``ops``, a regex) whose
    result has as many elements as the pool, whatever dimensions a bitcast
    gave it."""
    return [
        line for line, dims in _results(hlo_text, ops)
        if math.prod(dims) == math.prod(pool)
    ]


def _layer_sized_slices(hlo_text: str, pool: tuple) -> list[str]:
    """The ``dynamic-slice`` and ``copy`` instructions (and the fusions named
    after them) whose result is ONE LAYER of the pool: its dimensions, size-1
    axes apart. By dimensions and not by count: a layer of the MQA pool has as
    many elements as an MLP matrix, and a mixed step's gathered view
    ``[rows, slots, ...]`` as many as the layer it was gathered from."""
    layer = tuple(d for d in pool[1:] if d != 1)
    return [
        line
        for line, dims in _results(
            hlo_text, r"(?:\w+_)?dynamic[-_]slice|copy"
        )
        if tuple(d for d in dims if d != 1) == layer
    ]


@pytest.mark.parametrize(
    "program,widths", [
        ("decode", 1), ("ragged", 1), ("decode", 4),
        ("decode", "falcon-h1-34b-1chip"), ("ragged", "falcon-h1-34b-1chip"),
    ],
)
def test_step_program_carries_the_pool_in_place(v5e, program, widths):
    """The step loop's carry keeps the layout the layer scan reads, and the
    layer scan reads the pool by layer AND block in one gather: no program
    copies the whole pool (with one KV head it once did, six times a group),
    none slices or copies a whole layer out of it ahead of the gather (both
    did, for every layer of every step; with 4 KV heads re-tiled besides),
    and a decode group's temporaries are a small part of one pool."""
    compiled, pool = _compile_group(v5e, program, widths)
    text = compiled.as_text()
    assert _pool_sized_copies(text, pool) == []
    assert _layer_sized_slices(text, pool) == []
    if isinstance(widths, int):  # the other's temporaries are the state's
        pool_bytes = math.prod(pool) * jnp.dtype(DT).itemsize
        assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes / 10


@pytest.mark.parametrize(
    "program,widths,chunk",
    [
        (program, widths, chunk)
        for widths, chunk in (
            (1, 4), ("falcon-h1-34b-1chip", 4), ("olmo-hybrid-7b-1chip", 4),
            ("qwen3-next-80b-a3b-1chip", 8),
        )
        for program in ("decode", "ragged")
    ],
)
def test_step_program_reads_the_pool_where_it_lies(
    v5e, program, widths, chunk,
):
    """With ``kv.kernel`` (as on a TPU: ``ops/pallas_kv.py`` compiled) the
    decode group and the mixed group of the four cells that hold keys and
    values take both pools AS STORED: beside the kernel's custom call and
    the write there is no ``copy``, ``transpose``, ``bitcast-convert``,
    ``dynamic-slice`` or ``gather`` of a pool's size, no slice of a layer,
    no row's gathered ring ``[rows, slots, heads, head size]``, and the pool
    keeps ONE device layout (one KV head: the slots second-minor, dense
    tiles, read through a free reshape; two, four: ``T(2,128)`` /
    ``T(4,128)``, a slot's heads packed in a sublane pair; thirty-two:
    ``T(8,128)``)."""
    compiled, pool = _compile_group(
        v5e, program, widths, chunk=chunk, as_tpu=True
    )
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "kv_paged_attention" in text
    moved = (
        r"(?:\w+_)?(?:copy|transpose|dynamic[-_]slice|gather|"
        r"bitcast[-_]convert)"
    )
    assert _pool_sized_copies(text, pool, moved) == []
    assert _layer_sized_slices(text, pool) == []
    slots = pool[1] // ROWS * BS
    for t in {slots, min(slots, 512)}:  # the ring, the decode read bucket
        ring = ",".join(map(str, (ROWS, t) + pool[3:]))
        assert f"[{ring}]" not in text, ring
    dims = ",".join(map(str, pool))
    layouts = set(re.findall(rf"bf16\[{dims}\]\{{([^}}]*T[^}}]*)\}}", text))
    if pool[3] > 1:
        tile = min(pool[3], 8)
        assert layouts == {f"4,3,2,1,0:T({tile},128)(2,1)"}


@pytest.mark.parametrize("program", ["decode", "ragged"])
def test_two_kinds_of_layer_carry_every_pool_in_place(v5e, program):
    """``olmo-hybrid-7b-1chip`` at the published widths, 64 rows x 1,024
    positions: the block pool of the 3 attention layers (32 heads for the
    model's 30: at 30 the program copied it into another layout and back,
    four copies of 1.5 GB a group), the delta rule's state pool of the 9
    linear-attention layers (``[.., 30, 96, 192]`` as the update computes on
    it, 192 padded to two lane tiles on the device: flattened to whole tiles
    it is re-tiled a layer at a time on the way in and out, three more
    passes a layer a step) and their window pool (flattened: as ``[rows, 3,
    C]`` it was copied into a rows-second-minor layout and back) each go
    through the decode and the mixed group as they came: no pool-sized
    ``copy``, ``transpose`` or ``dynamic-slice`` of any of the three, no
    re-tiling ``copy`` of a layer's state, no layer-sized slice of the block
    pool, and the arguments and temporaries fit the chip."""
    compiled, pool = _compile_group(v5e, program, "olmo-hybrid-7b-1chip")
    assert pool == (3, 64 * 64, 16, 32, 128)
    text = compiled.as_text()
    moved = r"(?:\w+_)?(?:copy|transpose|dynamic[-_]slice)"
    for shape in (pool, (9, 64, 30, 96, 192), (9, 64, 3 * 11520)):
        assert _pool_sized_copies(text, shape, moved) == [], shape
    assert _pool_sized_copies(text, (64, 30, 96, 192)) == []
    assert _layer_sized_slices(text, pool) == []
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes == pytest.approx(11.50e9, rel=0.01)
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 14.5e9


def test_a_share_of_the_experts_under_two_kinds_of_layer_fits(v5e):
    """``qwen3-next-80b-a3b-1chip`` at the published widths, 64 rows x 5,120
    positions, the mixed group (the one step program its cell times): the
    128 held experts of all 8 layers are ONE stack read in place by the
    grouped matmul's own kernel (three custom calls a layer of the period's
    four), the block pool of the 2 attention layers keeps its 2 KV heads
    unpadded (``T(2,128)``: 1.34 GB), the state pool of 32 VALUE heads and
    the window pool go through as they came, and the arguments are what the
    configuration's ``memory`` says: 9.50 GB, with under 0.2 GB of
    temporaries (the gathered rings of the XLA read, most of 1.1 GB until
    the block pool was read where it lies, are gone)."""
    compiled, pool = _compile_group(
        v5e, "ragged", "qwen3-next-80b-a3b-1chip", as_tpu=True
    )
    assert pool == (2, 64 * 320, 16, 2, 256)
    text = compiled.as_text()
    moved = r"(?:\w+_)?(?:copy|transpose|dynamic[-_]slice)"
    for shape in (pool, (6, 64, 32, 128, 128), (6, 64, 3 * 8192),
                  (8, 128, 2048, 512)):
        assert _pool_sized_copies(text, shape, moved) == [], shape
    assert _layer_sized_slices(text, pool) == []
    # three a layer of the period's four, the three linear layers' state,
    # and the attention layer's read of the block pool (ops/pallas_kv.py)
    assert text.count("tpu_custom_call") == 12 + 3 + 1
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes == pytest.approx(9.50e9, rel=0.01)
    assert ma.temp_size_in_bytes < 0.2e9


@pytest.mark.parametrize("program", ["decode", "ragged"])
def test_a_selection_inside_paged_attention_fits_beside_three_pools(
    v5e, program,
):
    """``keye-vl-2.0-30b-a3b-1chip`` at the published widths, 32 rows x
    16,896 positions, the cell's mixed group (chunks of its
    ``chunked_prefill``) and the decode group over the whole ring: the three
    pools, keys and values ``bf16[6, 33792, 16, 4, 128]`` with their 4 KV
    heads unpadded (``T(4,128)``) and the indexer's keys ``f32[6, 33792, 16,
    128]`` row-major (64 numbers of key padded to a lane tile: as 64 wide the
    default device layout put the block axis minor and transposed the pool
    whole around every program), go through as they came, in ONE layout
    each, with no copy or transpose of a pool and no slice of a layer; the
    32 held experts of all 6 layers are one stack read in place by the
    grouped matmul's kernel; arguments 10.69 GB. Since PR 48 the keys and
    values are read where they lie by ``dsa.kernel`` (ops/pallas_dsa.py: one
    more custom call in the scan's one body), so beside it stands no gather
    of the kept tokens (``[rows * topk, 4, 128]``), no row's gathered ring,
    no float32 attention score over the ring (feeding rows x query heads x
    chunk of them) and no ``sort`` of a ring. Since PR 52 the indexer's
    scores come from the same walk over ITS pool (``idx.kernel``,
    ``%idx_paged_scores``: one call for every row's first query, in the
    mixed group one more for the feeding slots' chunk), so no view of the
    indexer pool is gathered (all rows' 277 MB, the feeding slots' 61 MB) and
    no score a head stands in HBM (``f32[7,32,16,16896]``, 242 MB): the
    temporaries are 0.04 / 0.06 GB where they were 0.32 / 0.35 (and the mask
    form's scores made the mixed group's 0.80). Since PR 53 the selection
    holds no running count over a ring (``s32[7,32,133,128]``: it was the
    cell's largest device op)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark", "cells",
            "keye-vl-2.0-30b-a3b-1chip.longdoc.json")) as f:
        chunk = json.load(f)["serve"]["chunked_prefill"]
    assert chunk == CELL_CHUNK["keye-vl-2.0-30b-a3b-1chip"]
    hf, cfg = _bench_config("keye-vl-2.0-30b-a3b-1chip")
    compiled, pool = _compile_group(
        v5e, program, "keye-vl-2.0-30b-a3b-1chip", t_bucket=None, as_tpu=True,
    )
    assert pool == (6, 32 * 1056, 16, 4, 128)
    text = compiled.as_text()
    index_pool = pool[:3] + (128,)
    moved = r"(?:\w+_)?(?:copy|transpose|dynamic[-_]slice)"
    for shape in (pool, index_pool, (6, 32, 2048, 768)):
        assert _pool_sized_copies(text, shape, moved) == [], shape
    assert _layer_sized_slices(text, pool) == []
    assert _layer_sized_slices(text, index_pool) == []
    dims = ",".join(map(str, pool))
    # (the custom call's operand constraints name the pools with no tiling)
    assert set(re.findall(rf"bf16\[{dims}\]\{{([^}}]*T[^}}]*)\}}", text)) == {
        "4,3,2,1,0:T(4,128)(2,1)"}
    dims = ",".join(map(str, index_pool))
    assert set(re.findall(rf"f32\[{dims}\]\{{([^}}]*T[^}}]*)\}}", text)) == {
        "3,2,1,0:T(8,128)"}
    rows, ring, topk = hf["serve"]["rows"], hf["serve"]["max_seq_len"], 2048
    heads, fed, mb = cfg.n_heads, 7, ring // 16  # ``feed_rows`` here: 7
    # a layer of the scan's one body: the experts' three, the read and the
    # indexer's scores (every row's first query; the feeding slots' chunk)
    calls = {name: len(re.findall(rf"%{name}\S* = ", text))
             for name in ("dsa_paged_attention", "idx_paged_scores")}
    walks = 1 if program == "decode" else 2
    assert calls == {"dsa_paged_attention": 1, "idx_paged_scores": walks}
    assert text.count("tpu_custom_call") == 4 + walks
    # no view of the indexer pool: all rows' (its gather's result as the
    # profile names it, and as a view), the feeding slots'
    for view in ((rows * mb, 16, 128), (rows, ring, 128), (fed, ring, 128),
                 (fed * mb, 16, 128), (fed, chunk, 16, ring)):
        assert f"f32[{','.join(map(str, view))}]" not in text, view
    for gathered in ((rows * topk,), (rows, topk), (rows, ring), (fed, ring)):
        dims = ",".join(map(str, gathered + pool[3:]))
        assert f"[{dims}]" not in text, dims
    scores = [
        line for line, dims in _results(text, r"\S")
        if "f32[" in line and dims[-1] in (ring, ring + chunk)
        and math.prod(dims[:-1]) >= fed * heads * chunk
    ]
    assert scores == []
    # the ``top_k`` that compacted a row's kept slots (the experts sort pairs)
    assert [d for _, d in _results(text, "sort") if ring in d] == []
    # no running count over a ring (PR 53: ``keep_topk`` cuts its tie group
    # at an index found by bisection): what is left of ``reduce-window`` is
    # the walks' and the experts' offsets, a few hundred numbers
    lanes = -(-(ring + 1) // 128)
    for counted in ((fed, rows, lanes, 128), (rows, lanes, 128)):
        assert f"s32[{','.join(map(str, counted))}]" not in text, counted
    windows = re.findall(r"= \w+\[([\d,]+)\]\S* reduce-window\(", text)
    assert windows and all(
        math.prod(map(int, d.split(","))) <= 1024 for d in windows), windows
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes == pytest.approx(10.69e9, rel=0.01)
    assert ma.temp_size_in_bytes < (0.45e9 if program == "ragged" else 0.4e9)


# kakaocorp/kanana-2-30b-a3b-instruct-2601's widths (deepseek_v3: a latent
# pool, 128 routed experts), cut to one dense and two expert layers, in the
# envelope of benchmark/configs/kanana-2-30b-a3b-1chip.json.
KANANA_2_30B_A3B = dict(
    model_type="deepseek_v3", vocab_size=128256, hidden_size=2048,
    num_attention_heads=32, num_key_value_heads=32, intermediate_size=6144,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, q_lora_rank=None, moe_intermediate_size=768,
    n_routed_experts=128, num_experts_per_tok=6, n_shared_experts=2,
    first_k_dense_replace=1, moe_layer_freq=1, n_group=1, topk_group=1,
    norm_topk_prob=True, routed_scaling_factor=2.448, scoring_func="sigmoid",
    topk_method="noaux_tc", num_hidden_layers=3, max_position_embeddings=5120,
    rms_norm_eps=1e-6, rope_theta=1000000, rope_interleave=True,
    rope_scaling=None, hidden_act="silu", tie_word_embeddings=False,
)


@pytest.mark.parametrize("program", ["decode", "ragged"])
def test_latent_step_program_carries_the_pool_in_place(v5e, program):
    """The latent pool (``[L, N, bs, 640]``: 576 padded to whole lane tiles,
    no head axis) goes through the step loops as it came: no pool-sized
    ``copy``. At 576 wide its default device layout had the block axis
    minor and every program transposed it four times (docs/latent-cache.md).
    The grouped matmul over the experts compiles as the chip's own kernel,
    and so does the read of the pool (ops/pallas_mla.py): no gather of the
    rows' rings (all 64 x 320 blocks in the mixed step, 64 x 32 at the
    decode step's 512-slot read), no float32 scores over them in HBM."""
    cfg = config_from_hf(
        types.SimpleNamespace(**KANANA_2_30B_A3B), dtype="bfloat16"
    )
    compiled, pool = _compile_step(v5e, program, cfg, 5120, as_tpu=True)
    assert pool == (3, 64 * 320, 16, 640)
    text = compiled.as_text()
    assert _pool_sized_copies(text, pool) == []
    assert text.count("tpu_custom_call") >= 4  # gate, up, down, the read
    views = [
        line for line in text.splitlines()
        if re.search(r"bf16\[(20480|2048),16,640\]", line)
        or re.search(r"f32\[64,[\d,]*5120\]|f32\[64,1,32,\d,512\]", line)
    ]
    assert views == []
    # the temporaries were the rows' gathered views and the scores (0.63 GB
    # in the mixed step); now 0.035 GB beside this three-layer pool's 1.26
    pool_bytes = math.prod(pool) * jnp.dtype(DT).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < 0.05 * pool_bytes


@pytest.mark.parametrize("program", ["decode", "ragged"])
def test_state_pool_is_updated_where_it_lies(v5e, program):
    """``falcon-h1-34b-1chip``'s decode and mixed groups as a TPU traces
    them (``state_update`` says ``ssm.kernel``): ONE custom call in the layer
    scan's body takes the state pool ``f32[5,64,32,128,256]`` straight from
    the carry and its result is the pool (the aliasing held: no ``copy`` of
    the pool), and nothing else in the program produces the pool's shape, a
    layer's (the slice the XLA path copies out, 0.27 GB), or the grouped
    form the oracle computes on."""
    compiled, _ = _compile_group(
        v5e, program, "falcon-h1-34b-1chip", as_tpu=True
    )
    text = compiled.as_text()
    state = r"f32\[(?:5,64,32|1,64,32|64,32|64,2,16),128,256\]"
    made = [
        line.strip()[:100] for line in text.splitlines()
        if re.match(rf"\s*(?:ROOT )?%\S+ = \(?{state}", line)
        and not re.search(r" (?:parameter|get-tuple-element|bitcast)\(", line)
    ]
    assert len(made) == 1 and made[0].startswith("%ssm_pool_update"), made
    # the state's update, and the read of the block pool (ops/pallas_kv.py)
    assert text.count("tpu_custom_call") == 1 + 1
    assert _pool_sized_copies(text, (5, 64, 32, 128, 256)) == []
    # the slice's temporary is gone: the mixed group holds 0.11 GB
    if program == "ragged":
        assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9


@pytest.mark.parametrize("program", ["decode", "ragged"])
@pytest.mark.parametrize(
    "config,pool,calls",
    [
        # beside the attention layer's read of the block pool
        ("olmo-hybrid-7b-1chip", (9, 64, 30, 96, 192), 3 + 1),
        # and the grouped matmul's three calls a layer of the period's four
        ("qwen3-next-80b-a3b-1chip", (6, 64, 32, 128, 128), 3 + 1 + 12),
    ],
    ids=["olmo-hybrid", "qwen3-next"],
)
def test_delta_rule_state_is_updated_where_it_lies(
    v5e, program, config, pool, calls,
):
    """Cells 4 and 5's decode and mixed groups as a TPU traces them
    (``state_update`` says ``gdn.kernel``): the period's three linear layers
    are three custom calls in the scan's body, each taking the state pool
    straight from the carry with the pool as its result (the aliasing held:
    no ``copy`` of the pool), and nothing else in the program produces the
    pool's shape or a layer's (the slice the XLA path copies out, 0.19 and
    0.13 GB, its update back, the passes between)."""
    compiled, _ = _compile_group(v5e, program, config, as_tpu=True)
    text = compiled.as_text()
    layer = ",".join(map(str, pool[1:]))
    state = rf"f32\[(?:{pool[0]},|1,)?{layer}\]"
    made = [
        line.strip()[:100] for line in text.splitlines()
        if re.match(rf"\s*(?:ROOT )?%\S+ = \(?{state}", line)
        and not re.search(
            r" (?:parameter|get-tuple-element|bitcast|while|tuple)\(", line)
    ]
    assert len(made) == 3, made
    assert all(line.startswith("%gdn_pool_update") for line in made), made
    assert text.count("tpu_custom_call") == calls
    moved = r"(?:\w+_)?(?:copy|transpose|dynamic[-_]slice)"
    assert _pool_sized_copies(text, pool, moved) == []
    assert _pool_sized_copies(text, pool[1:], moved) == []
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 14.5e9


def test_supports_refuses_what_vmem_cannot_hold():
    """``supports()`` and the compiler agree: shapes whose working set
    cannot fit a kernel's VMEM budget, or that it cannot tile, are refused
    up front."""
    # the read of keys and values: 64 heads of 256 overflow the budget at
    # the narrowest chunk; a head of 64 is not whole lanes; a 16-bit pool's
    # heads come out of their words in pairs; 16 fresh tokens a row at most
    assert pallas_kv.chunk_slots(BS, 16, 16, 256, 4, DT) == 128
    assert pallas_kv.supports(BS, 16, 16, 256, 4, jnp.float32)
    assert not pallas_kv.supports(BS, 64, 64, 256, 1, DT)
    assert not pallas_kv.supports(BS, 14, 2, 64, 1, DT)
    assert not pallas_kv.supports(BS, 9, 3, 128, 1, DT)
    assert not pallas_kv.supports(BS, 16, 1, 128, 32, DT)
    assert not pallas_kv.supports(BS, 16, 1, 128, 1, jnp.int8)
    # the selected read: the same walk with a query a bit of a 32-bit word
    assert pallas_dsa.supports(BS, 32, 4, 128, 32, DT)
    assert not pallas_dsa.supports(BS, 32, 4, 128, 33, DT)
    assert not pallas_dsa.supports(BS, 64, 64, 256, 1, DT)
    # the prefill's flash kernel: a chunk of 16 queries or more over
    # lane-friendly lengths (a decode step stays with XLA)
    assert pallas_attention.supports(S, T, 32, 8)
    assert not pallas_attention.supports(1, T, 32, 8)
    assert not pallas_attention.supports(S, T + 1, 32, 8)
    # the latent read: 256 query rows of 640 fit, 2048 do not
    assert pallas_mla.supports(BS, 32, 640, 8, DT, 512)
    assert not pallas_mla.supports(BS, 128, 640, 16, DT, 512)
    assert not pallas_mla.supports(BS, 32, 576, 8, DT)  # not whole lanes
    # the state's update: 8 heads of [128, 256] four times over fit, of
    # [128, 2048] do not
    assert pallas_ssm.supports(32, 128, 256, 2, 4)
    assert not pallas_ssm.supports(32, 128, 2048, 2, 4)
    # the delta rule's: a row's 32 heads of [128, 128] fit whole, 8 heads of
    # [128, 16384] do not
    assert pallas_gdn.supports(32, 128, 128, 8)
    assert not pallas_gdn.supports(32, 128, 16384, 8)


# What reads a paged pool in a decode step and in a mixed step of ``chunk``
# tokens a row, as a TPU traces them, for every file under benchmark/configs/
# (on one device and, where the file's mesh is wider, on that mesh) and this
# file's three width sets: PERF.md section 3's account, and the document of
# what still reads through the gather.
POOL_READS = {
    # name: (chunk, decode step, mixed step[, both on the file's own mesh])
    "starcoderbase-1b": (4, "kv.kernel", "kv.kernel"),
    # ``starcoderbase-1b.complete-sat``'s chunk: over the kernel's 16 tokens
    "starcoderbase-1b@64": (64, "kv.kernel", "gather"),
    "falcon-h1-34b-1chip": (4, "kv.kernel", "kv.kernel"),
    "kanana-2-30b-a3b-1chip": (8, "mla.kernel", "mla.kernel"),
    "olmo-hybrid-7b-1chip": (4, "kv.kernel", "kv.kernel"),
    "qwen3-next-80b-a3b-1chip": (8, "kv.kernel", "kv.kernel"),
    "keye-vl-2.0-30b-a3b-1chip": (32, "dsa.kernel", "dsa.kernel"),
    "gpt-j-6b-l16": (4, "kv.kernel", "kv.kernel"),
    "starcoder-15b-tp4": (4, "kv.kernel", "kv.kernel", "gather"),
    "widths:starcoderbase-1b": (4, "kv.kernel", "kv.kernel"),
    "widths:mistral-7b": (4, "kv.kernel", "kv.kernel"),
    "widths:gpt-j-6b": (4, "kv.kernel", "kv.kernel"),
}
READS = {"gather", "kv.kernel", "mla.kernel", "dsa.kernel", "dsa.tokens",
         "dsa.mask"}
# ``attn_form`` beside it: the configurations whose ``kv.kernel`` works all
# heads of a chunk in one product (a KV head a query head, whole tiles of
# them); ``head`` under every other ``kv.kernel``, ``none`` under another read
ATTN_FORMS = {
    "olmo-hybrid-7b-1chip": "heads", "gpt-j-6b-l16": "heads",
    "widths:gpt-j-6b": "heads",
}
# ``index_read`` beside it, as a TPU traces either step: how the indexer's
# pool is scored; ``none`` for every configuration without an indexer, and
# ``gather`` on the CPU for the one that has it
INDEX_READS = {"keye-vl-2.0-30b-a3b-1chip": "idx.kernel"}


def test_every_configuration_file_has_its_read_in_the_table():
    configs = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "configs",
    )
    files = {f[:-len(".json")] for f in os.listdir(configs)}
    assert files == {n.split("@")[0] for n in POOL_READS if ":" not in n}
    assert {n[len("widths:"):] for n in POOL_READS if ":" in n} == set(WIDTHS)
    assert {w for row in POOL_READS.values() for w in row[1:]} <= READS
    assert set(ATTN_FORMS) <= set(POOL_READS)


@pytest.mark.parametrize("name", POOL_READS)
def test_attn_read_names_the_read_of_every_configuration(v5e, name):
    """``attn_read`` on one device, as a TPU, for a decode step and for the
    cell's chunk, says the read the table names and never a word outside the
    six; on a wider mesh of the file's own, the gather. On the CPU, with
    nothing pinned, no kernel is chosen."""
    from jax.experimental import topologies

    chunk, decode, mixed, *on_mesh = POOL_READS[name]
    if name.startswith("widths:"):
        hf = {"serve": {"rows": ROWS, "max_seq_len": POSITIONS}}
        cfg = _widths_config(*WIDTHS[name[len("widths:"):]])
    else:
        hf, cfg = _bench_config(name.split("@")[0])
    cache, _ = _cache_shapes(
        cfg, jax.ShapeDtypeStruct, hf["serve"]["max_seq_len"],
        hf["serve"]["rows"],
    )
    mesh = mesh_mod.make_mesh(mesh_mod.MeshPlan(tp=1), devices=[v5e])
    on_cpu = {attn_read(cfg, cache, mesh, c) for c in (1, chunk)}
    assert on_cpu <= {"gather", "dsa.tokens", "dsa.mask"}, on_cpu
    assert {attn_form(cfg, cache, mesh, c) for c in (1, chunk)} == {"none"}
    scored = INDEX_READS.get(name, "none")
    assert {index_read(cfg, cache, mesh, c) for c in (1, chunk)} == {
        "none" if cfg.indexer is None else "gather"}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(
            importlib.import_module("llmss_tpu.ops.attention"),
            "pallas_interpret", lambda: False,
        )
        assert attn_read(cfg, cache, mesh, 1) == decode
        assert attn_read(cfg, cache, mesh, chunk) == mixed
        for c, read in ((1, decode), (chunk, mixed)):
            assert attn_form(cfg, cache, mesh, c) == (
                ATTN_FORMS.get(name, "head") if read == "kv.kernel" else "none")
        assert attn_read(cfg, cache, None, chunk) == mixed
        assert {index_read(cfg, cache, mesh, c) for c in (1, chunk)} == {scored}
        if on_mesh:
            tp = hf["mesh"]["tp"]
            wide = mesh_mod.make_mesh(
                mesh_mod.MeshPlan(tp=tp),
                devices=topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2").devices[:tp],
            )
            assert {attn_read(cfg, cache, wide, c) for c in (1, chunk)} == {
                on_mesh[0]}


@pytest.fixture
def fresh_runtime(monkeypatch):
    """``initialize_runtime()`` as a new process would run it, with the
    config it touches restored afterwards."""
    was = (
        jax.config.jax_compilation_cache_dir,
        jax.config.jax_persistent_cache_min_compile_time_secs,
    )
    monkeypatch.setattr(mesh_mod, "_initialized", False)
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    yield
    jax.config.update("jax_compilation_cache_dir", was[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", was[1])


@pytest.mark.parametrize("placed_from_outside", [True, False])
def test_compile_cache_placement(
    fresh_runtime, monkeypatch, tmp_path, placed_from_outside,
):
    """With JAX_COMPILATION_CACHE_DIR set the program sets no directory in
    code; unset, the cache lives at one fixed path inside the checkout."""
    sentinel = str(tmp_path / "untouched")
    jax.config.update("jax_compilation_cache_dir", sentinel)
    if placed_from_outside:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "x"))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    mesh_mod.initialize_runtime()
    got = jax.config.jax_compilation_cache_dir
    if placed_from_outside:
        assert got == sentinel
    else:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == os.path.join(repo, ".jax_compile_cache")
        assert os.path.isdir(got)
