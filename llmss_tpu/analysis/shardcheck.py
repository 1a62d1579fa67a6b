"""shardcheck: jaxpr/HLO-level SPMD auditor for the production programs.

graftlint (``jax_rules.py``) works on the AST and CompileGuard/devtel
watch the runtime; this module audits the *lowered programs themselves*.
It builds a tiny-but-real engine (2 layers, random params) over a
configurable mesh, traces every production jitted program — the prefill
buckets, single and grouped decode, the ragged mixed group, the
speculative group, and the paged absorb/merge scatters — and checks the
jaxpr + optimized HLO of each:

``partial-sum-leak``
    A scan's stacked ys reach a host-fetched program output without a
    replicated ``sharding_constraint``. This is the PR 6 bug class: GSPMD
    propagates an unreduced partial-sum layout from tp-sharded logits
    into the stacked output and the host reads values summed over the tp
    axis. The pin (``parallel/sharding.ys_pin``) is the documented
    discipline; this rule makes it machine-checked instead of a comment.
    Checked only when the audit mesh has tp > 1 (the hazard needs a tp
    axis to sum over).

``donation-unmatched``
    A donated input buffer has no output with the same shape/dtype, so
    XLA cannot alias it: the donation silently buys nothing and the
    caller still loses the buffer. Platform-independent (checked on
    avals, before the backend gets a say).

``donation-dropped``
    The compiled executable aliases fewer input/output pairs than the
    donation declares (``input_output_alias`` parsed from optimized HLO),
    or XLA emitted a "donated buffers were not usable" warning during
    compile. Skipped when the backend does not implement donation at all
    (probed once — the structural check above still runs there).

``host-fetch-not-replicated``
    An output the host fetches (token streams, packed group results)
    compiles to a non-replicated sharding: ``device_get`` would then
    gather shards on every fetch, putting a collective on the host
    critical path.

``comms-manifest-drift``
    The per-program collective inventory (all-reduce / all-gather /
    reduce-scatter / collective-permute / all-to-all counts and byte
    volumes from HLO) differs from the committed golden
    ``tools/comms_manifest.json``. An accidental extra all-gather in a
    hot loop fails CI the way a perf regression fails bench-trend.
    Regenerate deliberately with ``--update-manifest``.

Reuses graftlint's findings/suppression/baseline engine: findings are
anchored at each program's registration line in THIS file, so
``# lint: ignore[rule]`` comments above a registration suppress it with
the same syntax the AST lint uses, and a baseline JSON works unchanged.

CLI: ``python -m llmss_tpu.analysis --shardcheck`` (exit 0/1/2 — see
``cli.py``). Docs: docs/static-analysis.md.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import sys
import warnings
from pathlib import Path
from typing import Any, Callable

# The audit mesh needs multiple devices; on a CPU backend they must be
# virtualized BEFORE jax initializes. Harmless if jax is already up (the
# test suite's conftest sets the same flag).
if "jax" not in sys.modules:  # pragma: no cover - import-order dependent
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import jax
import jax.numpy as jnp

from .findings import Baseline, Finding, collect_suppressions, is_suppressed
from .shardcheck_rules import SHARD_RULES as RULES

#: Repo-relative path findings are anchored at (the registry lives here).
SRC_PATH = "llmss_tpu/analysis/shardcheck.py"

MANIFEST_VERSION = 1
DEFAULT_MANIFEST = "tools/comms_manifest.json"
DEFAULT_BASELINE = "tools/shardcheck_baseline.json"

#: Collective op names as they appear in optimized HLO.
COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all",
)

#: Ops a pin may legitimately sit behind when we look for the producer of
#: a scan body's ys output (pure relayout/dtype ops).
_PASSTHROUGH = {
    "transpose", "reshape", "convert_element_type", "squeeze",
    "expand_dims", "broadcast_in_dim", "copy",
}

_HLO_ITEMSIZE = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}


# --------------------------------------------------------------------------
# jaxpr analysis: scan-ys taint
# --------------------------------------------------------------------------

def _src_note(eqn) -> str:
    """Best-effort user source location of an equation, for messages."""
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info)
        if frame is not None:
            return f" (scan at {frame.file_name}:{frame.start_line})"
    except Exception:
        pass
    return ""


def _is_replicated_constraint(eqn) -> bool:
    if eqn.primitive.name != "sharding_constraint":
        return False
    sharding = eqn.params.get("sharding")
    try:
        return bool(sharding.is_fully_replicated)
    except Exception:
        return False


def _pinned_ys(body, outvar) -> bool:
    """Is a scan body's ys output produced by a replicated pin (possibly
    behind pure relayout ops)?"""
    producers = {}
    for eqn in body.eqns:
        for ov in eqn.outvars:
            producers[ov] = eqn
    cur = outvar
    for _ in range(16):  # bounded chain walk
        eqn = producers.get(cur)
        if eqn is None:
            return False
        if _is_replicated_constraint(eqn):
            return True
        if eqn.primitive.name in _PASSTHROUGH and eqn.invars:
            cur = eqn.invars[0]
            continue
        return False
    return False


def _sub_jaxpr(eqn):
    """The single sub-jaxpr of a higher-order eqn whose invars align
    positionally with the eqn's invars, or None."""
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = eqn.params.get(key)
        if sub is None:
            continue
        inner = getattr(sub, "jaxpr", sub)  # ClosedJaxpr or Jaxpr
        if len(inner.invars) == len(eqn.invars):
            return inner
    return None


def scan_ys_taint(jaxpr, tainted_in: dict[int, str]) -> dict[int, str]:
    """Forward taint analysis over one Jaxpr.

    Seeds: every scan ys output whose body outvar is NOT produced by a
    replicated ``sharding_constraint``. Taint propagates through every
    equation (conservative) and is cleared by a replicated pin. Returns
    ``{outvar index: hazard description}`` for the jaxpr's outputs.
    """
    from jax.extend.core import Literal

    taint: dict[Any, str] = {}
    for i, v in enumerate(jaxpr.invars):
        if i in tainted_in:
            taint[v] = tainted_in[i]

    def first_taint(eqn) -> str | None:
        for iv in eqn.invars:
            if not isinstance(iv, Literal) and iv in taint:
                return taint[iv]
        return None

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "sharding_constraint":
            if _is_replicated_constraint(eqn):
                continue  # the pin clears taint
            d = first_taint(eqn)
            if d is not None:
                for ov in eqn.outvars:
                    taint[ov] = d
            continue
        if name == "scan":
            body = eqn.params["jaxpr"].jaxpr
            num_carry = eqn.params["num_carry"]
            inner_in = {
                j: taint[iv]
                for j, iv in enumerate(eqn.invars)
                if not isinstance(iv, Literal) and iv in taint
            }
            inner_out = scan_ys_taint(body, inner_in)
            for j, ov in enumerate(eqn.outvars):
                if j < num_carry:
                    # Carries are exempt from the ys rule (their sharding
                    # is pinned by the next iteration's consumers) but
                    # still propagate taint from nested unpinned ys.
                    if j in inner_out:
                        taint[ov] = inner_out[j]
                    continue
                if _pinned_ys(body, body.outvars[j]):
                    continue
                taint[ov] = inner_out.get(j) or (
                    f"stacked scan ys #{j - num_carry}{_src_note(eqn)} "
                    "has no replicated sharding pin"
                )
            continue
        if name == "cond":
            branches = eqn.params.get("branches") or ()
            operand_taint = {
                j: taint[iv]
                for j, iv in enumerate(eqn.invars[1:])
                if not isinstance(iv, Literal) and iv in taint
            }
            merged: dict[int, str] = {}
            for br in branches:
                inner = getattr(br, "jaxpr", br)
                for j, d in scan_ys_taint(inner, operand_taint).items():
                    merged.setdefault(j, d)
            for j, ov in enumerate(eqn.outvars):
                if j in merged:
                    taint[ov] = merged[j]
            continue
        sub = _sub_jaxpr(eqn)
        if sub is not None:
            inner_in = {
                j: taint[iv]
                for j, iv in enumerate(eqn.invars)
                if not isinstance(iv, Literal) and iv in taint
            }
            inner_out = scan_ys_taint(sub, inner_in)
            for j, ov in enumerate(eqn.outvars):
                if j in inner_out:
                    taint[ov] = inner_out[j]
            continue
        d = first_taint(eqn)
        if d is not None:
            for ov in eqn.outvars:
                taint[ov] = d

    out: dict[int, str] = {}
    for i, v in enumerate(jaxpr.outvars):
        if not isinstance(v, Literal) and v in taint:
            out[i] = taint[v]
    return out


# --------------------------------------------------------------------------
# HLO analysis: collectives + donation aliasing
# --------------------------------------------------------------------------

_DEF_RE = re.compile(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*)$")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"(?P<shape>.+?)\s(?P<op>" + "|".join(COLLECTIVE_OPS) + r")(?:-start)?\("
)


def _shape_bytes(shape_text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        size = _HLO_ITEMSIZE.get(dtype)
        if size is None:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * size
    return total


def collective_inventory(hlo_text: str) -> dict[str, dict[str, int]]:
    """``{op: {"count": n, "bytes": result-bytes summed}}`` over every
    defining collective instruction in an HLO module (async ``-start``/
    ``-done`` pairs count once, via the start)."""
    out: dict[str, dict[str, int]] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line.strip())
        if m is None:
            continue
        m2 = _OP_RE.match(m.group(1))
        if m2 is None:
            continue
        entry = out.setdefault(m2.group("op"), {"count": 0, "bytes": 0})
        entry["count"] += 1
        entry["bytes"] += _shape_bytes(m2.group("shape"))
    return out


def count_aliased_outputs(hlo_text: str) -> int:
    """Number of entries in the module's ``input_output_alias`` annotation."""
    idx = hlo_text.find("input_output_alias={")
    if idx < 0:
        return 0
    start = idx + len("input_output_alias=")
    depth, end = 0, start
    for i in range(start, len(hlo_text)):
        if hlo_text[i] == "{":
            depth += 1
        elif hlo_text[i] == "}":
            depth -= 1
            if depth == 0:
                end = i
                break
    return hlo_text.count("-alias", start, end)


_DONATION_SUPPORTED: bool | None = None


def donation_supported() -> bool:
    """Does this backend's compiler implement buffer donation at all?
    Probed once with a trivially aliasable program."""
    global _DONATION_SUPPORTED
    if _DONATION_SUPPORTED is None:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            txt = (
                jax.jit(lambda x: x * 2, donate_argnums=0)
                .lower(jnp.zeros((8, 8), jnp.float32))
                .compile()
                .as_text()
            )
        _DONATION_SUPPORTED = "input_output_alias" in txt
    return _DONATION_SUPPORTED


def classify_donation_warnings(messages: list[str]) -> list[str]:
    """Donation-related warning texts that are genuine findings.

    "Some donated buffers were not usable" means XLA dropped a declared
    donation; "Donation is not implemented for <platform>" is a backend
    capability note, not a program bug (the structural aval check covers
    those platforms)."""
    out = []
    for msg in messages:
        if "onation is not implemented" in msg:
            continue
        if "donated" in msg and ("not usable" in msg or "not used" in msg):
            out.append(msg.splitlines()[0])
    return out


# --------------------------------------------------------------------------
# program registry
# --------------------------------------------------------------------------

#: Audit model: tiny but structurally real (rotary MHA, 2 scanned layers,
#: tp-sharded projections + vocab-parallel head — every collective class
#: the full-size configs emit, at toy sizes so the whole registry traces
#: and compiles in seconds on CPU).
BATCH = 2
MAX_SEQ = 64


@dataclasses.dataclass
class AuditEnv:
    """Everything the program builders need, built once per audit."""

    cfg: Any
    mesh: Any
    params: Any
    engine: Any
    paged: Any
    sample_args: dict

    @property
    def tp(self) -> int:
        from llmss_tpu.parallel.mesh import AXIS_TP

        return self.mesh.shape[AXIS_TP]

    def mesh_dims(self) -> dict[str, int]:
        from llmss_tpu.parallel.mesh import AXIS_DP, AXIS_SP, AXIS_TP

        return {
            "dp": self.mesh.shape[AXIS_DP],
            "sp": self.mesh.shape[AXIS_SP],
            "tp": self.mesh.shape[AXIS_TP],
        }


def build_env(plan=None) -> AuditEnv:
    from llmss_tpu.engine.engine import DecodeEngine, GenerationParams
    from llmss_tpu.models.common import DecoderConfig
    from llmss_tpu.models.decoder import init_params
    from llmss_tpu.parallel.mesh import MeshPlan, make_mesh

    plan = plan or MeshPlan(dp=1, sp=1, tp=2)
    n = plan.dp * plan.sp * plan.tp
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"audit mesh {plan} needs {n} devices, have {len(devices)} — "
            "on CPU set XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )
    mesh = make_mesh(plan, devices=devices[:n])
    cfg = DecoderConfig(
        model_type="shardcheck",
        vocab_size=128,
        hidden_size=32,
        n_layers=2,
        n_heads=4,
        n_kv_heads=4,
        head_dim=8,
        intermediate_size=64,
        max_position_embeddings=MAX_SEQ,
        positions="rotary",
        rope_style="half",
    )
    params = init_params(cfg, mesh, jax.random.PRNGKey(0))
    engine = DecodeEngine(
        cfg, params, mesh, batch_size=BATCH, max_seq_len=MAX_SEQ,
    )
    paged = DecodeEngine(
        cfg, params, mesh, batch_size=BATCH, max_seq_len=MAX_SEQ,
        kv_layout="paged", block_size=16,
    )
    sa = engine._sample_args(GenerationParams(), BATCH)
    return AuditEnv(cfg, mesh, params, engine, paged, sa)


@dataclasses.dataclass
class Program:
    """One production jitted program under audit.

    ``host_fetch`` lists the TOP-LEVEL output-tuple indices the serving
    host actually fetches (``np.asarray``/``device_get``): those outputs
    must be replicated and free of unpinned scan ys. ``line`` anchors
    findings (and ``# lint: ignore`` suppressions) at the registration
    site in this file.
    """

    name: str
    line: int
    host_fetch: tuple[int, ...]
    build: Callable[[AuditEnv], tuple]


def _vec_i32(fill=0):
    return jnp.full((BATCH,), fill, jnp.int32)


def _build_prefill(S):
    def build(env: AuditEnv):
        args = (
            env.params,
            jnp.zeros((BATCH, S), jnp.int32),
            env.engine.new_cache(BATCH),
            jnp.ones((BATCH,), jnp.int32),
            env.sample_args,
        )
        return env.engine._prefill, args, {}

    return build


def _build_decode(env: AuditEnv):
    args = (
        env.params, _vec_i32(), env.engine.new_cache(BATCH),
        jnp.ones((BATCH,), jnp.int32), env.sample_args,
    )
    return env.engine._decode, args, {"t_bucket": None}


def _build_decode_group(env: AuditEnv):
    args = (
        env.params, _vec_i32(), env.engine.new_cache(BATCH),
        jnp.ones((BATCH,), jnp.int32), env.sample_args,
        jnp.zeros((BATCH,), bool), _vec_i32(-1),
    )
    kw = {"n_chunks": 2, "n_steps": 2, "t_bucket": None}
    return env.engine._decode_group, args, kw


def _build_ragged_group(env: AuditEnv):
    # The ragged mixed path serves the paged layout (chunked prefill
    # streams through block tables — forward_ragged requires PagedKVCache).
    nc, CB = 2, 4
    args = (
        env.params, _vec_i32(), env.paged.new_paged_cache(BATCH),
        jnp.ones((BATCH,), jnp.int32), env.sample_args,
        jnp.zeros((BATCH,), bool), _vec_i32(-1),
        jnp.zeros((nc, BATCH, CB), jnp.int32),
        jnp.ones((nc, BATCH), jnp.int32),
        jnp.zeros((nc, BATCH), bool),
        jnp.ones((nc, BATCH), bool),
    )
    return env.paged._ragged_group, args, {}


def _build_spec_group(env: AuditEnv):
    from functools import partial

    from llmss_tpu.engine.speculative import spec_group_impl

    fn = jax.jit(
        partial(
            spec_group_impl, env.cfg, env.mesh,
            m=2, gamma=2, ngram=3, t_bucket=None,
        ),
        donate_argnums=(1, 3),
    )
    args = (
        env.params,
        jnp.zeros((BATCH, MAX_SEQ), jnp.int32),
        jnp.ones((BATCH,), jnp.int32),
        env.engine.new_cache(BATCH),
        jnp.zeros((BATCH,), bool),
        _vec_i32(-1),
    )
    return fn, args, {}


def _build_admit_merge(env: AuditEnv):
    args = (
        _vec_i32(), jnp.ones((BATCH,), jnp.int32),
        _vec_i32(1), jnp.ones((BATCH,), jnp.int32), _vec_i32(),
    )
    return env.engine._admit_merge, args, {}


def _build_seed(env: AuditEnv):
    Pb = 16
    cfg = env.cfg
    seg = jnp.zeros(
        (cfg.n_layers, Pb, cfg.n_kv_heads, cfg.head_dim), cfg.compute_dtype
    )
    args = (
        env.engine.new_cache(BATCH), seg, seg, None, None,
        jnp.asarray(8, jnp.int32),
    )
    return env.engine._seed, args, {}


def _build_import_blocks(env: AuditEnv):
    from llmss_tpu.engine.cache import import_blocks

    cfg, nb, bs = env.cfg, 4, 16
    fn = jax.jit(import_blocks, donate_argnums=(0,))
    seg = jnp.zeros(
        (cfg.n_layers, nb, bs, cfg.n_kv_heads, cfg.head_dim),
        cfg.compute_dtype,
    )
    args = (
        env.paged.new_paged_cache(BATCH), seg, seg, None, None,
        jnp.arange(nb, dtype=jnp.int32),
    )
    return fn, args, {}


def registry() -> list[Program]:
    """Every production program, named by its executable signature
    (``utils/signatures.py``).
    One registration per line: suppression comments and findings anchor
    here."""
    from llmss_tpu.utils.signatures import signature, signature_str

    progs: list[Program] = []

    def _reg(kind, key, host_fetch, build):
        name = signature_str(signature(kind, *key))
        progs.append(
            Program(name, sys._getframe(1).f_lineno, host_fetch, build)
        )

    _reg("prefill", (BATCH, 16), (0,), _build_prefill(16))
    _reg("prefill", (BATCH, 32), (0,), _build_prefill(32))
    _reg("prefill", (BATCH, 64), (0,), _build_prefill(64))
    _reg("decode", (BATCH, None), (0,), _build_decode)
    _reg("decode_group", (BATCH, 2, 2, None), (0,), _build_decode_group)
    _reg("ragged_group", (BATCH, 2, 4), (0,), _build_ragged_group)
    _reg("spec_group", (BATCH, 2, 2, None), (0,), _build_spec_group)
    _reg("admit_merge", (BATCH, BATCH), (), _build_admit_merge)
    _reg("seed", (BATCH, 16), (), _build_seed)
    _reg("import_blocks", (BATCH, 4), (), _build_import_blocks)
    return progs


# --------------------------------------------------------------------------
# per-program audit
# --------------------------------------------------------------------------

def _flat_ranges(shapes) -> list[tuple[int, int]]:
    """Flat-leaf index range of each top-level output-tuple element."""
    elements = shapes if isinstance(shapes, tuple) else (shapes,)
    ranges, start = [], 0
    for el in elements:
        n = len(jax.tree.leaves(el))
        ranges.append((start, start + n))
        start += n
    return ranges


def audit_program(
    prog: Program, env: AuditEnv
) -> tuple[list[Finding], dict[str, dict[str, int]]]:
    """Trace + compile one program; return (findings, collective inventory)."""
    import importlib

    attention = importlib.import_module("llmss_tpu.ops.attention")

    findings: list[Finding] = []

    def flag(rule: str, msg: str) -> None:
        findings.append(
            Finding(rule, SRC_PATH, prog.line, 1, f"{prog.name}: {msg}")
        )

    # Audit ONE deterministic lowering, whatever the platform would
    # choose and whatever pin the caller holds (tests force "pallas"):
    # the manifest must not diff for reasons that are not program changes.
    with attention.force_impl("xla"):
        fn, args, kwargs = prog.build(env)
        with warnings.catch_warnings(record=True) as wrec:
            warnings.simplefilter("always")
            lowered = fn.lower(*args, **kwargs)
            compiled = lowered.compile()
        shapes = lowered.out_info  # output pytree of shape/dtype structs
        # Bind static kwargs before make_jaxpr traces — the tracer must
        # not flow into jit's static_argnames.
        from functools import partial

        closed = jax.make_jaxpr(partial(fn, **kwargs))(*args)

    hlo = compiled.as_text()
    ranges = _flat_ranges(shapes)
    fetched_flat = [
        i for top in prog.host_fetch for i in range(*ranges[top])
    ]

    # (1) partial-sum leaks: unpinned scan ys reaching host-fetched outputs.
    if env.tp > 1:
        tainted = scan_ys_taint(closed.jaxpr, {})
        for i in fetched_flat:
            if i in tainted:
                flag(
                    "partial-sum-leak",
                    f"host-fetched output leaf #{i}: {tainted[i]} — wrap "
                    "the ys with parallel/sharding.ys_pin(mesh) inside "
                    "the program",
                )

    # (2) donation integrity.
    from collections import Counter

    info_leaves = [
        x for x in jax.tree.leaves(lowered.args_info)
        if hasattr(x, "donated")
    ]
    donated = [
        getattr(x, "aval", None) or x._aval for x in info_leaves if x.donated
    ]
    pool = Counter(
        (tuple(a.shape), str(a.dtype)) for a in jax.tree.leaves(shapes)
    )
    matched = 0
    for aval in donated:
        key = (tuple(aval.shape), str(aval.dtype))
        if pool[key] > 0:
            pool[key] -= 1
            matched += 1
        else:
            flag(
                "donation-unmatched",
                f"donated input {key[1]}[{','.join(map(str, key[0]))}] has "
                "no output of the same shape/dtype to alias — the buffer "
                "is lost for nothing",
            )
    if matched and donation_supported():
        aliased = count_aliased_outputs(hlo)
        if aliased < matched:
            flag(
                "donation-dropped",
                f"executable aliases {aliased} of {matched} matchable "
                "donated buffers (input_output_alias)",
            )
    for msg in classify_donation_warnings([str(w.message) for w in wrec]):
        flag("donation-dropped", f"XLA compile warning: {msg}")

    # (3) host-fetch replication.
    out_shardings = jax.tree.leaves(compiled.output_shardings)
    for i in fetched_flat:
        s = out_shardings[i]
        if not s.is_fully_replicated:
            flag(
                "host-fetch-not-replicated",
                f"host-fetched output leaf #{i} compiles to sharding {s} "
                "— every fetch gathers shards on the host path",
            )

    return findings, collective_inventory(hlo)


# --------------------------------------------------------------------------
# manifest
# --------------------------------------------------------------------------

def write_manifest(
    path: str | Path, env: AuditEnv,
    inventories: dict[str, dict[str, dict[str, int]]],
) -> None:
    payload = {
        "version": MANIFEST_VERSION,
        "mesh": env.mesh_dims(),
        "model": {
            "n_layers": env.cfg.n_layers,
            "hidden_size": env.cfg.hidden_size,
            "vocab_size": env.cfg.vocab_size,
            "batch": BATCH,
            "max_seq_len": MAX_SEQ,
        },
        "programs": {
            name: {op: dict(v) for op, v in sorted(inv.items())}
            for name, inv in sorted(inventories.items())
        },
    }
    Path(path).write_text(json.dumps(payload, indent=2) + "\n")


def load_manifest(path: str | Path) -> dict | None:
    p = Path(path)
    if not p.exists():
        return None
    data = json.loads(p.read_text())
    if data.get("version") != MANIFEST_VERSION:
        raise ValueError(
            f"unsupported comms manifest version in {p}: "
            f"{data.get('version')!r}"
        )
    return data


def diff_manifest(
    manifest: dict,
    inventories: dict[str, dict[str, dict[str, int]]],
    lines: dict[str, int],
    *,
    full: bool,
) -> list[Finding]:
    """Findings for every (program, collective op) whose count/bytes
    drifted from the golden manifest. ``full`` audits cover the whole
    registry, so a manifest program the audit did not produce is also
    drift; partial audits (tests' ``only=``) skip that direction."""
    findings: list[Finding] = []
    golden = manifest.get("programs", {})

    def flag(name: str, msg: str) -> None:
        findings.append(Finding(
            "comms-manifest-drift", SRC_PATH, lines.get(name, 1), 1,
            f"{name}: {msg}",
        ))

    for name, inv in sorted(inventories.items()):
        want = golden.get(name)
        if want is None:
            flag(name, "program missing from the golden manifest — run "
                 "--update-manifest if this program is new")
            continue
        for op in sorted(set(inv) | set(want)):
            have = inv.get(op, {"count": 0, "bytes": 0})
            gold = want.get(op, {"count": 0, "bytes": 0})
            if have != gold:
                flag(
                    name,
                    f"{op}: count {have['count']} / {have['bytes']} B vs "
                    f"golden {gold['count']} / {gold['bytes']} B",
                )
    if full:
        for name in sorted(set(golden) - set(inventories)):
            flag(name, "golden manifest lists a program the audit no "
                 "longer produces — run --update-manifest")
    return findings


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------

def run_shardcheck(
    manifest_path: str | None = DEFAULT_MANIFEST,
    *,
    update_manifest: bool = False,
    baseline_path: str | None = DEFAULT_BASELINE,
    plan=None,
    only: list[str] | None = None,
    programs: list[Program] | None = None,
) -> tuple[int, list[Finding]]:
    """Audit the registry; returns (exit code, reportable findings).

    Exit 0 = clean (or suppressed/baselined), 1 = findings, 2 = the
    auditor itself failed (mesh build, trace, or compile error).
    """
    try:
        env = build_env(plan)
    except Exception as e:  # noqa: BLE001 - any env failure is exit 2
        print(f"shardcheck: cannot build audit env: {e}", file=sys.stderr)
        return 2, []

    progs = programs if programs is not None else registry()
    if only:
        progs = [
            p for p in progs if any(p.name.startswith(o) for o in only)
        ]
    if not progs:
        print("shardcheck: no programs selected", file=sys.stderr)
        return 2, []

    findings: list[Finding] = []
    inventories: dict[str, dict[str, dict[str, int]]] = {}
    lines = {p.name: p.line for p in progs}
    for prog in progs:
        try:
            prog_findings, inv = audit_program(prog, env)
        except Exception as e:  # noqa: BLE001 - trace/compile failure
            import traceback

            traceback.print_exc()
            print(
                f"shardcheck: {prog.name} failed to trace/compile: {e}",
                file=sys.stderr,
            )
            return 2, []
        findings.extend(prog_findings)
        inventories[prog.name] = inv

    full = programs is None and not only
    if manifest_path is not None:
        if update_manifest:
            if not full:
                print(
                    "shardcheck: refusing --update-manifest on a partial "
                    "audit (--only)", file=sys.stderr,
                )
                return 2, []
            write_manifest(manifest_path, env, inventories)
            print(
                f"shardcheck: wrote {len(inventories)} program(s) to "
                f"{manifest_path}"
            )
        else:
            try:
                manifest = load_manifest(manifest_path)
            except ValueError as e:
                print(f"shardcheck: {e}", file=sys.stderr)
                return 2, []
            if manifest is None:
                print(
                    f"shardcheck: no manifest at {manifest_path} — run "
                    "--update-manifest to create the golden inventory",
                    file=sys.stderr,
                )
                return 2, []
            if manifest.get("mesh") != env.mesh_dims():
                print(
                    f"shardcheck: manifest mesh {manifest.get('mesh')} != "
                    f"audit mesh {env.mesh_dims()}; skipping the comms "
                    "diff (collective counts are mesh-specific)",
                    file=sys.stderr,
                )
            else:
                findings.extend(
                    diff_manifest(manifest, inventories, lines, full=full)
                )

    suppressions = collect_suppressions(Path(__file__).read_text())
    findings = [f for f in findings if not is_suppressed(f, suppressions)]
    findings.sort(key=lambda f: (f.line, f.rule, f.message))

    baseline = (
        Baseline.load(baseline_path) if baseline_path else Baseline()
    )
    new = [f for f in findings if f not in baseline]
    for f in new:
        print(f.render())
    baselined = len(findings) - len(new)
    if new:
        print(
            f"shardcheck: {len(new)} finding(s)"
            + (f" ({baselined} baselined)" if baselined else "")
        )
        return 1, new
    print(
        f"shardcheck: clean — {len(progs)} program(s) audited"
        + (f" ({baselined} baselined finding(s))" if baselined else "")
    )
    return 0, []
