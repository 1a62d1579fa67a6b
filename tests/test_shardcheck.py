"""shardcheck: seeded-regression detection, manifest drift, suppression/
baseline mechanics, and the shared executable-signature vocabulary.

The seeded fixtures re-introduce the exact bug classes the auditor exists
for — the PR 6 partial-sum leak (unpinned scan ys fetched by the host) and
a donation that aliases nothing — and assert each flips the exit code.
The full-registry audit against the committed golden manifest is the CI
step itself (and the `slow`-marked gate test at the bottom).
"""

import json
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from llmss_tpu.analysis import shardcheck as sc
from llmss_tpu.parallel.mesh import AXIS_TP


@pytest.fixture(scope="module")
def env(devices):
    e = sc.build_env()
    # Every run_shardcheck() in this module reuses the one audit env —
    # rebuilding params + engines per exit-code test is pure overhead.
    mp = pytest.MonkeyPatch()
    mp.setattr(sc, "build_env", lambda plan=None: e)
    yield e
    mp.undo()


def _prog(name, host_fetch, fn, args, kwargs=None, line=999):
    return sc.Program(name, line, host_fetch, lambda e: (fn, args, kwargs or {}))


# -- seeded regressions ------------------------------------------------------

def _buggy_pair(env):
    """The PR 6 bug, minimal: scan-stacked argmax over a tp-sharded matmul
    reaches a host-fetched output. GSPMD stacks the *unreduced* per-shard
    layout into the ys; every host fetch then sees partial sums."""
    mesh = env.mesh
    w = jax.device_put(
        jnp.zeros((8, 16)), NamedSharding(mesh, P(None, AXIS_TP))
    )
    x = jnp.zeros((2, 8))

    def buggy(w, x):
        def step(h, _):
            tok = jnp.argmax(h @ w, -1).astype(jnp.int32)
            return h, tok

        h, toks = jax.lax.scan(step, x, None, length=3)
        return toks.T, h

    def fixed(w, x):
        from llmss_tpu.parallel.sharding import ys_pin

        pin = ys_pin(mesh)

        def step(h, _):
            tok = jnp.argmax(h @ w, -1).astype(jnp.int32)
            return h, pin(tok)

        h, toks = jax.lax.scan(step, x, None, length=3)
        return toks.T, h

    return (
        _prog("decode/buggy", (0,), jax.jit(buggy), (w, x)),
        _prog("decode/fixed", (0,), jax.jit(fixed), (w, x)),
    )


def test_seeded_partial_sum_leak_detected(env):
    buggy, fixed = _buggy_pair(env)
    findings, _ = sc.audit_program(buggy, env)
    assert "partial-sum-leak" in {f.rule for f in findings}
    leak = next(f for f in findings if f.rule == "partial-sum-leak")
    # Findings anchor at the registration line in shardcheck.py itself so
    # `# lint: ignore[...]` comments land next to the program they cover.
    assert (leak.path, leak.line) == (sc.SRC_PATH, buggy.line)
    assert "ys_pin" in leak.message

    findings, _ = sc.audit_program(fixed, env)
    assert findings == []


def test_reintroduced_decode_many_bug_detected(env):
    """_decode_many before the ys_pin fix, verbatim: the grouped paths got
    the pin, this one leaked the same stacked tokens to np.asarray."""
    from llmss_tpu.engine.engine import DecodeEngine

    def old_decode_many(
        cfg, mesh, params, tokens, cache, cur_pos, sample_args, done, eos,
        *, n_steps, t_bucket=None,
    ):
        body = partial(
            DecodeEngine._decode_step_body,
            cfg, mesh, params, sample_args, eos, t_bucket,
        )
        # (the step body's ys are (tokens, routing counts) since PR 38)
        carry, (toks, _) = jax.lax.scan(
            body,
            (tokens, cache, cur_pos, done, jnp.zeros_like(done)),
            None,
            length=n_steps,
        )
        tokens, cache, cur_pos, done, poisoned = carry
        return toks.T, cache, cur_pos, done, poisoned

    fn = jax.jit(
        partial(old_decode_many, env.cfg, env.mesh),
        donate_argnums=(2,),
        static_argnames=("n_steps", "t_bucket"),
    )
    args = (
        env.params,
        jnp.zeros((sc.BATCH,), jnp.int32),
        env.engine.new_cache(sc.BATCH),
        jnp.ones((sc.BATCH,), jnp.int32),
        env.sample_args,
        jnp.zeros((sc.BATCH,), bool),
        jnp.full((sc.BATCH,), -1, jnp.int32),
    )
    prog = _prog(
        "decode_many/old", (0, 4), fn, args, {"n_steps": 2, "t_bucket": None}
    )
    findings, _ = sc.audit_program(prog, env)
    assert "partial-sum-leak" in {f.rule for f in findings}


def test_seeded_dropped_donation_detected(env):
    # Donating a (4,4) input to a program whose only outputs are (3,)
    # aliases nothing — the donated buffer is lost for no benefit.
    fn = jax.jit(lambda a, b: b * 2.0, donate_argnums=(0,))
    prog = _prog(
        "decode/donation", (), fn, (jnp.zeros((4, 4)), jnp.zeros((3,)))
    )
    findings, _ = sc.audit_program(prog, env)
    assert [f.rule for f in findings] == ["donation-unmatched"]

    # The matched twin: same shape/dtype out, donation aliases, clean.
    fn_ok = jax.jit(lambda a, b: a * 2.0, donate_argnums=(0,))
    prog_ok = _prog(
        "decode/donation-ok", (), fn_ok, (jnp.zeros((4, 4)), jnp.zeros((3,)))
    )
    findings, _ = sc.audit_program(prog_ok, env)
    assert findings == []


def test_dropped_donation_warning_classification():
    # XLA reports a dropped donation as a compile warning; the audit turns
    # it into a donation-dropped finding. Backend capability notes
    # ("Donation is not implemented for cpu") are not program bugs.
    msgs = [
        "Some donated buffers were not usable: f32[4,4]\nsecond line",
        "Donation is not implemented for cpu.\nSee explanation.",
        "Buffer donated to output 3 was not used.",
        "unrelated warning",
    ]
    out = sc.classify_donation_warnings(msgs)
    assert out == [
        "Some donated buffers were not usable: f32[4,4]",
        "Buffer donated to output 3 was not used.",
    ]


def test_aliased_output_count_from_hlo_header():
    # donation-dropped also fires structurally: fewer aliased buffers in
    # the executable than matchable donations. Parse a realistic header.
    hlo = (
        "HloModule jit_f, input_output_alias={ {0}: (2, {}, may-alias), "
        "{1}: (4, {}, must-alias) }, entry_computation_layout=...\n"
        "ENTRY main { ... }\n"
    )
    assert sc.count_aliased_outputs(hlo) == 2
    assert sc.count_aliased_outputs("HloModule jit_f, entry_layout=x") == 0


def test_host_fetch_not_replicated_detected(env):
    fn = jax.jit(
        lambda x: x * 2.0,
        out_shardings=NamedSharding(env.mesh, P(AXIS_TP)),
    )
    prog = _prog("decode/sharded-out", (0,), fn, (jnp.zeros((8,)),))
    findings, _ = sc.audit_program(prog, env)
    assert [f.rule for f in findings] == ["host-fetch-not-replicated"]


def test_seeded_finding_flips_exit_code(env):
    buggy, _ = _buggy_pair(env)
    code, findings = sc.run_shardcheck(
        None, programs=[buggy], baseline_path=None
    )
    assert code == 1
    assert {f.rule for f in findings} == {"partial-sum-leak"}


# -- golden comms manifest ---------------------------------------------------

def _collective_prog(env):
    """Tiny program with a real collective: tp-sharded matmul pinned
    replicated compiles to an all-reduce of the partial sums."""
    mesh = env.mesh
    w = jax.device_put(
        jnp.zeros((8, 16)), NamedSharding(mesh, P(None, AXIS_TP))
    )

    def f(w, x):
        return jax.lax.with_sharding_constraint(
            x @ w, NamedSharding(mesh, P())
        )

    return _prog("decode/tiny-collective", (0,), jax.jit(f), (w, jnp.zeros((2, 8))))


def _manifest_for(env, name, inv):
    return {
        "version": sc.MANIFEST_VERSION,
        "mesh": env.mesh_dims(),
        "model": {},
        "programs": {name: inv},
    }


def test_manifest_match_and_drift_flip_exit_code(env, tmp_path):
    prog = _collective_prog(env)
    findings, inv = sc.audit_program(prog, env)
    assert findings == []
    # The replication pin over tp-sharded compute must cost a collective.
    assert inv, "expected at least one collective in the tiny program"
    op = sorted(inv)[0]

    golden = tmp_path / "manifest.json"
    golden.write_text(json.dumps(_manifest_for(env, prog.name, inv)))
    code, findings = sc.run_shardcheck(
        str(golden), programs=[prog], baseline_path=None
    )
    assert (code, findings) == (0, [])

    # One extra collective in the golden counts — the audit must fail.
    tampered = {o: dict(v) for o, v in inv.items()}
    tampered[op]["count"] += 1
    golden.write_text(json.dumps(_manifest_for(env, prog.name, tampered)))
    code, findings = sc.run_shardcheck(
        str(golden), programs=[prog], baseline_path=None
    )
    assert code == 1
    assert {f.rule for f in findings} == {"comms-manifest-drift"}
    assert op in findings[0].message

    # A collective class the golden never heard of is also drift.
    extra = {o: dict(v) for o, v in inv.items()}
    extra.pop(op)
    golden.write_text(json.dumps(_manifest_for(env, prog.name, extra)))
    code, findings = sc.run_shardcheck(
        str(golden), programs=[prog], baseline_path=None
    )
    assert code == 1
    assert {f.rule for f in findings} == {"comms-manifest-drift"}


def test_program_missing_from_golden_is_drift(env, tmp_path):
    prog = _collective_prog(env)
    golden = tmp_path / "manifest.json"
    golden.write_text(json.dumps(_manifest_for(env, "someone/else", {})))
    code, findings = sc.run_shardcheck(
        str(golden), programs=[prog], baseline_path=None
    )
    assert code == 1
    assert any("missing from the golden manifest" in f.message for f in findings)
    # Partial audits skip the reverse direction (golden-but-not-audited):
    # `someone/else` not being audited here is not drift.
    assert len(findings) == 1


def test_mesh_mismatch_skips_comms_diff(env, tmp_path):
    prog = _collective_prog(env)
    _, inv = sc.audit_program(prog, env)
    manifest = _manifest_for(env, prog.name, {})  # would be drift...
    manifest["mesh"] = {"dp": 4, "sp": 1, "tp": 2}  # ...but wrong mesh
    golden = tmp_path / "manifest.json"
    golden.write_text(json.dumps(manifest))
    code, findings = sc.run_shardcheck(
        str(golden), programs=[prog], baseline_path=None
    )
    assert (code, findings) == (0, [])


def test_unsupported_manifest_version_is_infra_error(env, tmp_path):
    golden = tmp_path / "manifest.json"
    golden.write_text(json.dumps({"version": 99, "programs": {}}))
    code, _ = sc.run_shardcheck(
        str(golden), programs=[_collective_prog(env)], baseline_path=None
    )
    assert code == 2


def test_update_manifest_refuses_partial_audit(env, tmp_path):
    code, _ = sc.run_shardcheck(
        str(tmp_path / "m.json"),
        update_manifest=True,
        programs=[_collective_prog(env)],
        baseline_path=None,
    )
    assert code == 2


# -- suppression + baseline mechanics ----------------------------------------

def test_registration_line_suppression(env, monkeypatch):
    buggy, _ = _buggy_pair(env)
    monkeypatch.setattr(
        sc, "collect_suppressions",
        lambda _src: {buggy.line: {"partial-sum-leak"}},
    )
    code, findings = sc.run_shardcheck(
        None, programs=[buggy], baseline_path=None
    )
    assert (code, findings) == (0, [])
    # Rule-specific: suppressing a different rule leaves the finding live.
    monkeypatch.setattr(
        sc, "collect_suppressions",
        lambda _src: {buggy.line: {"donation-dropped"}},
    )
    code, _ = sc.run_shardcheck(None, programs=[buggy], baseline_path=None)
    assert code == 1


def test_baseline_accepts_existing_findings(env, tmp_path):
    from llmss_tpu.analysis.findings import Baseline

    buggy, _ = _buggy_pair(env)
    code, findings = sc.run_shardcheck(
        None, programs=[buggy], baseline_path=None
    )
    assert code == 1
    baseline = tmp_path / "shardcheck_baseline.json"
    Baseline().write(str(baseline), findings)
    code, findings = sc.run_shardcheck(
        None, programs=[buggy], baseline_path=str(baseline)
    )
    assert (code, findings) == (0, [])


# -- the executable-signature vocabulary --------------------------------------

def test_registry_names_are_signature_strs(env):
    from llmss_tpu.utils.signatures import KERNEL_CLASSES, signature

    with pytest.raises(ValueError):
        signature("warp_drive", 2)
    progs = sc.registry()
    assert len(progs) == len({p.name for p in progs})
    for p in progs:
        kind = p.name.split("/")[0]
        assert kind in KERNEL_CLASSES, p.name


# -- the gate itself (the CI step runs this same audit) ----------------------

@pytest.mark.slow
def test_full_registry_matches_committed_manifest():
    code, findings = sc.run_shardcheck()
    assert code == 0, "\n".join(f.render() for f in findings)
