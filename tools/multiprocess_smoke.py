"""Multi-process runtime smoke: one engine step over a cross-process mesh.

The reference's default launch is real multi-process rendezvous
(``torchrun --nproc_per_node 4``, ``poc-server/producer-consumer/
README.md:24-37``; ``utils/dist.py:65-77`` ``init_process_group``). The
TPU-native equivalent is multi-controller JAX: every host runs this same
program, ``jax.distributed.initialize`` rendezvouses them at the
coordinator, and the device mesh spans all processes — collectives are
compiled by XLA across ICI/DCN, with no communication library to manage.

This script rehearses that launch recipe on the CPU, and only there: each
process contributes ``--local-devices`` virtual CPU devices, the mesh is
TP over the global device count (the reference's world-group-as-TP-group,
``dist.py:77``), and one prefill + one decode step run SPMD across the
processes. It pins the CPU platform and asserts it got it — a chip belongs
to one process at a time, so two processes of this script on one host
could never share one. (One process drives all the chips of a host:
``python chip_smoke.py --chips 4``.)

Run two processes locally:

    python tools/multiprocess_smoke.py --process-id 0 --num-processes 2 \
        --coordinator localhost:9911 &
    python tools/multiprocess_smoke.py --process-id 1 --num-processes 2 \
        --coordinator localhost:9911

Each prints ``mpsmoke ok pid=N processes=2 devices=4 toks=[...]``; the
token lists must be identical (tests/test_multiprocess.py asserts this).
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--process-id", type=int, required=True)
    ap.add_argument("--num-processes", type=int, required=True)
    ap.add_argument("--coordinator", required=True)
    ap.add_argument("--local-devices", type=int, default=2)
    args = ap.parse_args()

    # Environment must be set before the JAX backend initializes. The env
    # var alone is read too late if anything imported jax before this
    # point — override via config as well, which wins as long as the
    # backend itself has not initialized yet (same as tests/conftest.py).
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count="
        f"{args.local_devices}"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")

    from llmss_tpu.parallel.mesh import initialize_runtime

    # The branch under test: real jax.distributed.initialize rendezvous
    # (≙ dist.py:65-73). Must run before any device query.
    initialize_runtime(
        coordinator_address=args.coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id,
    )

    import jax.numpy as jnp
    import numpy as np

    assert jax.default_backend() == "cpu", jax.default_backend()
    assert jax.process_count() == args.num_processes, jax.process_count()
    n_global = len(jax.devices())
    n_local = len(jax.local_devices())
    assert n_local == args.local_devices, n_local
    assert n_global == args.local_devices * args.num_processes, n_global

    from llmss_tpu.engine import DecodeEngine, GenerationParams
    from llmss_tpu.models.common import DecoderConfig
    from llmss_tpu.models.decoder import init_params
    from llmss_tpu.parallel import MeshPlan, make_mesh

    cfg = DecoderConfig(
        model_type="llama", vocab_size=64, hidden_size=32, n_layers=2,
        n_heads=4, n_kv_heads=4, head_dim=8, intermediate_size=64,
        max_position_embeddings=64, activation="silu", norm="rmsnorm",
        norm_eps=1e-5, mlp="swiglu", positions="rotary", rope_style="half",
        rotary_dim=8, attn_bias=False, mlp_bias=False,
        tie_word_embeddings=False, dtype="float32",
    )
    # TP over the whole cross-process world — the mesh's tp axis spans both
    # processes, so every RowLinear psum and the lm-head all-gather compiled
    # from the sharding constraints is a REAL cross-process collective.
    mesh = make_mesh(MeshPlan(tp=n_global))
    params = init_params(cfg, mesh, jax.random.key(0))
    engine = DecodeEngine(cfg, params, mesh, max_seq_len=32)

    ids = jnp.asarray(np.asarray([[1, 2, 3, 4, 5, 6, 7, 8] + [0] * 8]))
    lens = jnp.asarray(np.asarray([8], np.int32))
    sa = engine._sample_args(GenerationParams(is_greedy=True), 1)
    cache = engine.new_cache(1)
    tok, _, cache = engine._prefill(engine.params, ids, cache, lens, sa)
    toks = [int(np.asarray(engine.canon_vec(tok))[0])]
    cur = jnp.asarray(np.asarray([8], np.int32))
    for _ in range(3):
        tok, _, cache = engine._decode(engine.params, tok, cache, cur, sa)
        toks.append(int(np.asarray(engine.canon_vec(tok))[0]))
        cur = cur + 1

    print(
        f"mpsmoke ok pid={args.process_id} "
        f"processes={jax.process_count()} devices={n_global} toks={toks}",
        flush=True,
    )


if __name__ == "__main__":
    sys.exit(main())
