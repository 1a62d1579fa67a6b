"""``benchmark/run.py`` as it is, plus the readings of per-layer readers that
the manifest does not declare yet (the functions of ``benchmark/lib/moe.py``,
``lib/gdn.py``, ``lib/qwen3_next.py``, ``lib/setup.py``: only a ``benchmark``
PR may declare one, PERF.md section 7 (5)). The builder's way to read them on
the chip: the run is the harness's own, each reader is called on the same
``ctx`` the declared ones get, and its value goes to stderr as one line,
``READER <name> <value>``; nothing of the run's result changes.

    python3 tools/run_with_readers.py qwen3_next:experts_pct qwen3_next:gdn_pct -- \\
        --workload qwen3-next-80b-a3b-1chip.doc --seed 1 --seconds 51 --trace 1
"""

from __future__ import annotations

import importlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark import run  # noqa: E402


def main() -> int:
    cut = sys.argv.index("--")
    readers = []
    for spec in sys.argv[1:cut]:
        module, name = spec.split(":")
        readers.append(
            (spec, getattr(importlib.import_module(f"benchmark.lib.{module}"), name)))
    declared = run.layer_values

    def layer_values(entries, ctx):
        for spec, read in readers:
            run.log(f"READER {spec} {json.dumps(read(ctx))}")
        if ctx.get("trace"):
            run.log("OPS " + json.dumps(ctx["trace"].get("ops", [])))
        return declared(entries, ctx)

    run.layer_values = layer_values
    sys.argv = [str(ROOT / "benchmark" / "run.py"), *sys.argv[cut + 1:]]
    return run.main()


if __name__ == "__main__":
    sys.exit(main())
