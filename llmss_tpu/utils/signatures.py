"""Executable-signature vocabulary of the static SPMD auditor.

One closed enum of kernel classes and ONE formatting convention for
executable signatures: ``analysis/shardcheck.py``'s program registry and
``tools/comms_manifest.json`` name every program ``kind/part/part``.

Pure stdlib on purpose — the AST-only lint CI job imports nothing
heavier than this module.
"""

from __future__ import annotations

#: Every executable class the registry may key by: the model-forward
#: programs, then the state-management ones (scatters and merges, whose
#: sharding/donation/collective contracts are load-bearing).
KERNEL_CLASSES = (
    "prefill",
    "decode",
    "decode_group",
    "ragged_group",
    "spec_group",
    "admit_merge",
    "seed",
    "import_blocks",
)

def signature(kind: str, *key) -> tuple:
    """The canonical executable signature: ``(kind, *shape-key parts)``.

    ``kind`` must come from :data:`KERNEL_CLASSES` — an unknown class is a
    programming error at the call site (a new executable family must be
    added to the enum), not a new dict key.
    """
    if kind not in KERNEL_CLASSES:
        raise ValueError(
            f"unknown kernel class {kind!r}; add it to "
            f"signatures.KERNEL_CLASSES (have: {', '.join(KERNEL_CLASSES)})"
        )
    return (kind, *key)


def signature_str(sig: tuple) -> str:
    """Render a signature for export keys and manifest program names:
    ``/``-joined parts (``decode_group/8/4/16/None``)."""
    return "/".join(str(p) for p in sig)
