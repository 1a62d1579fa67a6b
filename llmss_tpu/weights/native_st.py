"""Native safetensors reader: header parse in Python, byte gather in C++.

The reference reads TP shards through the safetensors Python binding
(``utils/weights.py:77-88`` ``get_slice``), one GIL-bound call per tensor.
Here the data plane is native (``llmss_tpu/native/st_gather.cc``): a shard
read is
expressed as strided (offset, bytes, stride) segments and fanned out over a
pread thread pool — GIL-free, and many tensors batch into a single call
(``read_many``), which is what the stacked per-layer loads want.

The safetensors container itself is trivial to parse (8-byte little-endian
header length + JSON of ``{name: {dtype, shape, data_offsets}}``), so this
module has no dependency on the safetensors package; if the C++ library
can't be built, reads fall back to ``np.memmap`` with identical semantics.
"""

from __future__ import annotations

import ctypes
import hashlib
import json
import os
import struct
import subprocess
import tempfile
import threading
import warnings
from pathlib import Path

import numpy as np

import ml_dtypes

_DTYPES: dict[str, np.dtype] = {
    "F64": np.dtype(np.float64),
    "F32": np.dtype(np.float32),
    "F16": np.dtype(np.float16),
    "BF16": np.dtype(ml_dtypes.bfloat16),
    "F8_E4M3": np.dtype(ml_dtypes.float8_e4m3fn),
    "F8_E5M2": np.dtype(ml_dtypes.float8_e5m2),
    "I64": np.dtype(np.int64),
    "I32": np.dtype(np.int32),
    "I16": np.dtype(np.int16),
    "I8": np.dtype(np.int8),
    "U8": np.dtype(np.uint8),
    "U16": np.dtype(np.uint16),
    "U32": np.dtype(np.uint32),
    "U64": np.dtype(np.uint64),
    "BOOL": np.dtype(np.bool_),
}

_NATIVE_DIR = Path(__file__).resolve().parent.parent / "native"
_LIB_LOCK = threading.Lock()
_LIB: ctypes.CDLL | None = None
_LIB_FAILED = False
_LIB_ERROR = ""  # why the build/load failed, for require_native()


def _build_lib() -> ctypes.CDLL | None:
    """Compile-and-cache llmss_tpu/native/st_gather.cc → .../build/.

    The built library is named by a hash of the source it was compiled
    from, so a binary never outlives its source (a checkout copied with
    its ignored build directory, a file whose mtime moved backwards).

    Returns None (→ single-threaded memmap fallback, with a one-time
    warning) if no toolchain is available or the build fails; callers
    that must not run on the fallback call ``require_native()``. The
    compile goes to a temp file then ``os.replace`` — atomic, so
    concurrent processes never load a half-written .so or truncate one
    that another process has mapped."""
    global _LIB, _LIB_FAILED, _LIB_ERROR
    with _LIB_LOCK:
        if _LIB is not None or _LIB_FAILED:
            return _LIB
        src = _NATIVE_DIR / "st_gather.cc"
        try:
            digest = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
            so = _NATIVE_DIR / "build" / f"libstgather-{digest}.so"
            if not so.exists():
                so.parent.mkdir(parents=True, exist_ok=True)
                fd, tmp = tempfile.mkstemp(
                    suffix=".so", dir=str(so.parent)
                )
                os.close(fd)
                try:
                    subprocess.run(
                        ["g++", "-O3", "-std=c++17", "-shared", "-fPIC",
                         "-pthread", "-o", tmp, str(src)],
                        check=True, capture_output=True, timeout=120,
                    )
                    os.replace(tmp, so)
                finally:
                    if os.path.exists(tmp):
                        os.unlink(tmp)
            lib = ctypes.CDLL(str(so))
            lib.st_gather.restype = ctypes.c_int
            lib.st_gather.argtypes = [
                ctypes.c_char_p, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_void_p), ctypes.c_int,
            ]
            _LIB = lib
        except Exception as e:  # noqa: BLE001 — build/load failure → fallback
            _LIB_FAILED = True
            _LIB_ERROR = f"{type(e).__name__}: {e}"
            warnings.warn(
                f"native st_gather unavailable ({_LIB_ERROR}); "
                "weight reads fall back to single-threaded memmap",
                RuntimeWarning,
                stacklevel=2,
            )
        return _LIB


def require_native() -> None:
    """Raise unless the C++ gather serves this process's reads — for
    entry points (chip_smoke.py) where the memmap fallback would hide a
    toolchain or build fault behind a slower load."""
    if _build_lib() is None:
        raise RuntimeError(f"native st_gather unavailable: {_LIB_ERROR}")


class NativeSafetensors:
    """Read-only safetensors file with native sliced reads.

    Supports the shapes weight loading actually uses — full tensors and
    hyper-rectangle slices of 1D/2D tensors (TP shards). ND tensors read
    whole; general ND slicing is not needed for any registered model.
    """

    def __init__(self, path: str | Path, *, n_threads: int | None = None):
        self.path = Path(path)
        self.n_threads = n_threads or min(16, os.cpu_count() or 4)
        with open(self.path, "rb") as f:
            (header_len,) = struct.unpack("<Q", f.read(8))
            header = json.loads(f.read(header_len))
        self._data_start = 8 + header_len
        self.tensors: dict[str, tuple[np.dtype, tuple[int, ...], int, int]] = {}
        for name, info in header.items():
            if name == "__metadata__":
                continue
            lo, hi = info["data_offsets"]
            self.tensors[name] = (
                _DTYPES[info["dtype"]], tuple(info["shape"]), lo, hi
            )

    def keys(self):
        return self.tensors.keys()

    def shape(self, name: str) -> tuple[int, ...]:
        return self.tensors[name][1]

    def dtype(self, name: str) -> np.dtype:
        return self.tensors[name][0]

    # -- segment construction ------------------------------------------------

    def _segment(self, name: str, index: tuple[slice, ...] | None):
        """(file_offset, chunk_bytes, n_chunks, stride, out_shape)."""
        dt, shape, lo, hi = self.tensors[name]
        item = dt.itemsize
        base = self._data_start + lo
        if index is None or len(shape) == 0:
            n = (hi - lo) // item if item else 0
            return base, hi - lo, 1, 0, shape
        index = tuple(index) + (slice(None),) * (len(shape) - len(index))
        bounds = [
            (s.start or 0, s.stop if s.stop is not None else dim)
            for s, dim in zip(index, shape)
        ]
        out_shape = tuple(b - a for a, b in bounds)
        if len(shape) == 1:
            (a, b), = bounds
            return base + a * item, (b - a) * item, 1, 0, out_shape
        if len(shape) == 2:
            (r0, r1), (c0, c1) = bounds
            row_bytes = shape[1] * item
            return (
                base + r0 * row_bytes + c0 * item,
                (c1 - c0) * item,
                r1 - r0,
                row_bytes,
                out_shape,
            )
        raise ValueError(
            f"native sliced read supports 1D/2D tensors, got {shape}"
        )

    def supports(self, name: str, index: tuple[slice, ...] | None) -> bool:
        if name not in self.tensors:
            return False
        shape = self.tensors[name][1]
        if index is None:
            return True
        if any(s.step not in (None, 1) for s in index):
            return False
        return len(shape) <= 2

    # -- reads ---------------------------------------------------------------

    def read(self, name: str, index: tuple[slice, ...] | None = None
             ) -> np.ndarray:
        return self.read_many([(name, index)])[0]

    def read_many(
        self, requests: list[tuple[str, tuple[slice, ...] | None]]
    ) -> list[np.ndarray]:
        """Read several tensors/slices in one native call (one shared
        thread pool over all chunks). Requests the native path can't express
        (sliced ND>2, stepped slices) fall back to memmap."""
        lib = _build_lib()
        outs: list[np.ndarray | None] = [None] * len(requests)
        native = [
            i for i, (name, index) in enumerate(requests)
            if lib is not None and self.supports(name, index)
        ]
        if native:
            # Flatten to (offset, chunk_bytes, n_chunks, stride, dst) rows,
            # splitting big contiguous reads into 8 MB chunks so a single
            # large tensor still spreads over the whole thread pool.
            CHUNK = 8 << 20
            rows: list[tuple[int, int, int, int, int]] = []
            for i in native:
                off, cb, nc, stride, shape = self._segment(*requests[i])
                out = np.empty(shape, self.tensors[requests[i][0]][0])
                outs[i] = out
                ptr = out.ctypes.data
                if nc > 1 and stride == cb:
                    # Full-width row range: the rows are contiguous in the
                    # file — coalesce so the 8 MB splitter applies instead
                    # of issuing one pread per row.
                    cb, nc, stride = cb * nc, 1, 0
                if nc == 1 and cb > CHUNK:
                    n_full = cb // CHUNK
                    rows.append((off, CHUNK, n_full, CHUNK, ptr))
                    rem = cb - n_full * CHUNK
                    if rem:
                        rows.append(
                            (off + n_full * CHUNK, rem, 1, 0,
                             ptr + n_full * CHUNK)
                        )
                else:
                    rows.append((off, cb, nc, stride, ptr))
            n = len(rows)
            arr = lambda col: (ctypes.c_int64 * n)(  # noqa: E731
                *[r[col] for r in rows]
            )
            dsts = (ctypes.c_void_p * n)(*[r[4] for r in rows])
            rc = lib.st_gather(
                str(self.path).encode(), n,
                arr(0), arr(1), arr(2), arr(3), dsts, self.n_threads,
            )
            if rc != 0:
                detail = {
                    -1: "open/read failed",
                    -2: "unexpected EOF — file truncated or header "
                        "offsets out of range",
                }.get(rc, os.strerror(rc) if rc > 0 else f"code {rc}")
                raise OSError(f"st_gather({self.path}): {detail}")
        rest = [i for i in range(len(requests)) if outs[i] is None]
        if rest:
            mm = np.memmap(self.path, dtype=np.uint8, mode="r")
            for i in rest:
                name, index = requests[i]
                dt, shape, lo, hi = self.tensors[name]
                view = mm[
                    self._data_start + lo : self._data_start + hi
                ].view(dt).reshape(shape)
                outs[i] = np.array(
                    view[tuple(index)] if index is not None else view
                )
        return outs  # type: ignore[return-value]
