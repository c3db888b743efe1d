"""native — C++ host-runtime components with ctypes bindings.

The reference's compute path runs on third-party native code (JVM Spark for
ingestion, ATen for tensors, gloo for collectives — SURVEY.md §2.2). The
TPU build's device side is XLA/Pallas; this package is the *host* side in
C++: a fast libsvm parser (``libsvm_parser.cpp``), a threaded batch
row-gather (``batch_gather.cpp``), and one-pass batch text encoding
(``text_encode.cpp`` — tokenize + vocab lookup + pad; ~12× the Python
chain on the AG_NEWS-format corpus, exact-parity-tested).

Build model: compiled on demand with ``g++ -O3 -shared -fPIC`` into a cached
shared library next to the sources (atomic rename, safe under multi-process
gangs). The library's file name carries a hash of the sources' content, so
a binary is only ever loaded for the sources it was built from — a copied
tree (mtimes rewritten) or an edited source cannot pick up a stale one. No
pybind11 — plain C ABI + ctypes (the image has no pybind11; see build
contract). Everything degrades gracefully: callers catch ImportError and
fall back to the pure-Python paths; ``status()`` says which happened.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import tempfile
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCES = ("libsvm_parser.cpp", "batch_gather.cpp", "text_encode.cpp")
_SO_PREFIX = "_mlspark_native_"

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_build_error: Exception | None = None
_status = "unloaded"


def _so_path() -> str:
    """``_mlspark_native_<hash of the sources' bytes>.so``."""
    digest = hashlib.sha256()
    for name in _SOURCES:
        with open(os.path.join(_DIR, name), "rb") as f:
            digest.update(f.read())
    return os.path.join(_DIR, f"{_SO_PREFIX}{digest.hexdigest()[:16]}.so")


def _build(so_path: str) -> None:
    sources = [os.path.join(_DIR, s) for s in _SOURCES]
    # Build into a temp file then atomically rename: concurrent ranks of a
    # gang may race to build; the loser's rename simply overwrites with an
    # identical library.
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_DIR)
    os.close(fd)
    cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
        "-o", tmp, *sources,
    ]
    try:
        subprocess.run(
            cmd, check=True, capture_output=True, text=True, timeout=300
        )
        os.replace(tmp, so_path)
        for stale in glob.glob(os.path.join(_DIR, f"{_SO_PREFIX}*.so")):
            if stale != so_path:
                os.unlink(stale)  # built from sources that no longer exist
    except (subprocess.SubprocessError, OSError) as e:
        # covers compile errors, timeouts, and a missing g++ alike
        detail = getattr(e, "stderr", "") or str(e)
        raise ImportError(f"native build failed: {detail}") from e
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _load() -> ctypes.CDLL:
    """Build (unless a library for these sources exists) and load the
    shared library, memoized."""
    global _lib, _build_error, _status
    with _lock:
        if _lib is not None:
            return _lib
        if _build_error is not None:
            raise ImportError("native library unavailable") from _build_error
        try:
            so_path = _so_path()
            built = not os.path.exists(so_path)
            if built:
                _build(so_path)
            lib = ctypes.CDLL(so_path)
        except (ImportError, OSError) as e:
            _build_error = e
            _status = "python-fallback"
            raise ImportError("native library unavailable") from e
        _status = "built" if built else "loaded"

        lib.mlspark_libsvm_parse.restype = ctypes.c_void_p
        lib.mlspark_libsvm_parse.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_char_p, ctypes.c_int64,
        ]
        lib.mlspark_libsvm_copy.restype = None
        lib.mlspark_libsvm_copy.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_double),
            ctypes.c_int64,
        ]
        lib.mlspark_libsvm_free.restype = None
        lib.mlspark_libsvm_free.argtypes = [ctypes.c_void_p]
        lib.mlspark_gather_rows.restype = None
        lib.mlspark_gather_rows.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
            ctypes.c_char_p, ctypes.c_int32,
        ]
        lib.mlspark_text_vocab_create.restype = ctypes.c_int64
        lib.mlspark_text_vocab_create.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
        ]
        lib.mlspark_text_vocab_free.restype = None
        lib.mlspark_text_vocab_free.argtypes = [ctypes.c_int64]
        lib.mlspark_text_encode.restype = ctypes.c_int64
        lib.mlspark_text_encode.argtypes = [
            ctypes.c_int64, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
        ]
        _lib = lib
        return lib


def available() -> bool:
    """True when the native library builds/loads on this host."""
    try:
        _load()
        return True
    except ImportError:
        return False


def status() -> str:
    """How this process got its native code: ``"built"`` (compiled here),
    ``"loaded"`` (a library for these exact sources already existed) or
    ``"python-fallback"`` (build or load failed; callers use the Python
    paths). Loads on first call."""
    available()
    return _status


class libsvm_native:
    """Namespace matching the ``data.libsvm`` dispatch hook."""

    @staticmethod
    def parse_text(text: bytes | str) -> tuple[np.ndarray, np.ndarray]:
        lib = _load()
        if isinstance(text, str):
            text = text.encode()
        n_rows = ctypes.c_int64()
        n_features = ctypes.c_int64()
        err = ctypes.create_string_buffer(256)
        handle = lib.mlspark_libsvm_parse(
            text, len(text),
            ctypes.byref(n_rows), ctypes.byref(n_features),
            err, len(err),
        )
        if not handle:
            raise ValueError(err.value.decode() or "libsvm parse failed")
        try:
            features = np.zeros(
                (n_rows.value, n_features.value), dtype=np.float32
            )
            labels = np.zeros(n_rows.value, dtype=np.float64)
            lib.mlspark_libsvm_copy(
                handle,
                features.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                labels.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                n_features.value,
            )
        finally:
            lib.mlspark_libsvm_free(handle)
        return features, labels

    @staticmethod
    def parse_file(path: str) -> tuple[np.ndarray, np.ndarray]:
        with open(path, "rb") as f:
            return libsvm_native.parse_text(f.read())


def gather_rows(
    src: np.ndarray, indices: np.ndarray, *, n_threads: int | None = None
) -> np.ndarray:
    """``src[indices]`` for row-major arrays via threaded native memcpy.

    Falls back to numpy fancy indexing when the native library is not
    available or the layout is not contiguous.
    """
    if not np.issubdtype(np.asarray(indices).dtype, np.integer):
        raise IndexError(
            f"gather_rows needs integer indices, got {np.asarray(indices).dtype}"
        )
    indices = np.ascontiguousarray(indices, dtype=np.int64)
    # Object arrays hold PyObject* — memcpy'ing them would skip refcounting
    # and corrupt the interpreter; strided layouts can't be row-memcpy'd.
    if not (src.flags["C_CONTIGUOUS"] and src.ndim >= 1) or src.dtype.hasobject:
        return src[indices]
    if _build_error is not None:
        # Memoized build failure: skip the lock + raise/catch round trip on
        # this per-batch hot path.
        return src[indices]
    if indices.size and (
        indices.min() < -len(src) or indices.max() >= len(src)
    ):
        raise IndexError(
            f"gather index out of range for {len(src)} rows"
        )
    if indices.size and indices.min() < 0:
        indices = np.where(indices < 0, indices + len(src), indices)
    try:
        lib = _load()
    except ImportError:
        return src[indices]
    if n_threads is None:
        n_threads = min(os.cpu_count() or 1, 8)
    out = np.empty((len(indices),) + src.shape[1:], dtype=src.dtype)
    row_bytes = src.dtype.itemsize * int(np.prod(src.shape[1:], dtype=np.int64))
    lib.mlspark_gather_rows(
        src.ctypes.data_as(ctypes.c_char_p),
        row_bytes,
        indices.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(indices),
        out.ctypes.data_as(ctypes.c_char_p),
        n_threads,
    )
    return out


class text_native:
    """C++ batch text encoding (``text_encode.cpp``): tokenize + vocab
    lookup + sos/truncate/eos/pad in one native pass. ASCII-only by
    contract — callers (``data.text.TextPipeline``) route non-ASCII batches
    to the Python path, whose Unicode regex semantics the byte scanner
    cannot reproduce."""

    MODES = {"basic_english": 0, "word_punct": 1}

    @staticmethod
    def vocab_handle(itos: list[str]) -> int:
        """Register an index-ordered token list; returns a handle for
        ``encode``. The handle is process-local (rebuild after fork)."""
        lib = _load()
        blob = "\n".join(itos).encode("utf-8")
        return int(lib.mlspark_text_vocab_create(blob, len(blob)))

    @staticmethod
    def vocab_free(handle: int) -> None:
        try:
            _load().mlspark_text_vocab_free(handle)
        except ImportError:
            pass

    @staticmethod
    def encode(
        handle: int,
        texts: list[str],
        *,
        mode: int,
        max_seq_len: int,
        fixed_len: int,
        add_sos: bool,
        add_eos: bool,
        sos_id: int,
        eos_id: int,
        pad_id: int,
        default_index: int,
    ) -> np.ndarray:
        lib = _load()
        buf = "".join(texts).encode("ascii")
        offsets = np.zeros(len(texts) + 1, dtype=np.int64)
        np.cumsum([len(t) for t in texts], out=offsets[1:])
        out = np.empty((len(texts), fixed_len), dtype=np.int32)
        rc = lib.mlspark_text_encode(
            handle, buf,
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(texts), mode, max_seq_len, fixed_len,
            int(add_sos), int(add_eos), sos_id, eos_id, pad_id,
            default_index,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        )
        if rc != 0:
            raise RuntimeError(f"mlspark_text_encode failed (rc={rc})")
        return out


__all__ = ["available", "libsvm_native", "gather_rows", "text_native"]
