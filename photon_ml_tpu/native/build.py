"""On-demand compilation + loading of the native library.

Builds `libphoton_native.so` from the C++ sources in this directory with the
system `g++` the first time it is needed and caches the result next to the
sources (keyed by a content hash, so edits trigger a rebuild). Returns None
when the build fails — callers fall back to the pure-Python implementations
of the same on-disk formats, which are a different program (ingest an order
of magnitude slower), so the compiler's error is kept (`build_error()`) and
logged once instead of being swallowed.

Setting PHOTON_DISABLE_NATIVE=1 disables the native library for EVERY
component (index store, LibSVM parser, ...) — one global kill switch, not
per-component surprises. `load_native()` is the one shared ctypes loader;
each binding module declares its own symbol signatures on the returned CDLL.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

from photon_ml_tpu.utils.knobs import get_knob

_DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCES = [
    "index_store.cc",
    "libsvm_parser.cc",
    "bucketed_pack.cc",
    "avro_reader.cc",
    "avro_writer.cc",
]
_LOCK = threading.RLock()  # reentrant: load_native holds it across
# native_library_path so concurrent first calls cannot race past a
# half-initialized handle
_CACHED: Optional[str] = None
_ATTEMPTED = False
_CDLL: Optional[ctypes.CDLL] = None
_CDLL_TRIED = False
_BUILD_ERROR: Optional[str] = None

_DISABLE_ENV = "PHOTON_DISABLE_NATIVE"


def _source_hash() -> str:
    h = hashlib.sha256()
    for name in _SOURCES:
        with open(os.path.join(_DIR, name), "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def _zlib_failure(stderr: bytes) -> bool:
    """Did the compile/link fail because zlib is absent on this host?"""
    s = stderr.decode("utf-8", "replace")
    return "-lz" in s or "zlib.h" in s


def build_error() -> Optional[str]:
    """Why the last build attempt of this process failed (the compiler's
    stderr, or the OS error), or None when it succeeded / never ran."""
    return _BUILD_ERROR


def _build_failed(reason: str) -> None:
    global _BUILD_ERROR
    _BUILD_ERROR = reason
    logging.getLogger(__name__).warning(
        "native library build failed; every binding now runs its "
        "pure-Python fallback (ingest is an order of magnitude slower): %s",
        reason,
    )


def native_library_path() -> Optional[str]:
    """Path to the compiled shared library, or None if unbuildable/disabled."""
    global _CACHED, _ATTEMPTED
    if get_knob(_DISABLE_ENV):
        return None
    with _LOCK:
        if _ATTEMPTED:
            return _CACHED
        _ATTEMPTED = True
        build_dir = os.path.join(_DIR, "_build")
        so_path = os.path.join(build_dir, f"libphoton_native-{_source_hash()}.so")
        # The zlib-free degraded build caches under a DISTINCT name: a full
        # build must never be masked by a cached degraded one, and a process
        # that finds only the degraded artifact still retries the full build
        # (cheap, and self-healing once libz appears).
        nozlib_path = os.path.join(
            build_dir, f"libphoton_native-{_source_hash()}-nozlib.so"
        )
        if os.path.exists(so_path):
            _CACHED = so_path
            return _CACHED
        try:
            os.makedirs(build_dir, exist_ok=True)
            tmp = f"{so_path}.tmp.{os.getpid()}"  # per-process: concurrent
            # first-time builds must not interleave into one tmp file

            def _compile(sources: list[str], libs: list[str]):
                """None on success, else captured stderr bytes."""
                cmd = [
                    "g++",
                    "-O2",
                    "-std=c++17",
                    "-pthread",
                    "-shared",
                    "-fPIC",
                    "-o",
                    tmp,
                ] + [os.path.join(_DIR, s) for s in sources] + libs
                try:
                    subprocess.run(cmd, check=True, capture_output=True, timeout=120)
                    return None
                except subprocess.CalledProcessError as e:
                    return e.stderr or b"g++ failed without output"
                except (OSError, subprocess.SubprocessError) as e:
                    return repr(e).encode()

            err = _compile(_SOURCES, ["-lz"])
            if err is None:
                os.replace(tmp, so_path)
                _CACHED = so_path
            elif _zlib_failure(err):
                # Only avro_reader.cc needs zlib (deflate containers). On a
                # host without libz, rebuild with just the zlib-free
                # components so the index store, LibSVM parser and bucketed
                # packer survive; the Avro binding sees the missing symbol
                # and falls back to the Python codec. Any other failure
                # (transient OOM, genuine compile error) caches nothing so
                # the next process retries the full build.
                logging.getLogger(__name__).warning(
                    "zlib is absent on this host: the native library is "
                    "built without the Avro reader, and Avro ingest runs "
                    "the pure-Python codec"
                )
                if os.path.exists(nozlib_path):
                    _CACHED = nozlib_path
                else:
                    err2 = _compile(
                        [s for s in _SOURCES if s != "avro_reader.cc"], []
                    )
                    if err2 is None:
                        os.replace(tmp, nozlib_path)
                        _CACHED = nozlib_path
                    else:
                        _build_failed(err2.decode("utf-8", "replace")[-2000:])
            else:
                _build_failed(err.decode("utf-8", "replace")[-2000:])
        except OSError as e:
            _build_failed(repr(e))
        return _CACHED


def load_native() -> Optional[ctypes.CDLL]:
    """The process-wide CDLL handle (built on demand), or None.

    Binding modules call this and declare their own restype/argtypes on the
    returned object — declaring signatures is idempotent and per-symbol, so
    sharing one handle is safe.
    """
    global _CDLL, _CDLL_TRIED
    # The kill switch is honored per call, not just at first load: flipping
    # PHOTON_DISABLE_NATIVE at runtime disables an already-loaded handle, and
    # setting it for the first call does not permanently poison the cache.
    if get_knob(_DISABLE_ENV):
        return None
    with _LOCK:
        if _CDLL_TRIED:
            return _CDLL
        _CDLL_TRIED = True
        path = native_library_path()
        if path is None:
            return None
        try:
            _CDLL = ctypes.CDLL(path)
        except OSError:
            _CDLL = None
        return _CDLL
