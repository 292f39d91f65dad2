"""ctypes binding for the native content hasher (kthash.cpp).

Built from the source on first use with g++ and kept next to it, named by
the source's digest: a checkout carries no binary, and an edited source
never loads a stale one (file times do not survive a copy of the tree).
Callers fall back to hashlib when no toolchain exists (see
``sync.file_hash``).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from pathlib import Path
from typing import Optional

logger = logging.getLogger(__name__)

_DIR = Path(__file__).parent
_SRC = _DIR / "kthash.cpp"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def _ensure_lib() -> ctypes.CDLL:
    global _lib, _build_failed
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            raise RuntimeError("native hasher build previously failed")
        digest = hashlib.sha1(_SRC.read_bytes()).hexdigest()[:12]
        built = _DIR / f"libkthash-{digest}.so"
        if not built.exists():
            # other processes (pods sharing this checkout) may build at
            # the same moment: each writes its own file, then renames
            tmp = built.with_suffix(f".{os.getpid()}.tmp")
            try:
                # ktlint: disable=KT008 -- build-once barrier: the lock exists precisely so every contender waits for the one g++ build; nothing can use the lib before it exists
                subprocess.run(
                    ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                     str(_SRC), "-o", str(tmp)],
                    check=True, capture_output=True, timeout=120)
                os.replace(tmp, built)
            except (subprocess.SubprocessError, OSError) as exc:
                _build_failed = True
                tmp.unlink(missing_ok=True)
                logger.warning("native hasher build failed (%s); callers "
                               "hash with hashlib instead", exc)
                raise RuntimeError(f"native hasher build failed: {exc}")
        lib = ctypes.CDLL(str(built))
        lib.kt_hash_file.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                     ctypes.c_int]
        lib.kt_hash_file.restype = ctypes.c_int
        lib.kt_hash_buf.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                    ctypes.c_char_p, ctypes.c_int]
        lib.kt_hash_buf.restype = None
        _lib = lib
        return lib


def hash_file(path: str) -> str:
    lib = _ensure_lib()
    out = ctypes.create_string_buffer(17)
    rc = lib.kt_hash_file(path.encode(), out, 17)
    if rc != 0:
        raise OSError(f"kt_hash_file({path!r}) failed with {rc}")
    return out.value.decode()


def hash_bytes(data: bytes) -> str:
    lib = _ensure_lib()
    out = ctypes.create_string_buffer(17)
    lib.kt_hash_buf(data, len(data), out, 17)
    return out.value.decode()
