"""ctypes binding for the native arena store (native/arena_store.cpp).

The .so builds on first use with the in-image g++ (no pybind11 — plain
C ABI). Staleness is decided by a content hash of the source kept next to
the .so, never by mtime: a copy or a fresh checkout does not preserve
mtimes, and `native/build/` is git-ignored, so the library is always
built from what git would commit. `load()` returns None when the build
or the dlopen fails and the store falls back to the file-per-object
backend; `load_error()` says why, so a caller that must not run on the
slow path (chip_smoke.py) can refuse to.
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import os
import subprocess
import threading
from typing import List, Optional, Tuple

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "build", "libarena_store.so")
_STAMP_PATH = _SO_PATH + ".src-sha256"
_BUILD_LOCK = threading.Lock()
_LIB = None
_LOAD_ERROR: Optional[str] = None


def _configure(lib) -> None:
    u64 = ctypes.c_uint64
    lib.rtpu_store_open.restype = ctypes.c_void_p
    lib.rtpu_store_open.argtypes = [ctypes.c_char_p, u64]
    lib.rtpu_store_close.argtypes = [ctypes.c_void_p]
    lib.rtpu_store_create.restype = u64
    lib.rtpu_store_create.argtypes = [ctypes.c_void_p, ctypes.c_char_p, u64]
    lib.rtpu_store_seal.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.rtpu_store_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.POINTER(u64), ctypes.POINTER(u64)]
    lib.rtpu_store_contains.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.rtpu_store_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.rtpu_store_pin.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_int]
    lib.rtpu_store_addref.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_int]
    lib.rtpu_store_evict.argtypes = [ctypes.c_void_p, u64, ctypes.c_char_p,
                                     u64]
    lib.rtpu_store_lru_pinned.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, u64,
        ctypes.POINTER(u64), ctypes.POINTER(u64)]
    lib.rtpu_store_entry_flags.restype = None
    lib.rtpu_store_entry_flags.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                           ctypes.POINTER(ctypes.c_uint64)]
    lib.rtpu_store_stats.argtypes = [ctypes.c_void_p, u64 * 4]


def _source_digest() -> str:
    with open(os.path.join(_NATIVE_DIR, "arena_store.cpp"), "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _build_if_stale() -> None:
    digest = _source_digest()
    os.makedirs(os.path.dirname(_SO_PATH), exist_ok=True)
    # Raylet and workers may all get here at once: one builds, the
    # others wait on the lock and then find the stamp current.
    with open(_SO_PATH + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        try:
            with open(_STAMP_PATH) as f:
                current = f.read().strip() == digest
        except OSError:
            current = False
        if current and os.path.exists(_SO_PATH):
            return
        subprocess.run(["make", "-B", "-C", _NATIVE_DIR],
                       check=True, capture_output=True, timeout=120)
        with open(_STAMP_PATH, "w") as f:
            f.write(digest)


def load():
    """Build (once) + dlopen the arena store; None if unavailable (see
    `load_error()` for the reason)."""
    global _LIB, _LOAD_ERROR
    if _LIB is not None or _LOAD_ERROR is not None:
        return _LIB
    with _BUILD_LOCK:
        if _LIB is not None or _LOAD_ERROR is not None:
            return _LIB
        try:
            _build_if_stale()
            lib = ctypes.CDLL(_SO_PATH)
            _configure(lib)
            _LIB = lib
        except (OSError, subprocess.SubprocessError, AttributeError) as e:
            detail = getattr(e, "stderr", None)
            if isinstance(detail, bytes):
                detail = detail.decode(errors="replace")[-2000:]
            _LOAD_ERROR = f"{type(e).__name__}: {e}" + (
                f"\n{detail}" if detail else "")
    return _LIB


def load_error() -> Optional[str]:
    """Why the last `load()` returned None (None if it has not failed)."""
    return _LOAD_ERROR


_UINT64_MAX = 2 ** 64 - 1


class ArenaStore:
    """Thin OO wrapper over the C handle (ids are hex strings)."""

    def __init__(self, arena_path: str, capacity: int):
        self._lib = load()
        if self._lib is None:
            raise RuntimeError("native arena store unavailable")
        self._h = self._lib.rtpu_store_open(arena_path.encode(), capacity)
        if not self._h:
            raise RuntimeError(f"could not open arena at {arena_path}")
        self.path = arena_path
        self.capacity = capacity

    def create(self, oid: bytes, size: int) -> Optional[int]:
        off = self._lib.rtpu_store_create(self._h, oid.hex().encode(), size)
        return None if off == _UINT64_MAX else off

    def seal(self, oid: bytes) -> bool:
        return self._lib.rtpu_store_seal(self._h, oid.hex().encode()) == 0

    def get(self, oid: bytes) -> Optional[Tuple[int, int]]:
        """(offset, size) of a sealed object, else None."""
        off = ctypes.c_uint64()
        size = ctypes.c_uint64()
        rc = self._lib.rtpu_store_get(self._h, oid.hex().encode(),
                                      ctypes.byref(off), ctypes.byref(size))
        return (off.value, size.value) if rc == 0 else None

    def contains(self, oid: bytes) -> bool:
        return bool(self._lib.rtpu_store_contains(self._h,
                                                  oid.hex().encode()))

    def delete(self, oid: bytes) -> bool:
        return self._lib.rtpu_store_delete(self._h, oid.hex().encode()) == 0

    def addref(self, oid: bytes, delta: int) -> int:
        return self._lib.rtpu_store_addref(self._h, oid.hex().encode(),
                                           delta)

    def pin(self, oid: bytes, pinned: bool) -> None:
        self._lib.rtpu_store_pin(self._h, oid.hex().encode(),
                                 1 if pinned else 0)

    def evict_for(self, needed: int) -> List[bytes]:
        buf = ctypes.create_string_buffer(64 * 1024)
        n = self._lib.rtpu_store_evict(self._h, needed, buf, len(buf))
        out: List[bytes] = []
        raw = buf.raw
        pos = 0
        for _ in range(n):
            end = raw.index(b"\0", pos)
            if end == pos:
                break
            out.append(bytes.fromhex(raw[pos:end].decode()))
            pos = end + 1
        return out

    def lru_pinned(self) -> Optional[Tuple[bytes, int, int]]:
        buf = ctypes.create_string_buffer(128)
        off = ctypes.c_uint64()
        size = ctypes.c_uint64()
        rc = self._lib.rtpu_store_lru_pinned(
            self._h, buf, len(buf), ctypes.byref(off), ctypes.byref(size))
        if rc != 0:
            return None
        return bytes.fromhex(buf.value.decode()), off.value, size.value

    def entry_flags(self, oid: bytes) -> Tuple[int, int, int, int]:
        """(found, sealed, pinned, refs) — debug/diagnostic surface."""
        out = (ctypes.c_uint64 * 4)()
        self._lib.rtpu_store_entry_flags(self._h, oid.hex().encode(), out)
        return tuple(out)

    def stats(self) -> Tuple[int, int, int, int]:
        out = (ctypes.c_uint64 * 4)()
        self._lib.rtpu_store_stats(self._h, out)
        return tuple(out)

    def close(self) -> None:
        if self._h:
            self._lib.rtpu_store_close(self._h)
            self._h = None
