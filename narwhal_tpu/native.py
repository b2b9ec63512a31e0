"""ctypes loader for the native (C++) runtime components.

Builds native/*.cpp into shared libraries on first use and exposes thin
wrappers. A library is keyed by the CONTENT of its source and build flags
(`lib<name>.<sha256 prefix>.so`, git-ignored): a stale library copied
along with the tree, or one whose mtime a copy did not keep, can never be
what loads — only a library built from the `.cpp` beside it. Loading is
best-effort: when the toolchain or library is unavailable the callers fall
back to the pure-Python implementations, so the framework never
hard-depends on a compiler at runtime (`chip_smoke.py` does: it fails
rather than run the fallbacks). Disable explicitly with NARWHAL_NATIVE=0.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess

logger = logging.getLogger("narwhal.native")

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_ROOT, "native", "storage_engine.cpp")
_SCALAR_SRC = os.path.join(_ROOT, "native", "scalar_ops.cpp")
_CXX = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17"]

_lib: ctypes.CDLL | None = None
_tried = False
_scalar: ctypes.CDLL | None = None
_scalar_tried = False


def _build_lib(src: str, stem: str, extra: list[str]) -> str | None:
    """Path of the library built from `src` as it is now, building it if
    no library with this content key exists; None when the build fails."""
    try:
        with open(src, "rb") as f:
            key = hashlib.sha256(f.read() + " ".join(_CXX + extra).encode())
        base = os.path.join(os.path.dirname(src), f"lib{stem}")
        lib = f"{base}.{key.hexdigest()[:16]}.so"
        if os.path.exists(lib):
            return lib
        # Build beside the target and rename into place: concurrent node
        # processes (LocalBench boots a fleet at once) each see either no
        # library or a whole one.
        tmp = f"{lib}.{os.getpid()}.tmp"
        subprocess.run(
            [*_CXX, "-o", tmp, src, *extra], check=True, capture_output=True
        )
        os.replace(tmp, lib)
        for old in glob.glob(f"{base}.*so"):  # also matches lib<stem>.so
            if old != lib:
                os.unlink(old)  # libraries of earlier source revisions
        return lib
    except (OSError, subprocess.CalledProcessError) as e:
        logger.warning("native build of %s failed: %s", os.path.basename(src), e)
        return None


def load() -> ctypes.CDLL | None:
    """The shared library, built on demand; None if unavailable."""
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    if os.environ.get("NARWHAL_NATIVE", "1") == "0":
        return None
    path = _build_lib(_SRC, "narwhal_storage", ["-lz"])
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(path)
    except OSError as e:
        logger.warning("native storage engine load failed: %s", e)
        return None
    lib.nse_open.restype = ctypes.c_void_p
    lib.nse_open.argtypes = [ctypes.c_char_p]
    lib.nse_write_batch.restype = ctypes.c_int
    lib.nse_write_batch.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32]
    lib.nse_get.restype = ctypes.c_int
    lib.nse_get.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_char_p,
        ctypes.c_uint32,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_ubyte)),
        ctypes.POINTER(ctypes.c_uint32),
    ]
    lib.nse_contains.restype = ctypes.c_int
    lib.nse_contains.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_char_p,
        ctypes.c_uint32,
    ]
    lib.nse_len.restype = ctypes.c_uint64
    lib.nse_len.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.nse_dump.restype = None
    lib.nse_dump.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_ubyte)),
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.nse_compact.restype = None
    lib.nse_compact.argtypes = [ctypes.c_void_p]
    lib.nse_close_log.restype = None
    lib.nse_close_log.argtypes = [ctypes.c_void_p]
    lib.nse_close.restype = None
    lib.nse_close.argtypes = [ctypes.c_void_p]
    _lib = lib
    return _lib


def load_scalar() -> ctypes.CDLL | None:
    """The ed25519 host scalar pipeline (native/scalar_ops.cpp), built on
    demand; None when the toolchain is unavailable or NARWHAL_NATIVE=0.
    Entry points: `ed25519_precheck_k` (canonicality + challenge scalars),
    `scalar_fold` and `scalar_mulmod` (the msm lanes' scalars mod L),
    `msm_epilogue_native` (the host half of a dispatch's batch check: the
    Horner walk over the device's window sums and the identity test), and
    the self-test hooks `sha512_test`, `reduce_mod_l_test`, `fe_test`,
    `pt_test`, `fe_loose13_test`. ctypes releases the GIL for the call's
    duration: none of them needs the interpreter, so whichever thread calls
    one leaves the event loop running. The handle is cached on first load:
    a later NARWHAL_NATIVE=0 in the environment does not take it back."""
    global _scalar, _scalar_tried
    if _scalar_tried:
        return _scalar
    _scalar_tried = True
    if os.environ.get("NARWHAL_NATIVE", "1") == "0":
        return None
    path = _build_lib(_SCALAR_SRC, "narwhal_scalar", [])
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(path)
    except OSError as e:
        logger.warning("native scalar pipeline load failed: %s", e)
        return None
    lib.ed25519_precheck_k.restype = ctypes.c_int
    lib.ed25519_precheck_k.argtypes = [
        ctypes.c_int64,
        ctypes.c_char_p,  # pk rows
        ctypes.c_char_p,  # sig rows
        ctypes.c_char_p,  # msg buffer
        ctypes.c_void_p,  # int64 offsets
        ctypes.c_void_p,  # out k rows
        ctypes.c_void_p,  # out ok bytes
    ]
    lib.scalar_fold.restype = None
    lib.scalar_fold.argtypes = [
        ctypes.c_int64,
        ctypes.c_void_p,  # k rows
        ctypes.c_void_p,  # s rows
        ctypes.c_char_p,  # z rows
        ctypes.c_void_p,  # out ak rows
        ctypes.c_void_p,  # out sum
    ]
    lib.scalar_mulmod.restype = None
    lib.scalar_mulmod.argtypes = [
        ctypes.c_int64,
        ctypes.c_void_p,  # a rows (32B)
        ctypes.c_void_p,  # b rows (32B)
        ctypes.c_void_p,  # out rows (32B)
    ]
    lib.msm_epilogue_native.restype = ctypes.c_int
    lib.msm_epilogue_native.argtypes = [
        ctypes.c_void_p,  # V_a int32[4, 20, 64]
        ctypes.c_void_p,  # V_r int32[4, 20, wr]
        ctypes.c_int64,  # wr
        ctypes.c_char_p,  # sum_s, 32 bytes LE
    ]
    lib.fe_test.restype = None
    lib.fe_test.argtypes = [ctypes.c_int64, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p]
    lib.pt_test.restype = None
    lib.pt_test.argtypes = [ctypes.c_int64, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p]
    lib.fe_loose13_test.restype = None
    lib.fe_loose13_test.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    _scalar = lib
    return _scalar


class NativeEngine:
    """Handle on one C++ engine instance (tables + WAL)."""

    def __init__(self, path: str | None):
        lib = load()
        if lib is None:
            raise RuntimeError("native storage engine unavailable")
        self._lib = lib
        self._h = lib.nse_open((path or "").encode())
        if not self._h:
            raise RuntimeError(f"nse_open failed for {path!r}")

    def write_batch(self, body: bytes) -> None:
        if self._lib.nse_write_batch(self._h, body, len(body)) != 0:
            raise RuntimeError("malformed write batch")

    def get(self, cf: bytes, key: bytes) -> bytes | None:
        val = ctypes.POINTER(ctypes.c_ubyte)()
        vlen = ctypes.c_uint32()
        hit = self._lib.nse_get(
            self._h, cf, key, len(key), ctypes.byref(val), ctypes.byref(vlen)
        )
        if not hit:
            return None
        return ctypes.string_at(val, vlen.value)

    def contains(self, cf: bytes, key: bytes) -> bool:
        return bool(self._lib.nse_contains(self._h, cf, key, len(key)))

    def len(self, cf: bytes) -> int:
        return int(self._lib.nse_len(self._h, cf))

    def items(self, cf: bytes) -> list[tuple[bytes, bytes]]:
        buf = ctypes.POINTER(ctypes.c_ubyte)()
        blen = ctypes.c_uint64()
        self._lib.nse_dump(self._h, cf, ctypes.byref(buf), ctypes.byref(blen))
        raw = ctypes.string_at(buf, blen.value) if blen.value else b""
        out = []
        pos = 0
        while pos < len(raw):
            klen = int.from_bytes(raw[pos : pos + 4], "little")
            pos += 4
            key = raw[pos : pos + klen]
            pos += klen
            vlen = int.from_bytes(raw[pos : pos + 4], "little")
            pos += 4
            out.append((key, raw[pos : pos + vlen]))
            pos += vlen
        return out

    def compact(self) -> None:
        self._lib.nse_compact(self._h)

    def close(self) -> None:
        """Stop appends; tables stay readable (Python-engine close parity —
        late reads during shutdown must not hit a freed handle)."""
        if self._h:
            self._lib.nse_close_log(self._h)

    def __del__(self) -> None:
        h, self._h = getattr(self, "_h", None), None
        if h:
            try:
                self._lib.nse_close(h)
            except Exception:
                pass
