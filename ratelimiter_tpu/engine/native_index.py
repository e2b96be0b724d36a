"""ctypes binding for the native slot index (native/slot_index.cpp).

Same interface as the pure-Python ``SlotIndex`` (engine/slots.py) plus
vectorized batch assignment, which is what makes the host keep up with the
device: one C call maps a whole micro-batch of keys to slots.

The shared library is built on demand with the repo Makefile (g++ is in the
image; pybind11 is not, hence the C ABI + ctypes).  A library on disk is
used only when its build key — a content hash of the source, the Makefile,
the build variables and this host's CPU (the default build is
``-march=native``) — matches; otherwise it is rebuilt.  If compilation is
impossible the caller falls back to the Python index — behavior is
identical, only slower (tested equivalent in tests/test_native_index.py).
"""

from __future__ import annotations

import contextlib
import ctypes
import fcntl
import hashlib
import os
import subprocess
import threading
from typing import Hashable, Optional, Set, Tuple

import numpy as np

from ratelimiter_tpu.engine.errors import SlotCapacityError

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native")
_LIB_PATH = os.path.abspath(os.path.join(_NATIVE_DIR, "libslotindex.so"))
_build_lock = threading.Lock()
_lib = None
_lib_failed = False


def _cpu_signature() -> bytes:
    try:
        with open("/proc/cpuinfo", "rb") as fh:
            return b"".join(line for line in fh
                            if line.startswith((b"model name", b"flags")))
    except OSError:
        import platform

        return platform.processor().encode()


def build_key(source: str) -> str:
    """Content hash a library must have been built from: its source, the
    Makefile, the build variables and the CPU the default
    ``-march=native`` build targets."""
    native = os.path.abspath(_NATIVE_DIR)
    h = hashlib.sha256()
    for name in (source, "Makefile"):
        with open(os.path.join(native, name), "rb") as fh:
            h.update(fh.read())
    for var in ("CXX", "ARCH", "CXXFLAGS"):
        h.update(f"{var}={os.environ.get(var, '')}".encode())
    h.update(_cpu_signature())
    return h.hexdigest()


def _ensure_built(lib_path: str, source: str) -> None:
    """Rebuild ``lib_path`` unless its stamp records the current build
    key.  A file lock serializes concurrent builders (test workers); the
    Makefile renames the finished library into place."""
    native = os.path.abspath(_NATIVE_DIR)
    stamp = lib_path + ".buildkey"
    key = build_key(source)

    def current() -> bool:
        try:
            with open(stamp, encoding="ascii") as fh:
                return os.path.exists(lib_path) and fh.read() == key
        except OSError:
            return False

    if current():
        return
    with open(os.path.join(native, ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if current():
            return
        subprocess.run(["make", "-B", "-C", native,
                        os.path.basename(lib_path)],
                       check=True, capture_output=True, timeout=120)
        with open(stamp + ".tmp", "w", encoding="ascii") as fh:
            fh.write(key)
        os.replace(stamp + ".tmp", stamp)


def _load_library():
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    with _build_lock:
        if _lib is not None or _lib_failed:
            return _lib
        try:
            try:
                _ensure_built(_LIB_PATH, "slot_index.cpp")
            except Exception as exc:  # noqa: BLE001
                # A deployment with a prebuilt .so but no toolchain (the
                # Dockerfile's runtime stage) loads what it was shipped,
                # with a signal that it was not checked against the source.
                if not os.path.exists(_LIB_PATH):
                    raise
                import warnings

                warnings.warn(
                    f"native slot index build failed ({exc!r}); loading "
                    f"{_LIB_PATH}, which was not checked against the "
                    "source — rebuild with `make -C native`",
                    RuntimeWarning, stacklevel=2)
            lib = ctypes.CDLL(_LIB_PATH)
            _bind(lib)  # missing symbol (stale prebuilt .so) => fallback
        except Exception:  # noqa: BLE001 — any failure => Python fallback
            _lib_failed = True
            return None
        _lib = lib
        return _lib


def _bind(lib) -> None:
    """Declare the C ABI; raises AttributeError on a library that predates
    any entry point (caller maps that to the Python-index fallback)."""
    lib.rl_index_new.restype = ctypes.c_void_p
    lib.rl_index_new.argtypes = [ctypes.c_int64]
    lib.rl_index_free.argtypes = [ctypes.c_void_p]
    lib.rl_index_len.restype = ctypes.c_int64
    lib.rl_index_len.argtypes = [ctypes.c_void_p]
    lib.rl_index_assign_ints.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_uint64, ctypes.c_void_p, ctypes.c_void_p]
    lib.rl_index_assign_ints_multi.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p]
    lib.rl_index_assign_bytes.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_uint64, ctypes.c_void_p, ctypes.c_void_p]
    lib.rl_index_assign_ints_uniques.restype = ctypes.c_int64
    lib.rl_index_assign_ints_uniques.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_uint64, ctypes.c_int32, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
    lib.rl_index_assign_ints_multi_uniques.restype = ctypes.c_int64
    lib.rl_index_assign_ints_multi_uniques.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int64, ctypes.c_int32, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
    lib.rl_index_assign_bytes_uniques.restype = ctypes.c_int64
    lib.rl_index_assign_bytes_uniques.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int64, ctypes.c_uint64, ctypes.c_int32, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
    lib.rl_index_get_bytes.restype = ctypes.c_int32
    lib.rl_index_get_bytes.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_uint64]
    lib.rl_index_get_int.restype = ctypes.c_int32
    lib.rl_index_get_int.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_uint64]
    lib.rl_index_remove_bytes.restype = ctypes.c_int32
    lib.rl_index_remove_bytes.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_uint64]
    lib.rl_index_remove_int.restype = ctypes.c_int32
    lib.rl_index_remove_int.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_uint64]
    lib.rl_index_pin.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.rl_index_unpin.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.rl_index_pin_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]
    lib.rl_index_unpin_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]
    lib.rl_index_dump.restype = ctypes.c_int64
    lib.rl_index_dump.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
    lib.rl_index_restore.restype = ctypes.c_int32
    lib.rl_index_restore.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int64]
    lib.rl_index_lookup_fps.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_void_p]
    lib.rl_index_assign_fps.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_void_p]
    lib.rl_relay_decide.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int64, ctypes.c_void_p]
    lib.rl_shard_route.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p]
    lib.rl_route_ranges.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32, ctypes.c_void_p,
        ctypes.c_int32, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
    lib.rl_merge_ranges.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, ctypes.c_void_p, ctypes.c_int32,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p]
    lib.rl_sort_uniques.restype = ctypes.c_int32
    lib.rl_sort_uniques.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32, ctypes.c_void_p,
        ctypes.c_int64]
    lib.rl_rebuild_words.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_int32, ctypes.c_void_p]
    lib.rl_weighted_layout.restype = ctypes.c_int32
    lib.rl_weighted_layout.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
    lib.rl_weighted_decide.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p]
    # Optional (r6): the fingerprint string fast path + hash routing.
    # Stale prebuilt .so => callers fall back to the packed-bytes path.
    try:
        lib.rl_index_assign_fps_uniques.restype = ctypes.c_int64
        lib.rl_index_assign_fps_uniques.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int32, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
        lib.rl_hash_bytes_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_uint64, ctypes.c_void_p, ctypes.c_void_p]
        lib.rl_route_hashes.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
        lib.rl_shard_route2.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p]
        lib.rl_route_hashes2.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
        lib.rl_relay_decide_pos.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p]
    except AttributeError:
        pass


def native_available() -> bool:
    return _load_library() is not None


_STRPACK_PATH = os.path.abspath(os.path.join(_NATIVE_DIR, "libstrpack.so"))
_strpack = None
_strpack_failed = False


def _load_strpack():
    """Optional CPython-API string packer (native/str_pack.cpp): one C
    pass over the key list instead of join + encode + separator scan.
    Needs Python headers + shared libpython to build; any failure means
    the numpy packer below is used — behavior identical."""
    global _strpack, _strpack_failed
    if _strpack is not None or _strpack_failed:
        return _strpack
    with _build_lock:
        if _strpack is not None or _strpack_failed:
            return _strpack
        try:
            _ensure_built(_STRPACK_PATH, "str_pack.cpp")
            # PyDLL, not CDLL: these functions touch Python objects, so
            # the GIL must stay held across the call.
            lib = ctypes.PyDLL(_STRPACK_PATH)
            lib.rl_strlist_total.restype = ctypes.c_int64
            lib.rl_strlist_total.argtypes = [ctypes.py_object]
            # _pack2: arity changed with the bounds re-checks; binding by
            # a new name makes a stale prebuilt .so raise AttributeError
            # here (=> numpy fallback) instead of silently dropping them.
            lib.rl_strlist_pack2.restype = ctypes.c_int32
            lib.rl_strlist_pack2.argtypes = [
                ctypes.py_object, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_int64, ctypes.c_int64]
            # Optional (r6): windowed fingerprint hashing — a stale
            # prebuilt libstrpack without it must not lose pack2.
            try:
                lib.rl_strlist_hash_fp.restype = ctypes.c_int32
                lib.rl_strlist_hash_fp.argtypes = [
                    ctypes.py_object, ctypes.c_int64, ctypes.c_int64,
                    ctypes.c_uint64, ctypes.c_void_p, ctypes.c_void_p]
            except AttributeError:
                pass
        except Exception:  # noqa: BLE001 — optional fast path only
            _strpack_failed = True
            return None
        _strpack = lib
        return _strpack


def _pack_str_keys(keys):
    """(packed bytes u8[:], offsets i64[n+1]) for a batch of string keys.

    Fast path: one ``"\\x00".join().encode()`` pass (C speed) plus a
    vectorized separator scan and one masked compaction — no per-key
    Python encode loop.  Falls back to the per-key path when a key embeds
    NUL or isn't a str.  Byte-identical packing either way (the hashes
    must match every other entry path's)."""
    n = len(keys)
    if n == 0:
        return np.empty(0, dtype=np.uint8), np.zeros(1, dtype=np.int64)
    sp = _load_strpack() if isinstance(keys, list) else None
    if sp is not None:
        total = sp.rl_strlist_total(keys)
        if total >= 0:
            buf = np.empty(total, dtype=np.uint8)
            offs = np.empty(n + 1, dtype=np.int64)
            # n/total re-checked inside: the list could have been mutated
            # between the sizing pass and here (bounds, not a data race
            # guarantee — concurrent mutation still yields garbage keys,
            # just never a heap overflow).
            if sp.rl_strlist_pack2(keys, buf.ctypes.data,
                                   offs.ctypes.data, n, total) == 0:
                return buf, offs
    try:
        joined = "\x00".join(keys).encode()
    except TypeError:
        joined = None
    if joined is not None:
        buf = np.frombuffer(joined, dtype=np.uint8)
        seps = np.flatnonzero(buf == 0)
        if len(seps) == n - 1:  # no embedded NULs
            bounds = np.empty(n + 1, dtype=np.int64)
            bounds[0] = -1
            bounds[1:n] = seps
            bounds[n] = len(buf)
            lens = np.diff(bounds) - 1
            offs = np.empty(n + 1, dtype=np.int64)
            offs[0] = 0
            np.cumsum(lens, out=offs[1:])
            if n == 1:
                return buf, offs
            mask = np.ones(len(buf), dtype=bool)
            mask[seps] = False
            return buf[mask], offs
    encoded = [k.encode() if isinstance(k, str) else bytes(k) for k in keys]
    packed = np.frombuffer(b"".join(encoded), dtype=np.uint8)
    lens = np.fromiter((len(b) for b in encoded), dtype=np.int64,
                       count=n)
    offs = np.empty(n + 1, dtype=np.int64)
    offs[0] = 0
    np.cumsum(lens, out=offs[1:])
    return packed, offs


_FNV_OFF1 = 0xcbf29ce484222325
_FNV_PRIME = 0x100000001b3
_U64 = (1 << 64) - 1


def fnv_fingerprint_h1(data: bytes, seed: int) -> int:
    """Python mirror of the h1 stream of native/slot_index.cpp:
    hash_bytes — the fingerprint the string shard router keys on.  Used
    by scalar paths (parallel/sharded.py:shard_of_key) so scalar and
    batched string traffic always agree on a key's shard; parity with
    the C implementation is pinned by tests/test_native_index.py."""
    h = (_FNV_OFF1 ^ (seed & _U64)) & _U64
    for b in data:
        h = ((h ^ b) * _FNV_PRIME) & _U64
    return h


# Per-thread fingerprint scratch: the hash arrays are consumed within
# the same call that fills them (assign / route), so one grow-only pair
# per thread removes the 16 B/key allocation from every stream chunk.
_fp_tls = threading.local()


def _fp_scratch(n: int):
    h1 = getattr(_fp_tls, "h1", None)
    if h1 is None or len(h1) < n:
        _fp_tls.h1 = h1 = np.empty(max(n, 1024), dtype=np.uint64)
        _fp_tls.h2 = np.empty(max(n, 1024), dtype=np.uint64)
    return h1, _fp_tls.h2


def str_hash_available() -> bool:
    """Whether hash_str_keys has a native producer (either the CPython
    hasher or packed-bytes hashing through the index library)."""
    lib = _load_library()
    if lib is None or not hasattr(lib, "rl_hash_bytes_batch"):
        return False
    return True


def hash_str_keys(keys, seed: int, start: int = 0,
                  count: int | None = None):
    """128-bit fingerprints for a window of a string-key batch, with no
    per-key Python objects: (h1 u64[n], h2 u64[n]) views into per-thread
    scratch (consume before the next call on the same thread), or None
    when no native producer exists.

    Fast path: one CPython-API pass over the list window
    (str_pack.cpp:rl_strlist_hash_fp) — hashes straight off each str's
    interned UTF-8 buffer, no join/copy/offsets.  Fallback: the numpy
    packer + rl_hash_bytes_batch (handles bytes keys and non-list
    sequences).  Both produce fingerprints bit-identical to every other
    index entry path."""
    n = (len(keys) - start) if count is None else count
    if n < 0:
        return None
    h1, h2 = _fp_scratch(n)
    sp = _load_strpack() if isinstance(keys, list) else None
    if sp is not None and hasattr(sp, "rl_strlist_hash_fp"):
        if sp.rl_strlist_hash_fp(keys, start, n, seed & _U64,
                                 h1.ctypes.data, h2.ctypes.data) == 0:
            return h1[:n], h2[:n]
    lib = _load_library()
    if lib is None or not hasattr(lib, "rl_hash_bytes_batch"):
        return None
    sub = keys[start:start + n]
    packed, offs = _pack_str_keys(
        sub if isinstance(sub, list) else list(sub))
    lib.rl_hash_bytes_batch(packed.ctypes.data if len(packed) else 0,
                            offs.ctypes.data, n, seed & _U64,
                            h1.ctypes.data, h2.ctypes.data)
    return h1[:n], h2[:n]


def shard_route_gather(key_ids: np.ndarray, n_shards: int):
    """Fused shard routing + key gather: (shard i32[n], order i64[n],
    counts i64[n_shards], keys_sorted i64[n]) in one C pass — the
    separate numpy fancy-gather of the sorted keys was a whole extra
    memory pass per chunk on 1-core hosts.  None off-native (callers
    fall back to shard_route/_route_chunk + numpy gather).

    The sharded relay stream routes every int chunk with it (an on-mesh
    route pass lost to it on a 2x2 v5e host and was deleted; PERF.md)."""
    lib = _load_library()
    if lib is None or not hasattr(lib, "rl_shard_route2"):
        return None
    key_ids = np.ascontiguousarray(key_ids, dtype=np.int64)
    n = len(key_ids)
    shard = np.empty(n, dtype=np.int32)
    order = np.empty(n, dtype=np.int64)
    counts = np.empty(n_shards, dtype=np.int64)
    kst = np.empty(n, dtype=np.int64)
    lib.rl_shard_route2(key_ids.ctypes.data, n, int(n_shards),
                        shard.ctypes.data, order.ctypes.data,
                        counts.ctypes.data, kst.ctypes.data)
    return shard, order, counts, kst


def route_hashes_gather(h1: np.ndarray, h2: np.ndarray, n_shards: int):
    """Fused fingerprint routing + gather: (shard, order, counts,
    h1_sorted, h2_sorted) in one C pass; numpy fallback bit-identical.
    The sharded relay stream's route for STRING streams (by the h1
    stream, as ``shard_of_key``'s string branch)."""
    n = len(h1)
    lib = _load_library()
    if lib is not None and hasattr(lib, "rl_route_hashes2"):
        h1 = np.ascontiguousarray(h1, dtype=np.uint64)
        h2 = np.ascontiguousarray(h2, dtype=np.uint64)
        shard = np.empty(n, dtype=np.int32)
        order = np.empty(n, dtype=np.int64)
        counts = np.empty(n_shards, dtype=np.int64)
        h1s = np.empty(n, dtype=np.uint64)
        h2s = np.empty(n, dtype=np.uint64)
        lib.rl_route_hashes2(h1.ctypes.data, h2.ctypes.data, n,
                             int(n_shards), shard.ctypes.data,
                             order.ctypes.data, counts.ctypes.data,
                             h1s.ctypes.data, h2s.ctypes.data)
        return shard, order, counts, h1s, h2s
    shard, order, counts = route_hashes(h1, n_shards)
    return shard, order, counts, h1[order], h2[order]


def relay_decide_pos(counts: np.ndarray, uidx: np.ndarray,
                     rank: np.ndarray, pos: np.ndarray,
                     out: np.ndarray) -> int:
    """Scattered relay decision reconstruction: ``out[pos[i]] = rank[i]
    < counts[uidx[i]]`` in one C pass (``out`` a C-contiguous bool
    view), returning the allowed count — fuses the dense reconstruction
    + numpy fancy-scatter the sharded drain used to pay as two memory
    passes.  Falls back to the two-pass numpy route off-native."""
    lib = _load_library()
    n = len(uidx)
    if (lib is not None and hasattr(lib, "rl_relay_decide_pos")
            and counts.dtype.itemsize <= 2 and out.flags["C_CONTIGUOUS"]
            and out.dtype == np.bool_):
        counts = np.ascontiguousarray(counts)
        uidx = np.ascontiguousarray(uidx, dtype=np.int32)
        rank = np.ascontiguousarray(rank, dtype=np.int32)
        pos = np.ascontiguousarray(pos, dtype=np.int64)
        allowed = np.empty(1, dtype=np.int64)
        lib.rl_relay_decide_pos(
            counts.ctypes.data, counts.dtype.itemsize, uidx.ctypes.data,
            rank.ctypes.data, pos.ctypes.data, n, out.ctypes.data,
            allowed.ctypes.data)
        return int(allowed[0])
    got = relay_decide(counts, uidx, rank)
    out[pos] = got
    return int(got.sum())


def route_hashes(h1: np.ndarray, n_shards: int):
    """(shard i32[n], stable order i64[n], counts i64[n_shards]) from
    precomputed fingerprints: shard = h1 % n_shards + stable counting
    sort, one C pass (numpy fallback bit-identical)."""
    n = len(h1)
    lib = _load_library()
    if lib is not None and hasattr(lib, "rl_route_hashes"):
        h1 = np.ascontiguousarray(h1, dtype=np.uint64)
        shard = np.empty(n, dtype=np.int32)
        order = np.empty(n, dtype=np.int64)
        counts = np.empty(n_shards, dtype=np.int64)
        lib.rl_route_hashes(h1.ctypes.data, n, int(n_shards),
                            shard.ctypes.data, order.ctypes.data,
                            counts.ctypes.data)
        return shard, order, counts
    shard = (h1 % np.uint64(n_shards)).astype(np.int32)
    order = np.argsort(shard, kind="stable")
    return shard, order, np.bincount(
        shard, minlength=n_shards).astype(np.int64)


def relay_decide(counts: np.ndarray, uidx: np.ndarray,
                 rank: np.ndarray) -> np.ndarray:
    """allowed[i] = rank[i] < counts[uidx[i]] — the digest-mode decision
    reconstruction, fused into one C pass (numpy fallback off-native).
    ``counts`` is the device's u8/u16 per-unique allowed counts."""
    lib = _load_library()
    if lib is None or counts.dtype.itemsize > 2:
        return rank < counts.astype(np.int32)[uidx]
    counts = np.ascontiguousarray(counts)
    uidx = np.ascontiguousarray(uidx, dtype=np.int32)
    rank = np.ascontiguousarray(rank, dtype=np.int32)
    out = np.empty(len(uidx), dtype=np.uint8)
    lib.rl_relay_decide(counts.ctypes.data, counts.dtype.itemsize,
                        uidx.ctypes.data, rank.ctypes.data, len(uidx),
                        out.ctypes.data)
    return out.view(np.bool_)


def sort_uniques(uwords: np.ndarray, rank_bits: int,
                 uidx: np.ndarray) -> bool:
    """Sort ``uwords`` by slot IN PLACE (radix on the slot field) and
    remap ``uidx`` to the new positions — the prerequisite for the
    dense presorted device scatter.  Decision reconstruction is
    order-agnostic (counts[uidx] with the remapped uidx), so callers
    can sort freely before a digest dispatch.  False when the native
    library is unavailable (callers dispatch unsorted)."""
    lib = _load_library()
    if lib is None:
        return False
    # Explicit precondition checks (NOT asserts: under `python -O` an
    # assert vanishes and a non-contiguous or wrong-dtype array would
    # hand the C sort a garbage pointer) — ADVICE r4.
    if not (uwords.flags["C_CONTIGUOUS"] and uwords.dtype == np.uint32
            and uidx.flags["C_CONTIGUOUS"] and uidx.dtype == np.int32):
        return False  # caller dispatches unsorted, decisions unchanged
    lib.rl_sort_uniques(uwords.ctypes.data, len(uwords), int(rank_bits),
                        uidx.ctypes.data, len(uidx))
    return True


def rebuild_words_into(uwords: np.ndarray, uidx: np.ndarray,
                       rank: np.ndarray, rank_bits: int,
                       out: np.ndarray) -> bool:
    """Words-mode per-request reconstruction straight into the caller's
    (padded) dispatch buffer — one C pass instead of numpy's gather +
    shift temporaries + pad copy.  ``out`` must be a C-contiguous uint32
    view with at least len(uidx) lanes.  False when the native library
    is unavailable (callers fall back to ops/relay.rebuild_words)."""
    lib = _load_library()
    if lib is None:
        return False
    # Explicit check, not an assert (see sort_uniques) — ADVICE r4.
    if not (out.flags["C_CONTIGUOUS"] and out.dtype == np.uint32):
        return False  # caller rebuilds via ops/relay.rebuild_words
    lib.rl_rebuild_words(uwords.ctypes.data, uidx.ctypes.data,
                         rank.ctypes.data, len(uidx), int(rank_bits),
                         out.ctypes.data)
    return True


def weighted_layout(uwords: np.ndarray, rank_bits: int, uidx: np.ndarray,
                    rank: np.ndarray, perms: np.ndarray, r_b: int,
                    uw_sorted: np.ndarray, spos: np.ndarray,
                    roff: np.ndarray, perms_rank: np.ndarray) -> bool:
    """Count-descending rank-major layout for the weighted relay, in one
    C pass (native/slot_index.cpp:rl_weighted_layout) — emits the sorted
    words into caller-padded ``uw_sorted``, unique->position ``spos``,
    rank offsets ``roff``, and scatters ``perms`` into the caller-zeroed
    ``perms_rank``.  Returns False when the native library is missing
    (callers fall back to the numpy layout, bit-identical)."""
    lib = _load_library()
    if lib is None:
        return False
    rc = lib.rl_weighted_layout(
        uwords.ctypes.data, len(uwords), int(rank_bits),
        uidx.ctypes.data, rank.ctypes.data, len(uidx),
        perms.ctypes.data, int(r_b), uw_sorted.ctypes.data,
        spos.ctypes.data, roff.ctypes.data, perms_rank.ctypes.data)
    # rc != 0 = the C guard's own r_b ceiling (4096, slot_index.cpp)
    # tripped.  Unreachable while _WREL_MAX_R (64) stays far below it,
    # but if the cap is ever raised past 4096 the right behavior is the
    # bit-identical numpy fallback, not a hard failure of the whole
    # weighted pass — ADVICE r4.
    return rc == 0


def weighted_decide(bits: np.ndarray, roff: np.ndarray, spos: np.ndarray,
                    uidx: np.ndarray, rank: np.ndarray) -> np.ndarray:
    """Per-request decisions from the packed weighted bitmask: bit
    (roff[rank] + spos[uidx]) of ``bits`` (MSB-first), one C pass
    replacing unpackbits + fancy-index gather.  None-safe: callers only
    use this when :func:`weighted_layout` returned True."""
    lib = _load_library()
    out = np.empty(len(uidx), dtype=np.uint8)
    lib.rl_weighted_decide(bits.ctypes.data, roff.ctypes.data,
                           spos.ctypes.data, uidx.ctypes.data,
                           rank.ctypes.data, len(uidx), out.ctypes.data)
    return out.view(np.bool_)


def shard_route(key_ids: np.ndarray, n_shards: int):
    """(shard i32[n], stable order i64[n], counts i64[n_shards]) for an
    int64 key batch — one C pass of splitmix hash + counting sort,
    bit-identical to shard_of_int_keys + stable argsort.  None when the
    native library is unavailable (callers fall back to numpy)."""
    lib = _load_library()
    if lib is None:
        return None
    key_ids = np.ascontiguousarray(key_ids, dtype=np.int64)
    n = len(key_ids)
    shard = np.empty(n, dtype=np.int32)
    order = np.empty(n, dtype=np.int64)
    counts = np.empty(n_shards, dtype=np.int64)
    lib.rl_shard_route(key_ids.ctypes.data, n, int(n_shards),
                       shard.ctypes.data, order.ctypes.data,
                       counts.ctypes.data)
    return shard, order, counts


# Ranged partition routing (native/slot_index.cpp: rl_route_ranges,
# rl_merge_ranges): the route and merge passes of the host-partitioned
# index's batch walk (engine/partitioned.py).  The C side runs the
# request ranges [bounds[r], bounds[r+1]) on threads of its own, each
# writing only its own slice of the outputs.

def _require(arr: np.ndarray, dtype, n: int | None = None) -> None:
    if (arr.dtype != dtype or not arr.flags["C_CONTIGUOUS"]
            or (n is not None and len(arr) != n)):
        raise ValueError(f"expected a C-contiguous {np.dtype(dtype)} array"
                         + ("" if n is None else f" of {n}"))


def _check_bounds(bounds: np.ndarray, n: int) -> None:
    _require(bounds, np.int64)
    if (len(bounds) < 2 or bounds[0] != 0 or bounds[-1] != n
            or np.any(np.diff(bounds) < 0)):
        raise ValueError("bounds must rise from 0 to the batch length")


def route_ranges(lanes, hashed: bool, n_parts: int, bounds: np.ndarray):
    """Route a batch to ``n_parts`` partitions over request ranges.

    ``lanes`` are one or two C-contiguous 64-bit request lanes;
    ``lanes[0]`` routes: int keys through splitmix64 like
    :func:`shard_route` (``hashed``), fingerprint h1s as they are like
    :func:`route_hashes`.  Returns ``(part, local, offs, copies)``: each
    request's partition (uint8[n]), each range's first position in every
    partition (int64[ranges, n_parts]), the partitions' offsets
    (int64[n_parts + 1]) and the lanes copied partition-major, each
    partition's slice in arrival order."""
    n = len(lanes[0])
    if not 0 < n_parts <= 256 or not 1 <= len(lanes) <= 2:
        raise ValueError("1..256 partitions and one or two lanes")
    for lane in lanes:
        if (lane.dtype.itemsize != 8 or not lane.flags["C_CONTIGUOUS"]
                or len(lane) != n):
            raise ValueError("lanes must be C-contiguous 64-bit arrays "
                             "of one length")
    _check_bounds(bounds, n)
    part = np.empty(n, dtype=np.uint8)
    local = np.empty((len(bounds) - 1, n_parts), dtype=np.int64)
    offs = np.empty(n_parts + 1, dtype=np.int64)
    copies = [np.empty(n, dtype=lane.dtype) for lane in lanes]
    _load_library().rl_route_ranges(
        lanes[0].ctypes.data, int(bool(hashed)), n_parts, bounds.ctypes.data,
        len(bounds) - 1, part.ctypes.data, local.ctypes.data,
        offs.ctypes.data, copies[0].ctypes.data,
        lanes[1].ctypes.data if len(lanes) > 1 else None,
        copies[1].ctypes.data if len(lanes) > 1 else None)
    return part, local, offs, copies


def _lane_ptrs(srcs, counts) -> np.ndarray:
    for s, c in zip(srcs, counts):
        if c and (s is None or len(s) != c):
            raise ValueError("one source per partition, as long as the "
                             "partition's share of the batch")
        if s is not None:
            _require(s, np.int32)
    return np.asarray([0 if s is None else s.ctypes.data for s in srcs],
                      dtype=np.uintp)


def merge_ranges(part: np.ndarray, bounds: np.ndarray, local: np.ndarray,
                 offs: np.ndarray, src0, add0: np.ndarray,
                 src1=None) -> tuple:
    """Inverse of :func:`route_ranges` for the walks' int32 outputs:
    for request i in partition p = part[i] at position j of that
    partition's walk, ``out0[i] = src0[p][j] + add0[p]`` and ``out1[i]
    = src1[p][j]``.  ``src*`` hold one array per partition (None where
    it has no requests); ``local`` and ``offs`` are route_ranges'.
    Returns ``(out0, out1)`` (``out1`` None without ``src1``)."""
    n = len(part)
    n_parts = len(offs) - 1
    _require(part, np.uint8)
    _check_bounds(bounds, n)
    _require(local, np.int64)
    _require(add0, np.int32, n_parts)
    if (local.shape != (len(bounds) - 1, n_parts) or len(src0) != n_parts
            or offs[-1] != n):
        raise ValueError("routing and sources disagree on their shape")
    counts = np.diff(offs)
    p0 = _lane_ptrs(src0, counts)
    p1 = None if src1 is None else _lane_ptrs(src1, counts)
    out0 = np.empty(n, dtype=np.int32)
    out1 = None if src1 is None else np.empty(n, dtype=np.int32)
    _load_library().rl_merge_ranges(
        part.ctypes.data, n_parts, bounds.ctypes.data, len(bounds) - 1,
        local.ctypes.data, p0.ctypes.data, add0.ctypes.data,
        out0.ctypes.data, None if p1 is None else p1.ctypes.data,
        None if p1 is None else out1.ctypes.data)
    return out0, out1


def _split_key(key: Hashable) -> Tuple[int, bytes | int]:
    """Index keys arrive as (limiter_id, user_key); the lid becomes the hash
    seed so tenants are isolated."""
    if isinstance(key, tuple) and len(key) == 2:
        lid, user = key
        seed = int(lid) if isinstance(lid, int) else abs(hash(lid))
    else:
        seed, user = 0, key
    if isinstance(user, int):
        return seed, user
    if isinstance(user, bytes):
        return seed, user
    return seed, str(user).encode()


class NativeSlotIndex:
    """Drop-in SlotIndex backed by the C++ table (thread-safe via lock —
    matches the Python index; the batch path amortizes it over 1000s of keys)."""

    def __init__(self, num_slots: int):
        lib = _load_library()
        if lib is None:
            raise RuntimeError("native slot index unavailable")
        self._lib = lib
        self.num_slots = int(num_slots)
        self._h = ctypes.c_void_p(lib.rl_index_new(self.num_slots))
        self._lock = threading.Lock()

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.rl_index_free(h)
            self._h = None

    @contextlib.contextmanager
    def _pinned(self, pinned):
        """Hold pin refcounts on the given slots for the enclosed call.
        Must be entered with self._lock held."""
        pins = list(pinned) if pinned else []
        for s in pins:
            self._lib.rl_index_pin(self._h, s)
        try:
            yield
        finally:
            for s in pins:
                self._lib.rl_index_unpin(self._h, s)

    # -- scalar interface (SlotIndex parity) ----------------------------------
    def get(self, key: Hashable) -> Optional[int]:
        seed, user = _split_key(key)
        with self._lock:
            if isinstance(user, int):
                slot = self._lib.rl_index_get_int(self._h, user, seed)
            else:
                slot = self._lib.rl_index_get_bytes(self._h, user, len(user), seed)
        return None if slot < 0 else slot

    def assign(
        self, key: Hashable, pinned: Optional[Set[int]] = None,
        hold_pin: bool = False
    ) -> Tuple[int, Optional[int]]:
        seed, user = _split_key(key)
        out_slot = np.empty(1, dtype=np.int32)
        out_ev = np.empty(1, dtype=np.int32)
        with self._lock, self._pinned(pinned):
            if isinstance(user, int):
                keys = np.asarray([user], dtype=np.int64)
                self._lib.rl_index_assign_ints(
                    self._h, keys.ctypes.data, 1, seed,
                    out_slot.ctypes.data, out_ev.ctypes.data)
            else:
                data = np.frombuffer(user, dtype=np.uint8) if user else \
                    np.empty(0, dtype=np.uint8)
                offs = np.asarray([0, len(user)], dtype=np.int64)
                self._lib.rl_index_assign_bytes(
                    self._h, data.ctypes.data if len(user) else 0,
                    offs.ctypes.data, 1, seed,
                    out_slot.ctypes.data, out_ev.ctypes.data)
            if hold_pin and out_slot[0] >= 0:
                self._lib.rl_index_pin(self._h, int(out_slot[0]))
        if out_ev[0] == -2:
            raise RuntimeError("all slots pinned; increase num_slots or flush")
        evicted = int(out_ev[0]) if out_ev[0] >= 0 else None
        return int(out_slot[0]), evicted

    def remove(self, key: Hashable) -> Optional[int]:
        seed, user = _split_key(key)
        with self._lock:
            if isinstance(user, int):
                slot = self._lib.rl_index_remove_int(self._h, user, seed)
            else:
                slot = self._lib.rl_index_remove_bytes(self._h, user, len(user), seed)
        return None if slot < 0 else slot

    def __len__(self) -> int:
        with self._lock:
            return int(self._lib.rl_index_len(self._h))

    # -- vectorized interface -------------------------------------------------
    def assign_batch_ints(self, keys: np.ndarray, lid: int,
                          pinned: Optional[Set[int]] = None,
                          hold_pins: bool = False):
        """Assign slots for an int64 key batch in one C call.
        ``pinned`` slots (queued async requests) are never evicted.
        ``hold_pins`` pins the returned slots ATOMICALLY with the
        assignment (same lock hold) — the caller must ``unpin_batch``
        them once its dispatch is enqueued.  Returns (slots i32[n],
        evictions i32[k])."""
        keys = np.ascontiguousarray(keys, dtype=np.int64)
        n = len(keys)
        out_slots = np.empty(n, dtype=np.int32)
        out_ev = np.empty(n, dtype=np.int32)
        with self._lock, self._pinned(pinned):
            self._lib.rl_index_assign_ints(
                self._h, keys.ctypes.data, n, int(lid),
                out_slots.ctypes.data, out_ev.ctypes.data)
            # Pin only on full success: the caller raises on -2 and never
            # dispatches, so pinning the successful lanes would leak.
            failed = bool((out_ev == -2).any())
            if hold_pins and not failed:
                self._lib.rl_index_pin_batch(
                    self._h, out_slots.ctypes.data, n)
        if failed:
            raise SlotCapacityError("slot capacity exhausted (all pinned)",
                                    pending_clears=out_ev[out_ev >= 0])
        return out_slots, out_ev[out_ev >= 0]

    def assign_batch_ints_multi(self, keys: np.ndarray, lids: np.ndarray,
                                pinned: Optional[Set[int]] = None,
                                hold_pins: bool = False):
        """Assign slots for an int64 key batch with per-request limiter ids
        in one C call.  Same key namespace as per-lid assign_batch_ints —
        (lid, key) maps to the same slot whichever path touches it first."""
        keys = np.ascontiguousarray(keys, dtype=np.int64)
        seeds = np.ascontiguousarray(lids, dtype=np.uint64)
        n = len(keys)
        out_slots = np.empty(n, dtype=np.int32)
        out_ev = np.empty(n, dtype=np.int32)
        with self._lock, self._pinned(pinned):
            self._lib.rl_index_assign_ints_multi(
                self._h, keys.ctypes.data, seeds.ctypes.data, n,
                out_slots.ctypes.data, out_ev.ctypes.data)
            failed = bool((out_ev == -2).any())
            if hold_pins and not failed:  # see assign_batch_ints
                self._lib.rl_index_pin_batch(
                    self._h, out_slots.ctypes.data, n)
        if failed:
            raise SlotCapacityError("slot capacity exhausted (all pinned)",
                                    pending_clears=out_ev[out_ev >= 0])
        return out_slots, out_ev[out_ev >= 0]

    # -- held pins (streams: assign -> dispatch-enqueue window) ---------------
    def pin_batch(self, slots: np.ndarray) -> None:
        """Refcounted pins (duplicates fine) held across a dispatch-prep
        window so concurrent assigns can't evict these slots."""
        slots = np.ascontiguousarray(slots, dtype=np.int32)
        with self._lock:
            self._lib.rl_index_pin_batch(self._h, slots.ctypes.data,
                                         len(slots))

    def unpin_batch(self, slots: np.ndarray) -> None:
        slots = np.ascontiguousarray(slots, dtype=np.int32)
        with self._lock:
            self._lib.rl_index_unpin_batch(self._h, slots.ctypes.data,
                                           len(slots))

    # -- uniques interface (the relay streaming path; ops/relay.py) -----------
    # One uint32 per UNIQUE slot of the batch — (slot | clamped segment
    # count) — plus per-request (unique-index, rank) scratch the caller
    # keeps host-side (layout in native/slot_index.cpp:
    # assign_batch_uniques).  Evictions are reported exactly like the
    # plain batch assigns.

    def assign_batch_ints_uniques(self, keys: np.ndarray, lid: int,
                                  rank_bits: int,
                                  pinned: Optional[Set[int]] = None,
                                  hold_pins: bool = False):
        """Unique-compaction assign (segment-digest path): returns
        (uwords uint32[u], uidx i32[n], rank i32[n], evictions).  uwords
        carries (slot | clamped-count) per unique in first-appearance
        order; uidx/rank stay host-side for decision reconstruction."""
        keys = np.ascontiguousarray(keys, dtype=np.int64)
        n = len(keys)
        uwords = np.empty(n, dtype=np.uint32)
        uidx = np.empty(n, dtype=np.int32)
        rank = np.empty(n, dtype=np.int32)
        out_ev = np.empty(n, dtype=np.int32)
        with self._lock, self._pinned(pinned):
            u = self._lib.rl_index_assign_ints_uniques(
                self._h, keys.ctypes.data, n, int(lid), int(rank_bits),
                uwords.ctypes.data, uidx.ctypes.data, rank.ctypes.data,
                out_ev.ctypes.data)
            failed = bool((out_ev == -2).any())
            if hold_pins and not failed:  # see assign_batch_ints
                uslots = (uwords[:u] >> np.uint32(rank_bits + 1)).astype(
                    np.int32)
                self._lib.rl_index_pin_batch(
                    self._h, np.ascontiguousarray(uslots).ctypes.data, u)
        if failed:
            raise SlotCapacityError("slot capacity exhausted (all pinned)",
                                    pending_clears=out_ev[out_ev >= 0])
        return uwords[:u], uidx, rank, out_ev[out_ev >= 0]

    def assign_batch_ints_multi_uniques(self, keys: np.ndarray,
                                        lids: np.ndarray, rank_bits: int,
                                        pinned: Optional[Set[int]] = None,
                                        hold_pins: bool = False):
        keys = np.ascontiguousarray(keys, dtype=np.int64)
        seeds = np.ascontiguousarray(lids, dtype=np.uint64)
        n = len(keys)
        uwords = np.empty(n, dtype=np.uint32)
        uidx = np.empty(n, dtype=np.int32)
        rank = np.empty(n, dtype=np.int32)
        out_ev = np.empty(n, dtype=np.int32)
        with self._lock, self._pinned(pinned):
            u = self._lib.rl_index_assign_ints_multi_uniques(
                self._h, keys.ctypes.data, seeds.ctypes.data, n,
                int(rank_bits), uwords.ctypes.data, uidx.ctypes.data,
                rank.ctypes.data, out_ev.ctypes.data)
            failed = bool((out_ev == -2).any())
            if hold_pins and not failed:  # see assign_batch_ints
                uslots = (uwords[:u] >> np.uint32(rank_bits + 1)).astype(
                    np.int32)
                self._lib.rl_index_pin_batch(
                    self._h, np.ascontiguousarray(uslots).ctypes.data, u)
        if failed:
            raise SlotCapacityError("slot capacity exhausted (all pinned)",
                                    pending_clears=out_ev[out_ev >= 0])
        return uwords[:u], uidx, rank, out_ev[out_ev >= 0]

    def assign_batch_fps_uniques(self, h1: np.ndarray, h2: np.ndarray,
                                 rank_bits: int,
                                 pinned: Optional[Set[int]] = None,
                                 hold_pins: bool = False):
        """Unique-compaction assign for PRECOMPUTED fingerprints — the
        sharded/partitioned string streams hash once, route by h1, and
        feed each sub-index its slice here.  Identical semantics to the
        bytes-keyed uniques assign on the same fingerprints."""
        if not hasattr(self._lib, "rl_index_assign_fps_uniques"):
            raise RuntimeError("stale native library: rebuild native/ "
                               "(rl_index_assign_fps_uniques missing)")
        h1 = np.ascontiguousarray(h1, dtype=np.uint64)
        h2 = np.ascontiguousarray(h2, dtype=np.uint64)
        n = len(h1)
        uwords = np.empty(n, dtype=np.uint32)
        uidx = np.empty(n, dtype=np.int32)
        rank = np.empty(n, dtype=np.int32)
        out_ev = np.empty(n, dtype=np.int32)
        with self._lock, self._pinned(pinned):
            u = self._lib.rl_index_assign_fps_uniques(
                self._h, h1.ctypes.data, h2.ctypes.data, n,
                int(rank_bits), uwords.ctypes.data, uidx.ctypes.data,
                rank.ctypes.data, out_ev.ctypes.data)
            failed = bool((out_ev == -2).any())
            if hold_pins and not failed:  # see assign_batch_ints
                uslots = (uwords[:u] >> np.uint32(rank_bits + 1)).astype(
                    np.int32)
                self._lib.rl_index_pin_batch(
                    self._h, np.ascontiguousarray(uslots).ctypes.data, u)
        if failed:
            raise SlotCapacityError("slot capacity exhausted (all pinned)",
                                    pending_clears=out_ev[out_ev >= 0])
        return uwords[:u], uidx, rank, out_ev[out_ev >= 0]

    def assign_batch_strs_uniques(self, keys, lid: int, rank_bits: int,
                                  pinned: Optional[Set[int]] = None,
                                  hold_pins: bool = False,
                                  start: int = 0,
                                  count: int | None = None):
        """String-key uniques assign: pack -> hash -> slot walk with zero
        per-key Python objects.  ``start``/``count`` window the key
        sequence so stream chunking never slices a multi-million-entry
        list (the r5 path copied each chunk's slice).  Fast path: one
        CPython hash pass (fingerprints straight off the interned UTF-8
        buffers) feeding the fingerprint walk; fallback: the packed-bytes
        walk, bit-identical."""
        import time as _time

        n = (len(keys) - start) if count is None else count
        t_p0 = _time.perf_counter()
        fp = (hash_str_keys(keys, lid, start, n)
              if hasattr(self._lib, "rl_index_assign_fps_uniques")
              else None)
        if fp is not None:
            # Exposed for the stream loop's per-chunk phase lanes (pack
            # vs hash+walk — VERDICT r4 #7); the caller reads it before
            # it submits the next chunk's prefetch, so it always refers
            # to the chunk just assigned.
            self.str_pack_s = _time.perf_counter() - t_p0
            return self.assign_batch_fps_uniques(
                fp[0], fp[1], rank_bits, pinned=pinned,
                hold_pins=hold_pins)
        sub = keys if (start == 0 and n == len(keys)) else keys[
            start:start + n]
        packed, offs = _pack_str_keys(
            sub if isinstance(sub, list) else list(sub))
        self.str_pack_s = _time.perf_counter() - t_p0
        uwords = np.empty(n, dtype=np.uint32)
        uidx = np.empty(n, dtype=np.int32)
        rank = np.empty(n, dtype=np.int32)
        out_ev = np.empty(n, dtype=np.int32)
        with self._lock, self._pinned(pinned):
            u = self._lib.rl_index_assign_bytes_uniques(
                self._h, packed.ctypes.data if len(packed) else 0,
                offs.ctypes.data, n, int(lid), int(rank_bits),
                uwords.ctypes.data, uidx.ctypes.data, rank.ctypes.data,
                out_ev.ctypes.data)
            failed = bool((out_ev == -2).any())
            if hold_pins and not failed:  # see assign_batch_ints
                uslots = (uwords[:u] >> np.uint32(rank_bits + 1)).astype(
                    np.int32)
                self._lib.rl_index_pin_batch(
                    self._h, np.ascontiguousarray(uslots).ctypes.data, u)
        if failed:
            raise SlotCapacityError("slot capacity exhausted (all pinned)",
                                    pending_clears=out_ev[out_ev >= 0])
        return uwords[:u], uidx, rank, out_ev[out_ev >= 0]

    # -- fingerprint enumeration (checkpoint/resume at native speed) ----------
    def dump_fp(self):
        """All live entries as (h1 u64[n], h2 u64[n], slots i32[n]), in LRU
        order most-recent first — the native-speed checkpoint payload.
        Fingerprints are one-way: use the Python index when a dump must
        carry the original keys (cross-shard rebalance)."""
        cap = self.num_slots
        h1 = np.empty(cap, dtype=np.uint64)
        h2 = np.empty(cap, dtype=np.uint64)
        slots = np.empty(cap, dtype=np.int32)
        with self._lock:
            n = self._lib.rl_index_dump(
                self._h, h1.ctypes.data, h2.ctypes.data, slots.ctypes.data)
        return h1[:n].copy(), h2[:n].copy(), slots[:n].copy()

    def restore_fp(self, h1: np.ndarray, h2: np.ndarray,
                   slots: np.ndarray) -> None:
        """Rebuild from a dump_fp payload (exact LRU order restored)."""
        h1 = np.ascontiguousarray(h1, dtype=np.uint64)
        h2 = np.ascontiguousarray(h2, dtype=np.uint64)
        slots = np.ascontiguousarray(slots, dtype=np.int32)
        n = len(h1)
        if len(h2) != n or len(slots) != n:
            raise ValueError("fingerprint dump arrays disagree on length")
        with self._lock:
            rc = self._lib.rl_index_restore(
                self._h, h1.ctypes.data, h2.ctypes.data, slots.ctypes.data, n)
        if rc != 0:
            raise ValueError(
                "invalid fingerprint dump (bad slot, duplicate, or size)")

    def lookup_fps(self, h1: np.ndarray, h2: np.ndarray) -> np.ndarray:
        """Slots of the given fingerprints (-1 if absent); no LRU touch."""
        h1 = np.ascontiguousarray(h1, dtype=np.uint64)
        h2 = np.ascontiguousarray(h2, dtype=np.uint64)
        out = np.empty(len(h1), dtype=np.int32)
        with self._lock:
            self._lib.rl_index_lookup_fps(
                self._h, h1.ctypes.data, h2.ctypes.data, len(h1),
                out.ctypes.data)
        return out

    def assign_batch_fps(self, h1: np.ndarray, h2: np.ndarray,
                         pinned: Optional[Set[int]] = None,
                         hold_pins: bool = False):
        """Assign slots for raw fingerprints (flat-to-flat rebalance
        import, and the string fast path once the keys are hashed).
        Returns (slots i32[n], evictions i32[k])."""
        h1 = np.ascontiguousarray(h1, dtype=np.uint64)
        h2 = np.ascontiguousarray(h2, dtype=np.uint64)
        n = len(h1)
        out_slots = np.empty(n, dtype=np.int32)
        out_ev = np.empty(n, dtype=np.int32)
        with self._lock, self._pinned(pinned):
            self._lib.rl_index_assign_fps(
                self._h, h1.ctypes.data, h2.ctypes.data, n,
                out_slots.ctypes.data, out_ev.ctypes.data)
            failed = bool((out_ev == -2).any())
            if hold_pins and not failed:  # see assign_batch_ints
                self._lib.rl_index_pin_batch(
                    self._h, out_slots.ctypes.data, n)
        if failed:
            raise SlotCapacityError("slot capacity exhausted (all pinned)",
                                    pending_clears=out_ev[out_ev >= 0])
        return out_slots, out_ev[out_ev >= 0]

    def assign_batch_bytes(self, data, offsets, lid: int,
                           pinned: Optional[Set[int]] = None,
                           hold_pins: bool = False):
        """Assign slots straight off a packed UTF-8 key column (the
        sidecar's v5 batch frame: data uint8[klen] + offsets i64[n+1] is
        exactly rl_index_assign_bytes' input), so a whole frame of keys
        assigns with zero per-key Python objects.  Fingerprints are
        seeded by lid like the per-frame string path — the same key
        lands in the same slot through either.  Returns (slots i32[n],
        evictions i32[k])."""
        data = np.ascontiguousarray(data, dtype=np.uint8)
        offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        n = len(offsets) - 1
        out_slots = np.empty(n, dtype=np.int32)
        out_ev = np.empty(n, dtype=np.int32)
        with self._lock, self._pinned(pinned):
            self._lib.rl_index_assign_bytes(
                self._h, data.ctypes.data if len(data) else 0,
                offsets.ctypes.data, n, int(lid),
                out_slots.ctypes.data, out_ev.ctypes.data)
            failed = bool((out_ev == -2).any())
            if hold_pins and not failed:  # see assign_batch_ints
                self._lib.rl_index_pin_batch(
                    self._h, out_slots.ctypes.data, n)
        if failed:
            raise SlotCapacityError("slot capacity exhausted (all pinned)",
                                    pending_clears=out_ev[out_ev >= 0])
        return out_slots, out_ev[out_ev >= 0]

    def assign_batch_strs(self, keys, lid: int,
                          pinned: Optional[Set[int]] = None,
                          hold_pins: bool = False,
                          start: int = 0, count: int | None = None):
        """Assign slots for a string key batch in one C call (fingerprint
        fast path when the CPython hasher is available; windowed like
        assign_batch_strs_uniques)."""
        n = (len(keys) - start) if count is None else count
        fp = hash_str_keys(keys, lid, start, n)
        out_slots = np.empty(n, dtype=np.int32)
        out_ev = np.empty(n, dtype=np.int32)
        if fp is not None:
            h1 = np.ascontiguousarray(fp[0], dtype=np.uint64)
            h2 = np.ascontiguousarray(fp[1], dtype=np.uint64)
            with self._lock, self._pinned(pinned):
                self._lib.rl_index_assign_fps(
                    self._h, h1.ctypes.data, h2.ctypes.data, n,
                    out_slots.ctypes.data, out_ev.ctypes.data)
                failed = bool((out_ev == -2).any())
                if hold_pins and not failed:  # see assign_batch_ints
                    self._lib.rl_index_pin_batch(
                        self._h, out_slots.ctypes.data, n)
            if failed:
                raise SlotCapacityError(
                    "slot capacity exhausted (all pinned)",
                    pending_clears=out_ev[out_ev >= 0])
            return out_slots, out_ev[out_ev >= 0]
        sub = keys if (start == 0 and n == len(keys)) else keys[
            start:start + n]
        packed, offs = _pack_str_keys(
            sub if isinstance(sub, list) else list(sub))
        with self._lock, self._pinned(pinned):
            self._lib.rl_index_assign_bytes(
                self._h, packed.ctypes.data if len(packed) else 0,
                offs.ctypes.data, n, int(lid),
                out_slots.ctypes.data, out_ev.ctypes.data)
            failed = bool((out_ev == -2).any())
            if hold_pins and not failed:  # see assign_batch_ints
                self._lib.rl_index_pin_batch(
                    self._h, out_slots.ctypes.data, n)
        if failed:
            raise SlotCapacityError("slot capacity exhausted (all pinned)",
                                    pending_clears=out_ev[out_ev >= 0])
        return out_slots, out_ev[out_ev >= 0]
