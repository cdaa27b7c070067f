"""Native (C++) components, compiled on demand with the system toolchain.

The only native component is the Avro block decoder (``avro_block.cc``) used
by :mod:`photon_tpu.io.streaming`. It is compiled once per source change with
``g++ -O3 -shared`` into this directory and loaded via ctypes; if no compiler
is available (or ``PHOTON_TPU_NO_NATIVE=1``), callers fall back to the pure
Python codec (``photon_tpu.io.avro``) — slower, identical semantics.
"""
from __future__ import annotations

import ctypes
import fcntl
import os
import subprocess
import threading
from typing import Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "avro_block.cc")


def _isa_tag() -> str:
    """Short tag of this host's vector ISA, so a -march=native build cached
    in a checkout shared over a network filesystem is never dlopen'd by a
    host with a different instruction set (SIGILL). crc32, not md5: FIPS
    hosts raise on md5, and this is a cache key, not cryptography."""
    import platform
    import zlib

    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    return f"{zlib.crc32(line.encode()) & 0xFFFFFFFF:08x}"
    except OSError:
        pass
    return platform.machine() or "unknown"


_SO = os.path.join(_HERE, f"_avro_block.{_isa_tag()}.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_failed = False


def _compile(force: bool = False) -> bool:
    """Build ``_SO`` in place; False if the toolchain cannot.

    Several processes may get here at once (xdist workers, ingest workers,
    the drivers of one fresh checkout). Each builds to a name of its own in
    this directory and renames it into place, so a reader never maps a half
    written file; the flock makes the losers of the race wait for the
    winner's file instead of building their own.
    """
    with open(_SO + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not force and not _stale():
            return True  # another process built it while we waited
        tmp = f"{_SO}.{os.getpid()}.tmp"
        cmd = [
            # -march=native is safe here: the .so is compiled on demand on
            # the same host that runs it (never shipped), and the hash/parse
            # inner loops gain measurably from host vector ISA.
            "g++", "-O3", "-march=native", "-shared", "-fPIC", "-std=c++17",
            "-o", tmp, _SRC,
        ]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=300)
            os.replace(tmp, _SO)
        except (OSError, subprocess.SubprocessError):
            return False
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
    return True


def _stale() -> bool:
    return (
        not os.path.exists(_SO)
        or os.path.getmtime(_SO) < os.path.getmtime(_SRC)
    )


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    i64, i32, u64, f64, u8 = (
        ctypes.c_int64, ctypes.c_int32, ctypes.c_uint64, ctypes.c_double,
        ctypes.c_uint8,
    )
    P = ctypes.POINTER
    lib.ph_hash_keys.argtypes = [P(u8), P(i64), i64, P(u64)]
    lib.ph_hash_keys.restype = None
    lib.ph_create.argtypes = [
        P(i32), i64, P(i32), i64, P(i64), i64,
        i32, P(f64), i32,
        P(u8), P(i64), i64,
        i32, P(P(u64)), P(P(i32)), P(i64),
    ]
    lib.ph_create.restype = ctypes.c_void_p
    lib.ph_destroy.argtypes = [ctypes.c_void_p]
    lib.ph_decode_block.argtypes = [ctypes.c_void_p, P(u8), i64, i64]
    lib.ph_decode_block.restype = i64
    lib.ph_chunk_rows.argtypes = [ctypes.c_void_p]
    lib.ph_chunk_rows.restype = i64
    lib.ph_get_num_col.argtypes = [ctypes.c_void_p, i32, P(f64)]
    lib.ph_get_str_codes.argtypes = [ctypes.c_void_p, i32, P(i32)]
    lib.ph_shard_nnz.argtypes = [ctypes.c_void_p, i32]
    lib.ph_shard_nnz.restype = i64
    lib.ph_get_shard_triples.argtypes = [ctypes.c_void_p, i32, P(i32), P(i32), P(f64)]
    lib.ph_dict_size.argtypes = [ctypes.c_void_p, i32]
    lib.ph_dict_size.restype = i64
    lib.ph_dict_heap_bytes_from.argtypes = [ctypes.c_void_p, i32, i64]
    lib.ph_dict_heap_bytes_from.restype = i64
    lib.ph_get_dict_range.argtypes = [ctypes.c_void_p, i32, i64, P(u8), P(i64)]
    lib.ph_shard_dict_size.argtypes = [ctypes.c_void_p, i32]
    lib.ph_shard_dict_size.restype = i64
    lib.ph_shard_dict_heap_bytes_from.argtypes = [ctypes.c_void_p, i32, i64]
    lib.ph_shard_dict_heap_bytes_from.restype = i64
    lib.ph_shard_dict_range.argtypes = [ctypes.c_void_p, i32, i64, P(u8), P(i64)]
    lib.ph_reset_chunk.argtypes = [ctypes.c_void_p]
    f32 = ctypes.c_float
    lib.ph_ell_scatter_f32.argtypes = [
        P(i32), P(i32), P(f64), i64, i64, i64, P(i32), P(f32)
    ]
    lib.ph_ell_scatter_f32.restype = None
    lib.ph_ell_scatter_f64.argtypes = [
        P(i32), P(i32), P(f64), i64, i64, i64, P(i32), P(f64)
    ]
    lib.ph_ell_scatter_f64.restype = None
    lib.ph_shard_max_run.argtypes = [ctypes.c_void_p, i32]
    lib.ph_shard_max_run.restype = i64
    lib.ph_shard_ell_f32.argtypes = [
        ctypes.c_void_p, i32, i64, i64, i64, i64, P(i32), P(f32)
    ]
    lib.ph_shard_ell_f32.restype = None
    lib.ph_shard_ell_f64.argtypes = [
        ctypes.c_void_p, i32, i64, i64, i64, i64, P(i32), P(f64)
    ]
    lib.ph_shard_ell_f64.restype = None
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    """The compiled decoder library, or None if native is unavailable."""
    global _lib, _failed
    if _lib is not None:
        return _lib
    if _failed or os.environ.get("PHOTON_TPU_NO_NATIVE") == "1":
        return None
    with _lock:
        if _lib is not None or _failed:
            return _lib
        try:
            if _stale() and not _compile():
                _failed = True
                return None
            try:
                _lib = _bind(ctypes.CDLL(_SO))
            except AttributeError:
                # A cached .so that predates newly-added symbols (mtime
                # preserved by tar/rsync, or equal mtimes): rebuild once
                # instead of crashing every ingest call.
                if not _compile(force=True):
                    _failed = True
                    return None
                _lib = _bind(ctypes.CDLL(_SO))
        except (OSError, AttributeError):
            _failed = True
            return None
    return _lib
