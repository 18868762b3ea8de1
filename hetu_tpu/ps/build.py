"""Build + load the native PS core (g++ → libhetu_ps, loaded via ctypes;
native_build.py says where the library file goes).

The reference ships its store as prebuilt C++ (libps.so loaded by ctypes at
executor.py:100-137); here the library is compiled on first use from the
in-tree source so the repo stays self-contained.
"""

from __future__ import annotations

import ctypes
import os

from ..native_build import NativeLib

_HERE = os.path.dirname(os.path.abspath(__file__))


def _declare(lib):
        i64, f32p, i64p, u64p = (ctypes.c_int64,
                                 ctypes.POINTER(ctypes.c_float),
                                 ctypes.POINTER(ctypes.c_int64),
                                 ctypes.POINTER(ctypes.c_uint64))
        f = ctypes.c_float
        lib.ps_table_create.restype = i64
        lib.ps_table_create.argtypes = [i64, i64, ctypes.c_int, f, f, f, f, f]
        lib.ps_table_destroy.argtypes = [i64]
        lib.ps_table_rows.restype = i64
        lib.ps_table_rows.argtypes = [i64]
        lib.ps_table_dim.restype = i64
        lib.ps_table_dim.argtypes = [i64]
        lib.ps_table_init_uniform.argtypes = [i64, ctypes.c_uint64, f]
        lib.ps_table_set_rows.argtypes = [i64, i64p, i64, f32p]
        lib.ps_table_lookup.argtypes = [i64, i64p, i64, f32p]
        lib.ps_table_versions.argtypes = [i64, i64p, i64, u64p]
        lib.ps_table_push.argtypes = [i64, i64p, f32p, i64]
        lib.ps_table_save.restype = ctypes.c_int
        lib.ps_table_save.argtypes = [i64, ctypes.c_char_p]
        lib.ps_table_load.restype = ctypes.c_int
        lib.ps_table_load.argtypes = [i64, ctypes.c_char_p]
        lib.ps_cache_create.restype = i64
        lib.ps_cache_create.argtypes = [i64, i64, ctypes.c_int, i64, i64]
        lib.ps_cache_destroy.argtypes = [i64]
        lib.ps_cache_lookup.argtypes = [i64, i64p, i64, f32p]
        lib.ps_cache_update.argtypes = [i64, i64p, f32p, i64]
        lib.ps_cache_flush.argtypes = [i64]
        lib.ps_cache_stats.argtypes = [i64] + [ctypes.POINTER(i64)] * 4
        lib.ssp_create.restype = i64
        lib.ssp_create.argtypes = [ctypes.c_int]
        lib.ssp_destroy.argtypes = [i64]
        lib.ssp_tick.argtypes = [i64, ctypes.c_int]
        lib.ssp_clock.restype = i64
        lib.ssp_clock.argtypes = [i64, ctypes.c_int]
        lib.ssp_min.restype = i64
        lib.ssp_min.argtypes = [i64]
        lib.preduce_create.restype = i64
        lib.preduce_create.argtypes = []
        lib.preduce_destroy.argtypes = [i64]
        lib.preduce_get_partner.restype = ctypes.c_int
        lib.preduce_get_partner.argtypes = [
            i64, i64, ctypes.c_int, ctypes.c_int, ctypes.c_float,
            ctypes.POINTER(ctypes.c_int)]


_native = NativeLib(os.path.join(_HERE, "native", "hetu_ps.cpp"),
                    "libhetu_ps",
                    declare=_declare, extra_flags=["-pthread"])


def build():
    return _native.build()


def load():
    """Compile (if needed) and load the native library, declaring arg types."""
    return _native.load()
