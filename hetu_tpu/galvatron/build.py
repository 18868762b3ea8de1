"""Build + load the native Galvatron DP core (g++ → libgalvatron_dp).

Reference ships tools/Hetu-Galvatron/csrc/dp_core.cpp as a pybind11 module;
pybind11 is absent here so the core exposes a C ABI consumed via ctypes,
compiled on first use (same pattern as hetu_tpu/ps/build.py).
"""

from __future__ import annotations

import ctypes
import os
import warnings

import numpy as np

from ..native_build import NativeLib

_HERE = os.path.dirname(os.path.abspath(__file__))


def _declare(lib):
    i64 = ctypes.c_int64
    lib.galvatron_dp_core.restype = ctypes.c_int
    lib.galvatron_dp_core.argtypes = [
        i64, i64, i64,
        ctypes.POINTER(ctypes.c_int32),   # mem_cost [L*S]
        ctypes.POINTER(ctypes.c_double),  # intra_cost [L*S]
        ctypes.POINTER(ctypes.c_double),  # inter_cost [L*S*S]
        ctypes.POINTER(ctypes.c_int32),   # res [L]
        ctypes.POINTER(ctypes.c_double),  # cost_out
        ctypes.POINTER(i64),              # mem_left_out
    ]


_native = NativeLib(os.path.join(_HERE, "csrc", "dp_core.cpp"),
                    "libgalvatron_dp",
                    declare=_declare)


def build():
    return _native.build()


def load():
    return _native.load()


def dp_core(mem_cost, intra_cost, inter_cost, max_mem):
    """Run the native DP.  mem_cost [L,S] int, intra_cost [L,S], inter_cost
    [L,S,S].  Returns (total_cost, per-layer strategy indices, mem_left);
    (inf, None, -1) if infeasible."""
    mem_cost = np.ascontiguousarray(mem_cost, dtype=np.int32)
    intra = np.ascontiguousarray(intra_cost, dtype=np.float64)
    inter = np.ascontiguousarray(inter_cost, dtype=np.float64)
    L, S = mem_cost.shape
    assert intra.shape == (L, S) and inter.shape == (L, S, S)
    res = np.zeros(L, dtype=np.int32)
    cost = ctypes.c_double(0.0)
    left = ctypes.c_int64(0)
    lib = load()
    rc = lib.galvatron_dp_core(
        L, int(max_mem), S,
        mem_cost.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        intra.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        inter.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        res.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ctypes.byref(cost), ctypes.byref(left))
    if rc != 0:
        return float("inf"), None, -1
    return float(cost.value), res.tolist(), int(left.value)


_fallback_warned = False


def dp_core_auto(mem_cost, intra_cost, inter_cost, max_mem,
                 use_native=True):
    """Run the DP on the native csrc core when it builds, the numpy
    oracle otherwise — and say WHICH ran: returns ``(result, core)``
    with ``core in ("native", "numpy")``.  A toolchain-less host must
    not silently search on a different code path than the one the
    committed plans were produced by, so the first native→numpy
    fallback warns with the build error."""
    global _fallback_warned
    if use_native:
        try:
            return dp_core(mem_cost, intra_cost, inter_cost,
                           max_mem), "native"
        except (RuntimeError, OSError) as e:
            if not _fallback_warned:
                _fallback_warned = True
                warnings.warn(
                    f"galvatron native dp_core unavailable "
                    f"({type(e).__name__}: {e}); searches run on the "
                    f"numpy oracle instead")
    return dp_core_numpy(mem_cost, intra_cost, inter_cost,
                         max_mem), "numpy"


def dp_core_numpy(mem_cost, intra_cost, inter_cost, max_mem):
    """Pure-numpy oracle of the same recurrence (test/fallback path)."""
    mem_cost = np.asarray(mem_cost, dtype=np.int64)
    intra = np.asarray(intra_cost, dtype=np.float64)
    inter = np.asarray(inter_cost, dtype=np.float64)
    L, S = mem_cost.shape
    V = int(max_mem)
    # two buffers, not a rolling array: mem_cost 0 would alias the row
    # being written (same fix as dp_core.cpp)
    f_prev = np.zeros((V, S))
    f = np.zeros((V, S))
    mark = -np.ones((L, V, S), dtype=np.int64)
    for i in range(L):
        for v in range(V - 1, -1, -1):
            for s in range(S):
                m = mem_cost[i, s]
                if v < m:
                    f[v, s] = np.inf
                    continue
                if i == 0:
                    best, best_si = f_prev[v - m, s], s
                else:
                    cands = f_prev[v - m, :] + inter[i, :, s]
                    best_si = int(np.argmin(cands))
                    best = cands[best_si]
                if np.isfinite(best):
                    f[v, s] = best + intra[i, s]
                    mark[i, v, s] = best_si
                else:
                    f[v, s] = np.inf
        f_prev, f = f, f_prev
    f_prev, f = f, f_prev  # undo the last swap: f holds layer L-1
    cur = int(np.argmin(f[V - 1]))
    total = f[V - 1, cur]
    if not np.isfinite(total):
        return float("inf"), None, -1
    res = [0] * L
    res[L - 1] = cur
    v = V - 1
    for i in range(L - 1, 0, -1):
        prev_s = int(mark[i, v, cur])
        v -= int(mem_cost[i, cur])
        cur = prev_s
        res[i - 1] = cur
    v -= int(mem_cost[0, cur])
    return float(total), res, v
