"""Shared per-block quantize/dequantize codec for the serving plane.

ONE arithmetic core for all three quantized-transport legs (ISSUE 16):
int8/fp8 paged KV pages (``serving/kv_cache.py``), the block-quantized
PS wire codec (``ps/rpc.py``), and quantized TP all-gathers
(``models/_decode_common.make_gather``).  Keeping every
narrow-dtype cast in this module is load-bearing: the round-trip error
bounds in ``tests/test_quant.py`` are proved against THIS code, and the
AST gate there fails any ad-hoc ``astype(int8)``/bitcast elsewhere in
the package — inline quantization drifting out of the error-bound tests
is exactly the bug class the gate exists to catch.

Scheme: symmetric per-block absmax scaling along the LAST axis.  A
block of ``block`` consecutive elements shares one float32 scale
``absmax / QMAX[dtype]``; codes are ``x / scale`` rounded into the
target dtype's representable range.  Zero blocks emit scale 0 and codes
0, so dequantization reproduces exact zeros (freshly allocated KV pages
stay bitwise-zero through a round trip).  EQuARX (PAPERS.md) uses the
same block-scaled layout for quantized collectives; per-block rather
than per-tensor scales are what keep one outlier row from wiping out
the mantissa budget of every other row in a KV page.

Every function is generic over the array namespace: pass numpy arrays
for host/wire paths (the PS server quantizes replies without touching
jax) and jax arrays for in-graph paths (KV gather/scatter, TP gathers).
``fp8`` is e4m3 (``float8_e4m3fn``).
"""

from __future__ import annotations

import numpy as np

#: largest representable magnitude per codec dtype: int8 is symmetric
#: [-127, 127] (-128 unused so negation round-trips), fp8 e4m3 saturates
#: at +-448
QMAX = {"int8": 127.0, "fp8": 448.0}

#: codec dtypes whose codes are themselves floats (scaled, not rounded
#: to integers)
_FLOAT_CODES = ("fp8",)


def _fp8_np_dtype():
    """numpy's float8_e4m3fn: the ml_dtypes definition jax depends on and
    re-exports as ``jnp.float8_e4m3fn``, so one lookup covers the numpy
    (wire) and the jax.numpy (in-graph) paths without importing jax."""
    import ml_dtypes
    return np.dtype(ml_dtypes.float8_e4m3fn)


def code_dtype(dtype):
    """The storage dtype of ``dtype``'s codes (np.dtype)."""
    if dtype == "int8":
        return np.dtype(np.int8)
    if dtype == "fp8":
        return _fp8_np_dtype()
    raise ValueError(f"unknown quantization dtype {dtype!r}; "
                     f"expected one of {sorted(QMAX)}")


def code_bytes_per_element(dtype):
    """Storage bytes per quantized element (both codecs are 1 today,
    but the ledger/bench math must not hard-code that)."""
    return int(code_dtype(dtype).itemsize)


def _namespace(x):
    """numpy for host arrays, jax.numpy for everything else (tracers
    included) — imported lazily so the wire path never pulls in jax."""
    if isinstance(x, (np.ndarray, np.generic)):
        return np
    import jax.numpy as jnp
    return jnp


def quantize_blocks(x, block=None, dtype="int8"):
    """Quantize ``x`` along its last axis in blocks of ``block``.

    Returns ``(codes, scales)``: ``codes`` has ``x``'s shape in the
    codec storage dtype; ``scales`` is float32 with shape
    ``x.shape[:-1] + (x.shape[-1] // block,)`` — one scale per block.
    ``block=None`` means one block spanning the whole last axis
    (``scales`` ends in a broadcast-ready trailing 1, the paged-KV
    layout).  ``block`` must divide the last axis exactly: transport
    blocking is a layout decision made where shapes are known, not
    something this core pads silently."""
    xp = _namespace(x)
    d = int(x.shape[-1])
    block = d if block is None else int(block)
    if block < 1 or d % block:
        raise ValueError(
            f"block={block} must divide the last axis ({d}) exactly")
    qmax = QMAX[dtype]          # raises KeyError-shaped below if bad
    cdt = code_dtype(dtype)
    nblocks = d // block
    blocked = xp.reshape(xp.asarray(x, np.float32),
                         x.shape[:-1] + (nblocks, block))
    absmax = xp.max(xp.abs(blocked), axis=-1, keepdims=True)
    # zero blocks: emit scale 0 (dequant reproduces exact zeros) but
    # divide by 1 so the codes stay finite
    safe = xp.where(absmax > 0, absmax / qmax, xp.float32(1.0))
    scaled = blocked / safe
    if dtype in _FLOAT_CODES:
        codes = scaled.astype(cdt)
    else:
        codes = xp.clip(xp.rint(scaled), -qmax, qmax).astype(cdt)
    scales = xp.where(absmax > 0, absmax / qmax, xp.float32(0.0))
    return (xp.reshape(codes, x.shape),
            xp.reshape(scales, x.shape[:-1] + (nblocks,))
              .astype(np.float32))


def dequantize_blocks(codes, scales):
    """Invert :func:`quantize_blocks`: ``codes`` in any codec storage
    dtype times the per-block ``scales`` back to float32, in ``codes``'s
    shape.  Block size is recovered from the shapes, so call sites never
    thread it separately (and can't get it wrong)."""
    xp = _namespace(codes)
    d, nblocks = int(codes.shape[-1]), int(scales.shape[-1])
    if nblocks < 1 or d % nblocks:
        raise ValueError(
            f"scales last axis ({nblocks}) must divide codes last "
            f"axis ({d})")
    block = d // nblocks
    blocked = xp.reshape(codes.astype(np.float32),
                         codes.shape[:-1] + (nblocks, block))
    out = blocked * xp.reshape(scales, scales.shape + (1,)).astype(
        np.float32)
    return xp.reshape(out, codes.shape)


def roundtrip_bound(dtype, absmax=1.0, block=None):
    """Worst-case absolute round-trip error for one block whose largest
    magnitude is ``absmax``: half a quantization step for int8's
    round-to-nearest, one e4m3 ulp-at-absmax (2^-3 relative) for fp8.
    ``block`` is accepted for signature symmetry — the bound depends on
    the block's absmax, not its width."""
    del block
    if dtype == "int8":
        return float(absmax) / QMAX["int8"] * 0.5
    if dtype == "fp8":
        return float(absmax) * 2.0 ** -3
    raise ValueError(f"unknown quantization dtype {dtype!r}")
