"""Peak-rate tables + MFU / roofline arithmetic for the profiling layer.

Pure functions over numbers the :mod:`~hetu_tpu.telemetry.profiling`
capture layer supplies (XLA cost-model flops/bytes, measured steps/s),
so every derived signal here is unit-testable without a device:

* :func:`chip_peaks` — per-chip peak flop rate and HBM bandwidth, from
  the device kind (published TPU specs with their source; bf16
  dense-matmul peaks); an unknown device raises.  ``HETU_PEAK_FLOPS`` /
  ``HETU_PEAK_HBM_BW`` env overrides pin CPU-quick rounds to a stable
  denominator.
* :func:`mfu` — model flops utilization: achieved flops/s over peak.
* :func:`roofline` — arithmetic intensity vs the ridge point, i.e.
  whether the program sits on the compute or the memory roof.
* :func:`derive` — the full per-program derived block bench/report use.

On CPU the table returns a NOMINAL host peak: the absolute MFU is
meaningless there (and flagged ``peak_source="nominal_cpu"``); device
numbers come from a chip run (``python3 -m chipbench.run``) only.
"""

from __future__ import annotations

import os

__all__ = ["CHIP_PEAKS", "chip_peaks", "mfu", "roofline", "derive"]

#: (device_kind substring, peak flops/s, HBM bytes/s, source).  Flop peaks
#: are bf16 dense-matmul numbers; substrings are matched in order against
#: the lower-cased ``jax.devices()[0].device_kind``, so "v5p" precedes
#: "v5 lite" etc.  The trailing "cpu" entry is nominal.
CHIP_PEAKS = (
    # a v6e reports device_kind "TPU v6 lite"
    ("v6 lite", 918e12, 1640e9, "Google Cloud TPU docs, 'TPU v6e'"),
    ("v5p", 459e12, 2765e9, "Google Cloud TPU docs, 'TPU v5p'"),
    # a v5e reports device_kind "TPU v5 lite"
    ("v5 lite", 197e12, 819e9, "Google Cloud TPU docs, 'TPU v5e'"),
    ("v4", 275e12, 1228e9, "Google Cloud TPU docs, 'TPU v4'"),
    ("v3", 123e12, 900e9, "Google Cloud TPU docs, 'TPU v3'"),
    ("v2", 45e12, 700e9, "Google Cloud TPU docs, 'TPU v2'"),
    ("cpu", 2e11, 5e10, "nominal_cpu"),  # host-order numbers, not a spec
)


def chip_peaks(device_kind=None):
    """``{"device_kind", "peak_flops", "peak_hbm_bytes_per_s",
    "peak_source"}`` for the current (or named) chip.

    ``device_kind=None`` reads ``jax.devices()[0].device_kind`` — lazy
    import, so the module stays importable without jax.  A device the
    table does not know raises: a utilization against a guessed peak is
    worse than none.  Env overrides ``HETU_PEAK_FLOPS`` /
    ``HETU_PEAK_HBM_BW`` win over the table (and stand in for it on an
    unknown device when both are set).
    """
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    kind_l = str(device_kind).lower()
    flops = bw = source = None
    for sub, f, b, src in CHIP_PEAKS:
        if sub in kind_l:
            flops, bw, source = f, b, src
            break
    env_f = os.environ.get("HETU_PEAK_FLOPS")
    env_b = os.environ.get("HETU_PEAK_HBM_BW")
    if env_f:
        flops, source = float(env_f), "env"
    if env_b:
        bw = float(env_b)
        source = source if env_f else "env"
    if flops is None or bw is None:
        raise ValueError(
            f"no peak rates on record for device_kind {device_kind!r}; "
            "add a row with its source to telemetry.perf_model.CHIP_PEAKS "
            "(or set HETU_PEAK_FLOPS and HETU_PEAK_HBM_BW)")
    return {"device_kind": str(device_kind),
            "peak_flops": float(flops),
            "peak_hbm_bytes_per_s": float(bw),
            "peak_source": source}


def mfu(flops_per_step, steps_per_sec, peak_flops):
    """Model flops utilization: (flops/step x steps/s) / peak flops/s.

    0.0 when any input is missing/non-positive (never raises: profiling
    must degrade, not break, on backends without a cost model)."""
    if not flops_per_step or not steps_per_sec or not peak_flops:
        return 0.0
    if flops_per_step <= 0 or steps_per_sec <= 0 or peak_flops <= 0:
        return 0.0
    return float(flops_per_step) * float(steps_per_sec) / float(peak_flops)


def roofline(flops_per_step, bytes_per_step, peaks):
    """Roofline position of one program: arithmetic intensity (flops per
    HBM byte accessed) vs the chip's ridge point (peak_flops / peak_bw).
    ``bound`` is "compute" above the ridge, "memory" below, None when
    the inputs are missing."""
    peak_f = peaks["peak_flops"]
    peak_b = peaks["peak_hbm_bytes_per_s"]
    ridge = (peak_f / peak_b) if peak_b else None
    if not flops_per_step or not bytes_per_step or bytes_per_step <= 0:
        return {"arithmetic_intensity": None, "ridge_intensity": ridge,
                "bound": None}
    ai = float(flops_per_step) / float(bytes_per_step)
    bound = None
    if ridge is not None:
        bound = "compute" if ai >= ridge else "memory"
    return {"arithmetic_intensity": round(ai, 6),
            "ridge_intensity": round(ridge, 6) if ridge else None,
            "bound": bound}


def derive(cost, steps=None, elapsed_s=None, peaks=None, n_chips=1,
           tokens=None, items_name="tokens"):
    """The derived-signal block for one profiled program.

    ``cost`` is the normalized XLA cost dict (flops, "bytes accessed");
    ``steps``/``elapsed_s`` a measured execution count and wall window
    (None -> static-only signals); ``tokens`` an optional item count for
    serving-style throughput (items/s/chip under ``items_name``).
    Arithmetic is deliberately transparent —
    ``mfu == flops_per_step * steps_per_sec / peak_flops`` exactly —
    and pinned by tests/test_profiling.py.
    """
    peaks = peaks or chip_peaks()
    flops = float(cost.get("flops", 0.0) or 0.0)
    nbytes = float(cost.get("bytes accessed", 0.0) or 0.0)
    out = {"flops_per_step": flops, "bytes_per_step": nbytes,
           "roofline": roofline(flops, nbytes, peaks)}
    if steps and elapsed_s and elapsed_s > 0:
        sps = float(steps) / float(elapsed_s)
        out["steps"] = int(steps)
        out["elapsed_s"] = round(float(elapsed_s), 6)
        out["steps_per_sec"] = round(sps, 4)
        out["achieved_flops_per_sec"] = round(flops * sps, 2)
        out["achieved_bytes_per_sec"] = round(nbytes * sps, 2)
        out["mfu"] = round(mfu(flops, sps, peaks["peak_flops"]), 6)
        bw = peaks["peak_hbm_bytes_per_s"]
        out["hbm_frac"] = round(nbytes * sps / bw, 6) if bw else None
        if tokens:
            per_chip = float(tokens) / float(elapsed_s) / max(1, n_chips)
            out[f"{items_name}_per_sec_per_chip"] = round(per_chip, 2)
    return out
