"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` jax reports.  A device that is not here is an error, never
a default: a share of an assumed peak is not a measurement."""

from __future__ import annotations

#: Google Cloud documentation, "TPU v5e" system architecture page: one chip
#: has 197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM2e at 819 GB/s
#: and 1,600 Gbit/s of chip-to-chip interconnect.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16 * 2 ** 30,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
        "source": "cloud.google.com/tpu/docs/v5e (system architecture)",
    },
}


def peaks_for(device_kind):
    """The row of ``device_kind``; raises for a device the table lacks."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"chipbench/peaks.py has no row for device_kind "
            f"{device_kind!r}; add one with its source, do not assume"
        ) from None
