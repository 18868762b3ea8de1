"""What every builder needs from the program's registry and compiler."""

from __future__ import annotations


def counter(name, **labels):
    """Current value of a registry counter or gauge, summed over the series
    whose labels match."""
    from hetu_tpu import telemetry
    metric = telemetry.get_registry().snapshot().get(name, {"samples": []})
    return sum(s["value"] for s in metric["samples"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


def jax_seed(seed):
    """``--seed`` may pass 2**31; a jax key takes 32 bits."""
    return int(seed) % (2 ** 31 - 1)
