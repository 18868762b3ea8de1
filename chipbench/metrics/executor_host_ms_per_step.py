"""Host time a step with the device waiting: the benchmark's annotation
around ``ex.run`` less the device-busy time inside it (trace)."""
from chipbench.metrics._lib import host_ms_outside_device


def read(ctx):
    return host_ms_outside_device(ctx, "executor_run")
