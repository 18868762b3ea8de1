"""What the rest of the package needs from the installed jax (0.9) in one
place: plain-dict views of XLA's compiled-program analyses, and the one
function that points jax's persistent compilation cache.

Platform selection is jax's own: set ``JAX_PLATFORMS`` (and, for a
virtual CPU mesh, ``XLA_FLAGS=--xla_force_host_platform_device_count=N``)
in the shell before the process starts.
"""

from __future__ import annotations

import os

#: the checkout that holds this package; the default compile cache sits
#: beside it so that every process of one checkout shares one directory
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compile_cache():
    """Give jax's persistent compilation cache a directory; returns it.

    Entry points (``chip_smoke.py``, ``chipbench``, the ``examples/``
    mains) call this before their first compilation; importing
    ``hetu_tpu`` does not.  When ``JAX_COMPILATION_CACHE_DIR`` is set,
    jax has already read it and nothing is changed here.  Otherwise the
    cache goes to ``<checkout>/.jax_cache``: a fixed path, because the
    path is part of what a later process must repeat to hit."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_memory_limit():
    """Bytes of memory the first device offers its programs
    (``memory_stats()["bytes_limit"]``).  A tpu must report it.  The cpu
    backend reports no statistics and gets a nominal 16 GiB, one v5e's
    HBM, so that a CPU rehearsal takes the decisions the chip would."""
    import jax
    dev = jax.devices()[0]
    stats = dev.memory_stats()
    if stats and stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    if dev.platform == "tpu":
        raise RuntimeError(
            f"{dev.device_kind} reported no bytes_limit in memory_stats() "
            f"({stats!r}); refusing to assume a memory size")
    return 16 * 1024 ** 3


def compiled_cost_analysis(compiled):
    """XLA ``cost_analysis`` of a compiled program as a plain
    ``{str: float}`` dict; ``{}`` when the backend has no cost model."""
    cost = compiled.cost_analysis()
    return dict(cost) if cost else {}


#: the CompiledMemoryStats fields the profiling layer consumes, in the
#: order they are reported (device-side only; host_* mirrors excluded)
_MEMORY_FIELDS = ("generated_code_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "alias_size_in_bytes",
                  "temp_size_in_bytes")


def compiled_memory_analysis(compiled):
    """XLA ``memory_analysis`` (a ``CompiledMemoryStats`` attribute
    object) as a plain ``{str: int}`` dict; ``{}`` when the backend
    reports none."""
    ma = compiled.memory_analysis()
    if ma is None:
        return {}
    return {field: int(getattr(ma, field)) for field in _MEMORY_FIELDS}
