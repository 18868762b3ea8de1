"""Peak bytes on the fullest chip over the chip's HBM; the source of the
peak (allocator mark, or live bytes plus XLA's memory analysis) is printed
on an earlier line."""


def read(ctx):
    if ctx["peaks"] is None:
        return None
    ctx["say"](f"peak_hbm_share from {ctx['memory_peak_source']}")
    return 100.0 * ctx["memory_peak_bytes"] / ctx["peaks"]["hbm_bytes"]
