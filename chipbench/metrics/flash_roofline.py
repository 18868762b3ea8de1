"""Flash attention's least possible time over the measured device time of
its events (trace events whose name holds ``hetu_flash_fwd`` or
``hetu_flash_bwd``), both passes together: ``_lib.flash_roofline``.  The
backward pass is held to five matrix products over the summed time of
whatever kernels carry it out."""
from chipbench.metrics._lib import flash_roofline as read  # noqa: F401
