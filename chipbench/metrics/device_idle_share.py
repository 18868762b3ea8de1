"""1 - the union of the intervals in which an operation ran on the device,
over the traced window; averaged over the chips used."""
from chipbench.metrics._lib import idle_share as read  # noqa: F401
