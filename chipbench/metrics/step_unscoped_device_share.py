"""The share of the step program's device time that no block of the program
owns: the table's rows ``unscoped`` (an ``op_name`` with no block's name in
it: the program forgot a scope) and ``no_op_name`` (XLA left the instruction
no metadata: no scope can reach it) over the table's sum (``_blocks.py``,
which prints the table and the largest operations of both rows)."""
from chipbench.metrics._blocks import unscoped_share as read  # noqa: F401
