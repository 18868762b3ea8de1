"""The share of the step's device time that recomputes a forward pass: the
ENTRY instructions of the compiled step whose ``op_name`` carries jax's mark of
a checkpoint's recomputation (``.../checkpoint/rematted_computation/...``: the
forward pass of an ``ht.remat()`` group run again inside the backward pass),
keyed to device events by the walk ``_scopes.py`` shares with ``_blocks.py``
(loops whole), over the by-block table's sum, the step program's busy time.
What the looped decoder pays for keeping one residual stream a layer
application.  None where no instruction carries the mark (nothing is
recomputed, a parent commit's program) or there is no table."""
from chipbench.metrics._blocks import block_ms
from chipbench.metrics._scopes import scoped_ms

MARK = "rematted_computation"


def read(ctx):
    found = scoped_ms(ctx, (MARK,), "loop_recompute_device_share")
    if found is None or block_ms(ctx, "no_op_name") is None:
        return None
    return 100.0 * found[MARK] / sum(ctx["blocks"].values())
