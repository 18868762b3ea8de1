"""Device time a step of the optimiser's pass: the blocks ``hetu_optim`` (the
global-norm clip, the update, the masters' write) and ``hetu_param_cast`` (the
cast of the f32 masters to the compute type at the top of the step)
(``_blocks.py``)."""
from chipbench.metrics._blocks import block_ms


def read(ctx):
    return block_ms(ctx, "hetu_optim", "hetu_param_cast")
