"""Device time a step inside the Mamba-2 mixers, all blocks, forward,
recomputed forward and backward: the operations under the program's scopes
``hetu_ssm_proj`` (the input projection and its three parts),
``hetu_ssm_conv`` (the causal convolution), ``hetu_ssm_scan`` (the gates, the
chunked scan with its walk over chunk states, the skip) and ``hetu_ssm_out``
(the gate, the grouped norm and the output projection).  Which device
operations count is read from the compiled step's scopes (``_scopes.py``);
the reader prints them, by scope."""
from chipbench.metrics._scopes import scoped_ms

SCOPES = ("hetu_ssm_proj", "hetu_ssm_conv", "hetu_ssm_scan", "hetu_ssm_out")


def read(ctx):
    ms = scoped_ms(ctx, SCOPES, "ssm")
    return None if ms is None else sum(ms.values())
