"""Device time a step inside the Mamba-1 mixers and the Gated Memory Units,
forward, recomputed forward and backward: the operations under the program's
scopes ``hetu_ssm_proj``, ``hetu_ssm_conv``, ``hetu_ssm_scan`` (the softplus,
the selective scan's kernels, the skip), ``hetu_ssm_out`` and ``hetu_gmu``
(``_scopes.py``); the reader prints them, by scope."""
from chipbench.metrics._scopes import scoped_ms

SCOPES = ("hetu_ssm_proj", "hetu_ssm_conv", "hetu_ssm_scan", "hetu_ssm_out",
          "hetu_gmu")


def read(ctx):
    ms = scoped_ms(ctx, SCOPES, "ssm")
    return None if ms is None else sum(ms.values())
