"""Device time a step inside the Kimi Delta Attention mixers, all layers,
forward, recomputed forward and backward: the operations under the program's
scopes ``hetu_kda_proj`` (the five projections as one product, and beta's),
``hetu_kda_conv`` (the causal convolution), ``hetu_kda_scan`` (norms, gates
and the chunked delta rule with a decay a channel) and ``hetu_kda_out`` (the
gated norm and the output projection).  Which device operations count is read
from the compiled step's scopes (``_scopes.py``); the reader prints them, by
scope.  A program without the scopes gives nothing."""
from chipbench.metrics._scopes import scoped_ms

SCOPES = ("hetu_kda_proj", "hetu_kda_conv", "hetu_kda_scan", "hetu_kda_out")


def read(ctx):
    ms = scoped_ms(ctx, SCOPES, "kda")
    return None if ms is None else sum(ms.values())
