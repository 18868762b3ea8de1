"""Device time a step inside the Gated DeltaNet mixers, all layers, forward,
recomputed forward and backward: the operations under the program's scopes
``hetu_gdn_proj`` (the two input projections and the split), ``hetu_gdn_conv``
(the causal convolution), ``hetu_gdn_scan`` (gates, normalisation and the
chunked delta rule with its walk over chunk states) and ``hetu_gdn_out`` (the
gated norm and the output projection).  Which device operations count is read
from the compiled step's scopes (``_scopes.py``); the reader prints them, by
scope."""
from chipbench.metrics._scopes import scoped_ms

SCOPES = ("hetu_gdn_proj", "hetu_gdn_conv", "hetu_gdn_scan", "hetu_gdn_out")


def read(ctx):
    ms = scoped_ms(ctx, SCOPES, "gdn")
    return None if ms is None else sum(ms.values())
