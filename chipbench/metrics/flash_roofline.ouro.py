"""``flash_roofline`` where whole layers are recomputed: the Ling-3.0 cell's
reader (causal, each pass REQUIRED once a layer application and step: the
builder's ``attention_passes``, ``P x k``, x the traced steps, whatever the
forward calls seen; the measured time holds a forward pass run again inside
the backward pass, which earns nothing).  With no ``score_dim`` stated its
operations and bytes are ``flops.flash_pass``'s at the one head size, 128
here."""
from chipbench.run import reader

read = reader("flash_roofline", "ling3")
