"""``flash_roofline`` of Xing4.0's six MLA layers (the MTP depth's among them),
keys 192 and values 128 wide, whole layers recomputed: the Ling-3.0 cell's
reader, which requires each pass once a layer and step (the builder's
``attention_passes`` x the traced steps) however often the forward kernel
runs, over the scores' and the values' own widths."""
from chipbench.run import reader

read = reader("flash_roofline", "ling3")
