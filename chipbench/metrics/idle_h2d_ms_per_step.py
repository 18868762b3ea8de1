"""Device-idle ms a step while the executor canonicalises, casts and uploads
feeds: the idle time under the program's ``h2d`` span (``_phases``)."""
from chipbench.metrics._phases import reader

read = reader("h2d")
