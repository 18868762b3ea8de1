"""Device-idle ms a step while the caller, not the executor, holds the thread:
the idle time outside every ``run`` root of the program (``_phases``)."""
from chipbench.metrics._phases import reader

read = reader("outside_run")
