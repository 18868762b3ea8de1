"""Device-idle ms a step while the host waits for the results and copies them:
the idle time under the program's ``fetch`` span from the step program's
start on (``_phases``)."""
from chipbench.metrics._phases import reader

read = reader("fetch")
