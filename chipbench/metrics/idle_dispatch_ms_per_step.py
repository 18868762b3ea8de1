"""Device-idle ms a step from the jitted call's start until the step program
runs on the device: the idle time under the program's ``dispatch`` span, and
under ``fetch`` before the program's start (``_phases``)."""
from chipbench.metrics._phases import reader

read = reader("dispatch")
