"""Device-idle ms a step in ``run()``'s own bookkeeping: the idle time under
the program's ``run`` root that none of ``h2d``, ``dispatch``, ``fetch``
covers (``_phases``)."""
from chipbench.metrics._phases import reader

read = reader("run_self")
