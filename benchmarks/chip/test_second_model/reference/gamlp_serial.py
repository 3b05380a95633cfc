"""Reference of the serial test model: the program's own pre-optimisation
iteration, ``pdadmm.iterate_reference``, with naive per-trial backtracking
and no kernel dispatch. It serves the harness's tests only; a measured
configuration brings a reference that imports nothing of the program."""
from repro.core import pdadmm

init = pdadmm.init_state
iterate = pdadmm.iterate_reference
