"""Test-session bootstrap.

Re-exports the recursive jaxpr introspection machinery used by the
trace-level dispatch tests (`count_primitive`, plus the collective-
scheduling helpers `jaxprs_with`/`collective_profile` that the overlap
battery uses to prove ppermutes moved off the critical path) from its
library home `repro.analysis.jaxpr_tools` — the walkers graduated from
test-only code when the replay cost model started building its task DAG
from the same jaxpr walks.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

from repro.analysis.jaxpr_tools import (collective_profile,  # noqa: F401,E402
                                        count_primitive, count_primitives,
                                        jaxprs_with)
