# analysis-virtual-path: core/partition.py
"""LP003 bad: the core layer reaching up into engine/serving — absolute
and relative forms both resolve, at module level or inside a function."""
import repro.engine.runtime  # FLAG: LP003
from repro.gserve import server  # FLAG: LP003
from ..obs import recorder  # FLAG: LP003


def partition(g):
    return repro.engine.runtime, server, recorder, g


def traced_partition(g):
    from ..obs import get  # FLAG: LP003
    return get(), g
