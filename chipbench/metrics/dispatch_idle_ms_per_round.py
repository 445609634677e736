"""Device-idle time while the host is in ``fedsim.dispatch`` (``run``
building a block's round ids and dispatching the round block; a compile
in the window would show here), in ms per round of the traced window.

Idle is split time-weighted by the innermost ``fedsim.*`` host span
over it (``chipbench/layers.py``).
"""
from chipbench import layers


def read(ctx):
    ms = layers.idle_ms_per_round(ctx)
    return None if ms is None else ms.get("dispatch", 0.0)
