"""Device-idle time while the host is in ``fedsim.pull`` (``run`` pulling
a block's accuracies and scalars and appending its metrics), in ms per
round of the traced window.

Idle is split time-weighted by the innermost ``fedsim.*`` host span
over it (``chipbench/layers.py``).
"""
from chipbench import layers


def read(ctx):
    ms = layers.idle_ms_per_round(ctx)
    return None if ms is None else ms.get("pull", 0.0)
