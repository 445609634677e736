"""Device time of the criteria, in ms per round of the traced window: the
ops under ``fedsim.criteria`` (``_measure_criteria``, the
``divergence_sq`` kernel included).

An op's layer is the one the compiled round block's op-to-layer table
gives it (``chipbench/layers.py``).
"""
from chipbench import layers


def read(ctx):
    ms = layers.device_ms_per_round(ctx)
    return None if ms is None else ms["criteria"]
