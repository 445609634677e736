"""Device time of Algorithm-1, in ms per round of the traced window: the
ops under ``fedsim.adjust`` (the candidate of every priority order and
its evaluation).

An op's layer is the one the compiled round block's op-to-layer table
gives it (``chipbench/layers.py``).
"""
from chipbench import layers


def read(ctx):
    ms = layers.device_ms_per_round(ctx)
    return None if ms is None else ms["adjust"]
