"""Device time of the boundary evaluation, in ms per round of the traced
window: the ops under ``fedsim.eval`` (the evaluation that closes each
block; Algorithm-1's candidate evaluations are ``adjust``).

An op's layer is the one the compiled round block's op-to-layer table
gives it (``chipbench/layers.py``).
"""
from chipbench import layers


def read(ctx):
    ms = layers.device_ms_per_round(ctx)
    return None if ms is None else ms["eval"]
