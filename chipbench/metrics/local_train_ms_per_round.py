"""Device time of local training, in ms per round of the traced window:
the ops under ``fedsim.local_train`` (every ``local_train`` call of the
round body, with the collusion pass and the compressed path's quantize
and dequantize beside it).

An op's layer is the one the compiled round block's op-to-layer table
gives it (``chipbench/layers.py``).
"""
from chipbench import layers


def read(ctx):
    ms = layers.device_ms_per_round(ctx)
    return None if ms is None else ms["local_train"]
