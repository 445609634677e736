"""Device time of the model part ``model.mlp``, in ms per round of the
traced window, training and evaluation together: every layer's gated
MLP: the norm before it, the fused gate-and-up and the down projection
with their adapters and the residual. Forward, backward and recomputed
ops alike (``chipbench/parts.py``).
"""
from chipbench import parts


def read(ctx):
    ms = parts.ms_per_round(ctx)
    return None if ms is None else ms.get("mlp")
