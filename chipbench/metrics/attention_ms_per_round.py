"""Device time of the model part ``model.attention``, in ms per round of
the traced window, training and evaluation together: the attention
mixer: the norm before it, the q/k/v/o projections with their adapters,
the scores and the residual. Forward, backward and recomputed ops alike
(``chipbench/parts.py``).
"""
from chipbench import parts


def read(ctx):
    ms = parts.ms_per_round(ctx)
    return None if ms is None else ms.get("attention")
