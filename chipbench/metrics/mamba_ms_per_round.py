"""Device time of the model part ``model.mamba``, in ms per round of the
traced window, training and evaluation together: a Mamba-2 mixer less
its chunked scan: the norm before it, the input and output projections
with their adapters, the convolution, the gated norm and the residual.
Forward, backward and recomputed ops alike (``chipbench/parts.py``).
"""
from chipbench import parts


def read(ctx):
    ms = parts.ms_per_round(ctx)
    return None if ms is None else ms.get("mamba")
