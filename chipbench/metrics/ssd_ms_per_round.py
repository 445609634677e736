"""Device time of the model part ``model.ssd``, in ms per round of the
traced window, training and evaluation together: the Mamba-2 chunked
scan (``ssd_chunked``): the intra-chunk products, the chunk states and
the inter-chunk recurrence. Forward, backward and recomputed ops alike
(``chipbench/parts.py``).
"""
from chipbench import parts


def read(ctx):
    ms = parts.ms_per_round(ctx)
    return None if ms is None else ms.get("ssd")
