"""Device time of the model part ``model.logits``, in ms per round of the
traced window, training and evaluation together: the tied unembedding,
in position chunks, with the loss (training) or the target probabilities
(evaluation). Forward, backward and recomputed ops alike
(``chipbench/parts.py``).
"""
from chipbench import parts


def read(ctx):
    ms = parts.ms_per_round(ctx)
    return None if ms is None else ms.get("logits")
