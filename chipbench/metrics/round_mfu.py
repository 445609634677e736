"""The round block's share of the chip's bf16 peak: the operations the
rounds of the traced window require, over the window, over the peak.

Required per round: one training row's forward and backward passes
(the model file's ``train_flops``, by default three forward passes'
worth) for every row of every local step of the cohort, and one forward
pass over every *real* test row for each evaluation the round makes:
the boundary evaluation, and one per priority order where Algorithm-1
is on.  Padded rows and any recomputation do not count.
"""
import math

from chipbench import harness


def round_flops(model, config: dict, recipe: dict, test_rows: int) -> float:
    m = config["model"]
    train = (harness.train_flops(model, m) * recipe["S"] * recipe["steps"]
             * recipe["batch_size"])
    evals = 1 + (math.factorial(len(recipe["criteria"]))
                 if recipe["online_adjust"] else 0)
    return float(train + model.forward_flops(m) * test_rows * evals)


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if peaks is None or tr.window_s <= 0 or not tr.devices:
        return None
    flops = round_flops(ctx["model"], ctx["config"], ctx["recipe"],
                        ctx["test_rows"]) * ctx["rounds"]
    return 100.0 * flops / tr.window_s / peaks["bf16_flops"]
