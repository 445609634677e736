"""The lower-precision control: the plain reference computed one step
below the configuration's precision (three bfloat16 passes for float32
at ``highest``, bfloat16 for float32 at the default), put in the
program's place, must read not correct against the float32 reference
under each cell's limits; so must Algorithm-1's rule picking the worst
candidate.  Here at a tiny size; ``chipbench/control.py`` reads both on
the chip at the cells' own sizes."""
import numpy as np
import pytest

from _tiny import SEED, harness, tiny_cell, tiny_data
from chipbench import precision, reference


def _setup(workload):
    cell = tiny_cell(workload)
    data = tiny_data(cell)
    rec = harness.recipe(cell["config"], cell["traffic"], data.counts)
    model = harness.model_module(cell["config"])
    w0 = {k: np.asarray(v) for k, v in
          model.init_params(cell["config"]["model"],
                            harness.seed_key(SEED)).items()}
    return cell, data, rec, model, w0


@pytest.mark.parametrize("workload", ["femnist_paper_adjust",
                                      "mnist_fedavg_e5"])
def test_bfloat16_control_is_not_correct(workload):
    cell, data, rec, model, w0 = _setup(workload)
    dtype, prec = precision.control_of(cell["config"])
    control = reference.Reference(data, model, rec, dtype=dtype,
                                  precision=prec).run(
        w0, rec["checked_rounds"])
    nums = reference.Reference(data, model, rec).check(control, w0)
    limits = cell["limits"]
    over = {k: v for k, v in nums.items() if k in limits and v > limits[k]}
    assert over, nums


class _WorstPick(reference.Reference):
    rule = staticmethod(lambda q, prev_q, cur: int(np.argmin(q)))


def test_worst_candidate_picked_is_not_correct():
    cell, data, rec, model, w0 = _setup("femnist_paper_adjust")
    fault = _WorstPick(data, model, rec).run(w0, rec["checked_rounds"])
    nums = reference.Reference(data, model, rec).check(fault, w0)
    assert nums["alg1_slack"] > cell["limits"]["alg1_slack"], nums
