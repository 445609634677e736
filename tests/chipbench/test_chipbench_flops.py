"""Operation and byte counts the metrics divide by, pinned to hand counts."""
import pytest

from _tiny import ROOT, harness

FEMNIST = harness.load_json(ROOT / "chipbench/configs/femnist_cnn_paper.json")
MNIST = harness.load_json(ROOT / "chipbench/configs/mnist_cnn_fedavg.json")
CNN = harness.model_module(FEMNIST)
MFU = harness.metric_reader("round_mfu")


@pytest.mark.parametrize("config,flops,params", [
    (FEMNIST, 34_423_808, 6_603_710),
    (MNIST, 24_546_304, 1_663_370),
])
def test_forward_flops_and_params(config, flops, params):
    assert CNN.forward_flops(config["model"]) == flops
    assert CNN.num_params(config["model"]) == params


@pytest.mark.parametrize("config,rec,tests,want", [
    # paper round: 37 clients x 290 steps x 10 images, 7 evaluations
    (FEMNIST, dict(S=37, steps=290, batch_size=10, online_adjust=True,
                   criteria=["Ds", "Ld", "Md"]), 5373,
     3 * 34_423_808 * 37 * 290 * 10 + 34_423_808 * 5373 * 7),
    # FedAvg: 10 clients x 300 steps x 10 images, one boundary evaluation
    (MNIST, dict(S=10, steps=300, batch_size=10, online_adjust=False,
                 criteria=["Ds"]), 10_000,
     3 * 24_546_304 * 10 * 300 * 10 + 24_546_304 * 10_000),
    # FedSGD: one step of 586 images per client
    (FEMNIST, dict(S=37, steps=1, batch_size=586, online_adjust=True,
                   criteria=["Ds", "Ld", "Md"]), 5373,
     3 * 34_423_808 * 37 * 586 + 34_423_808 * 5373 * 7),
])
def test_round_flops(config, rec, tests, want):
    assert MFU.round_flops(CNN, config, rec, tests) == want


def test_kernel_costs():
    div = harness.metric_reader("divergence_sq_roofline")
    agg = harness.metric_reader("weighted_agg_roofline")
    assert div.cost(37, 6_603_710) == ((37 * 6_603_710 + 6_603_710) * 4,
                                       3 * 37 * 6_603_710)
    assert agg.cost(10, 1_663_370) == ((10 * 1_663_370 + 10 + 1_663_370) * 4,
                                       2 * 10 * 1_663_370)


class _Trace:
    def __init__(self, calls, secs, window=1.0):
        self._k, self.window_s, self.devices = (calls, secs), window, {"d": []}

    def kernel(self, needle):
        return self._k


PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_roofline_share_from_kernel_time():
    div = harness.metric_reader("divergence_sq_roofline")
    ctx = {"recipe": {"S": 37}, "config": FEMNIST, "peaks": PEAKS}
    least = (37 * 6_603_710 + 6_603_710) * 4 / 819e9
    got = div.read(dict(ctx, trace=_Trace(3, 6 * least)))
    assert got == pytest.approx(50.0)
    assert div.read(dict(ctx, trace=_Trace(0, 0.0))) is None
    assert div.read(dict(ctx, trace=_Trace(3, 1.0), peaks=None)) is None


def test_round_mfu_over_the_window():
    rec = dict(S=10, steps=300, batch_size=10, online_adjust=False,
               criteria=["Ds"])
    ctx = {"trace": _Trace(0, 0.0, window=2.0), "peaks": PEAKS,
           "model": CNN, "config": MNIST, "recipe": rec,
           "test_rows": 10_000, "rounds": 4}
    want = 100 * 4 * MFU.round_flops(CNN, MNIST, rec, 10_000) / 2.0 / 197e12
    assert MFU.read(ctx) == pytest.approx(want)
