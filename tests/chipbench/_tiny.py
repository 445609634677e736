"""A cell of the benchmark cut to a size the CPU test run holds: the
configuration and traffic files of a real cell with the client count,
data and hidden width made tiny.  Limits are the real cell's."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import data as datasets  # noqa: E402
from chipbench import harness  # noqa: E402

SEED = 2**33 + 12345          # wider than 32 bits, as run seeds may be


def tiny_cell(workload: str = "femnist_paper_adjust") -> dict:
    cell = harness.find_cell(workload)
    cfg = dict(cell["config"])
    cfg["model"] = dict(cfg["model"], hidden=16)
    ds = dict(cfg["dataset"])
    if ds["partition"] == "writers":
        ds.update(num_clients=8, mean_samples=12)
    else:
        ds.update(num_clients=6, shard_size=20, test_per_shard=5)
    cfg["dataset"] = ds
    cell["config"] = cfg
    cell["traffic"] = dict(cell["traffic"], fraction=0.5)
    return cell


def tiny_data(cell: dict):
    return datasets.load(cell["config"]["dataset"], cache=False)
