"""The benchmark's cells cut to a size the CPU runs in seconds."""
import copy
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

# Cut of each cell for the CPU: 2 x 2 banks of 2 subarrays, 32 x 16-word
# rows; the long shift cut to 37 columns of the 512 a row has here.
TINY_DEVICE = dict(channels=1, ranks=2, banks_per_rank=2, subarrays=2,
                   num_rows=32, words=16)
TINY_TRAFFIC = {"shift_n512": dict(shift_k=37, pool_rows=64),
                "shift_n1": dict(pool_rows=64)}


def tiny_spec(name, root=ROOT):
    import harness
    spec = harness.Spec.load(name, root)
    spec.config = copy.deepcopy(spec.config)
    spec.config["device"].update(TINY_DEVICE)
    spec.traffic = dict(spec.traffic, **TINY_TRAFFIC.get(name, {}))
    return spec
