"""Share of the vector unit's min-plus peak that the predecessor round
kernels reach: the least time of the window's route solves over the device
self time of the ``fw_round_pred`` stripe grid and the ``fw_block_pred``
pivot closure.  A route solve does the algorithm's 2 n**3 operations
(``counts.fw_ops``) and at least reads the n x n input once and writes the
n x n distances and the n x n int32 predecessors once: 3 n**2 4-byte
entries.  Nothing is read from a program whose trace holds no
``fw_round_pred`` kernel."""

from perfbench import counts

KERNELS = ("fw_round_pred", "fw_block_pred")


def route_min_bytes(n: int) -> float:
    return 3.0 * float(n) ** 2 * 4


def least(run) -> float:
    """Least time of the window's solves at the peak."""
    n = run["out"]["n"]
    return run["out"]["solves"] * counts.least_seconds(
        counts.fw_ops(n), route_min_bytes(n), run["peak"])


def read(run):
    tr, out = run["trace"], run["out"]
    if tr is None or not out["solves"]:
        return None
    ops = dict(tr.device_ops(top=10**6))
    if "fw_round_pred" not in ops:
        return None
    return 100.0 * least(run) / sum(ops.get(k, 0.0) for k in KERNELS)
