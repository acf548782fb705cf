"""Share of the vector unit's min-plus peak that the blocked FW kernels
reach: the least time of the window's solves, counted as ``cold.vpu_share``
counts it, over the device self time of the ``fw_round`` stripe grid and
the ``fw_block`` pivot closure alone, without the copies and slices around
them.  Nothing is read from a program whose kernels carry no such names."""

from perfbench import counts

KERNELS = ("fw_round", "fw_block")


def read(run):
    tr, out = run["trace"], run["out"]
    if tr is None or not out["solves"]:
        return None
    ops = dict(tr.device_ops(top=10**6))
    if "fw_round" not in ops:
        return None
    n = out["n"]
    least = out["solves"] * counts.least_seconds(
        counts.fw_ops(n), counts.fw_min_bytes(n), run["peak"])
    return 100.0 * least / sum(ops.get(k, 0.0) for k in KERNELS)
