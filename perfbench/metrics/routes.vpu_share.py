"""Share of the vector unit's min-plus peak that the route solves reach:
their least time, counted as ``routes.fw_round_pred_vpu_share`` counts it,
over the time the device was busy in the window."""

from perfbench import spec


def read(run):
    tr, out = run["trace"], run["out"]
    if tr is None or tr.busy_s <= 0 or not out["solves"]:
        return None
    share = spec.load_module("metrics", "routes.fw_round_pred_vpu_share")
    return 100.0 * share.least(run) / tr.busy_s
