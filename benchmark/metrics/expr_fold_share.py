"""expr_fold_share: of the aggregate outputs over an arithmetic expression that the window's requests asked for, the share
folded inside the device program (`device_routes.expr_aggs_device`) and not evaluated by the CPU engine (`expr_aggs_host`: a
plan-time rejection, or a block the CPU engine folded). None where the window asked for no such aggregate, and where the
program has no such counters."""

from benchmark import readers

BOTH = ["device_routes.expr_aggs_device", "device_routes.expr_aggs_host"]


def read(run: dict):
    return readers.stat_share(run, BOTH[:1], BOTH)
