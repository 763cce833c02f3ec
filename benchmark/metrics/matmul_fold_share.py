"""matmul_fold_share: of the blocks the window's device programs folded, the share whose additive reduction (count,
per-aggregate counts, sums) ran as one-hot products on the MXU: the plain one-hot dot (`device_routes.fold_onehot_blocks`)
or the factored one (`fold_factored_blocks`), and not as a scatter-add (`fold_scatter_blocks`). None where the program has
no such counters, and where the window folded no block."""

from benchmark import readers

ALL = ["device_routes.fold_onehot_blocks", "device_routes.fold_factored_blocks", "device_routes.fold_scatter_blocks"]


def read(run: dict):
    return readers.stat_share(run, ALL[:2], ALL)
