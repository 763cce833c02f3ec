"""operand_puts_per_query: device_routes.operand_puts summed over the window's responses, over their number: the host-to-device
transfers of small operands (predicate tables, time scalars, remaps) that a request's dense block loop made. None where the
program has no such counter."""

from benchmark import readers


def read(run: dict):
    return readers.stat_mean(run, ["device_routes.operand_puts"])
