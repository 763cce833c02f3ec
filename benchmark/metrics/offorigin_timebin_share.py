"""offorigin_timebin_share: of the blocks whose text bins (`date_bin` / `date_trunc`) a time column that is off the block's
origin (an event time years from the minute of ingest), the share binned inside the device program, in the column's own
steps (`device_routes.timebin_offorigin_device_blocks`), and not by host code (`timebin_offorigin_host_blocks`: a block
the CPU engine folded, a bin the column's unit does not divide among them). None where the window binned no such
column, and where the program has no such counters."""

from benchmark import readers

BOTH = ["device_routes.timebin_offorigin_device_blocks", "device_routes.timebin_offorigin_host_blocks"]


def read(run: dict):
    return readers.stat_share(run, BOTH[:1], BOTH)
