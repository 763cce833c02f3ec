"""encode_declined_columns: counter parseable_tpu_encode_declined_total after the window, all reasons: columns that
ops/device.py could not hold on the device (a timestamp column too wide for any whole unit, sub-millisecond residue, a
nested type), each of which sent a block of every query that names it to the CPU engine. None where the program has no
such counter."""


def read(run: dict):
    samples = run["after"].get("parseable_tpu_encode_declined_total")
    return sum(samples.values()) if samples else None
