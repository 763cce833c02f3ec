"""The reference put in the program's place with its aggregated values held at the
configuration's `control.values` first, where that is a precision that cannot hold
them (`float8_e4m3`: three mantissa bits, whole numbers up to 16, then every second,
fourth, eighth), f64 from there on. For a cell whose values bfloat16 holds exactly
(whole numbers up to 256: `values_lowprec` would pass it), and whose aggregates are
maxima, which no number of rows averages out: one value's rounding is the group's.
The text's reference module does the rounding (`partial(..., value_dtype)`)."""


def value_dtype(cfg: dict) -> str:
    return cfg["control"]["values"]


def partial_dtype(cfg: dict):
    return None
