"""What a SQL text that bins an event-time column asks of the program before a run does any work.

Beside `needs.py` (which see, and which a PR that adds a need may not edit): a text's reference module states its need
when it is imported, which `run.py` does while it reads the cell (phase `arguments`); against a program that lacks it the
run ends there, with exit code 20, nothing on stdout and the reason on the last line of stderr. Only the program's
Prometheus registry is looked at (`parseable_tpu/utils/metrics.py`: `prometheus_client` and no more, so no JAX and no
backend)."""

TIMEBIN_OFFORIGIN = "parseable_tpu_timebin_offorigin"


def device_time_bins_off_the_origin(text: str) -> None:
    """A text with `date_bin` / `date_trunc` over a time column that is not the partition timestamp, in a stream whose
    rows carry their own time years from the minute they were ingested in (a backfill, a replay, TSBS's bulk load). A
    program without `parseable_tpu_timebin_offorigin_total` (before PR 34) holds such a column on the device but declares
    every bin over it `UnsupportedOnDevice`: every block of every request is then folded by its CPU engine
    (`cpu_routed_blocks` counts them, `correct` reads false), the chip runs nothing, and a traced run holds no device
    operation."""
    from parseable_tpu.utils import metrics

    if TIMEBIN_OFFORIGIN not in {family.name for family in metrics.REGISTRY.collect()}:
        raise ValueError(f"the program beside this benchmark has no counter {TIMEBIN_OFFORIGIN}_total: it does not bin a time column "
                         f"that is off a block's origin on the device, so every block of {text} would be answered by the CPU "
                         "engine and the cell would time that engine with the chip idle")
