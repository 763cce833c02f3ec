"""What a SQL text asks of the program before a run does any work.

A text's reference module states its need when it is imported, which `run.py` does while it reads the cell (phase
`arguments`): against a program that lacks it the run ends there, with exit code 20, nothing on stdout and the reason
on the last line of stderr, as it does for a cell the manifest does not have. The other way round the run would load
its rows, answer every request by some other path and print a line under the cell's name that measures something else.

Only the program's Prometheus registry is looked at (`parseable_tpu/utils/metrics.py`: `prometheus_client` and no
more, so no JAX and no backend): a family's name is what the program publishes to whoever scrapes it."""

EXPR_AGGREGATES = "parseable_tpu_expr_aggregates"


def counted_expression_aggregates(text: str) -> None:
    """A text with an aggregate over an arithmetic expression. A program without `parseable_tpu_expr_aggregates_total`
    (before PR 30) rejects such an aggregate at plan time and hands the whole query to the CPU engine with every route
    counter at 0: `cpu_routed_blocks` reads 0, `correct` reads true, the chip stays idle, and a traced run finds no
    device operation at all (my chip run, PR 30, call B; the driver's traced run of the parent, PERF.md section 6)."""
    from parseable_tpu.utils import metrics

    if EXPR_AGGREGATES not in {family.name for family in metrics.REGISTRY.collect()}:
        raise ValueError(f"the program beside this benchmark has no counter {EXPR_AGGREGATES}_total: it does not count where an "
                         f"aggregate over an expression is evaluated, so {text} would be answered by the CPU engine unseen by "
                         "cpu_routed_blocks, and the cell would time that engine with the chip idle")
