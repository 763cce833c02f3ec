"""TSBS DevOps query `cpu-max-all-1` (timescale/tsbs, `MaxAllCPU` with 1 host): the maximum of each of the ten CPU
metrics per hour, for one host, over eight hours.

Plain reference of `benchmark/sql/tsbs_cpu_max_all_1.sql`: `tsbs_cpu_max_all_8.py`'s evaluator (which see) over its own
list of hosts, the first of the eight."""

from benchmark import needs_event_time
from benchmark.reference.tsbs_cpu_max_all_8 import HOSTS, SPEC as EIGHT, compare, merge, named_columns, partial  # noqa: F401

needs_event_time.device_time_bins_off_the_origin("tsbs_cpu_max_all_1")  # or the run ends here, exit 20: needs_event_time.py says why

SPEC = dict(EIGHT, hosts=HOSTS[:1])
