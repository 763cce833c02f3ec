"""parseable_tpu — a TPU-native observability data lake.

A from-scratch re-design of the capabilities of parseablehq/parseable
(reference: /root/reference, Rust) for TPU hardware:

- Schema-on-write JSON / OTel ingest over HTTP -> Arrow record batches.
- Minute-bucketed Arrow IPC staging on local disk, compacted to Parquet and
  uploaded to object storage (the source of truth) with a stats-bearing
  manifest/snapshot catalog (reference: src/catalog/).
- SQL queries over the union of staging + hot tier + object-store Parquet,
  with time/min-max pruning — but the *execution operators* (filter,
  projection, hash-aggregate, sort/top-k, distinct-count) run as JAX/Pallas
  kernels on TPU over columnar buffers instead of a CPU vectorized engine.
- Distributed deployments: N ingestors + M queriers coordinating through
  object-store metadata; partial aggregates merge over a `jax.sharding.Mesh`
  with psum/all_gather collectives instead of querier-side merge loops.

Layer map mirrors SURVEY.md (L0 storage .. L8 CLI); see each subpackage.
"""

__version__ = "0.1.0"

# Internal stream names (reference: src/parseable/mod.rs internal stream consts)
INTERNAL_STREAM_NAME = "pmeta"
FIELD_STATS_STREAM_NAME = "pstats"

# Reserved column names added to every event
# (reference: src/utils/arrow/mod.rs:99-150 add_parseable_fields)
DEFAULT_TIMESTAMP_KEY = "p_timestamp"

# Sync intervals (reference: src/lib.rs:79-85)
STORAGE_UPLOAD_INTERVAL = 30  # seconds: staging parquet -> object store
LOCAL_SYNC_INTERVAL = 60  # seconds: arrows flush -> parquet conversion
OBJECT_STORE_DATA_GRANULARITY = 1  # minutes per object-store prefix slot
