"""One column of the Time Series Benchmark Suite's `cpu-only` use case (timescale/tsbs, `tsbs_generate_data
--use-case=cpu-only`), named by `gen.field`: a flat row of `time`, ten tags and ten CPU fields, as TSBS's ClickHouse,
QuestDB and Influx targets lay it.

Every host reports once every `cfg["interval_ms"]`, the hosts in their own order inside a tick (`host_0` ..
`host_<scale-1>`), so a minute of ingest holds `rows_per_minute // scale` whole ticks in `time` order: row r of minute m
is host `r % scale` at tick `m * ticks + r // scale`. The stream's last tick is the one before `cfg["time_end_ms"]`: at
the configuration's own size (8,640 ticks, one day) the first is `cfg["time_start_ms"]` exactly, as TSBS has it, and a
stream cut shorter (the stated fallback, a rehearsal, a test's few rows) keeps the day's end, which is where the texts'
windows lie, as a dashboard's look-back from now does. The ten tags are fixed per host, drawn once from the seed. Each
field is the host's clamped random walk (TSBS `ClampedRandomWalkDistribution`: start uniform in 0..100, step
normal(0, 1), clamped to 0..100 after every step), written as the whole number nearest the walk's state; the state itself
stays a float, so a minute's walks start exactly where the earlier minutes leave them.

That makes a minute depend on the steps of every earlier one, which no single `rng` holds. The seed is therefore read
off the generator the harness hands over (`gen.gen_minute` makes it from `[seed, minute]`), every minute's steps come
from a generator of their own made from (seed, minute), and the state at a minute's end is memoised: asked for minute
by minute, as the loader and the reference do, each minute is walked once; asked out of order, the walk is redone from
the start. A minute stays a function of (configuration, seed, minute). The harness's `rng` itself is not drawn from.

The value lists of the tags and the walk's parameters are TSBS's as remembered (the configuration's `from_memory` says
so): this machine has no network."""

import numpy as np

PER_MINUTE = False

REGIONS = {
    "us-east-1": ["us-east-1a", "us-east-1b", "us-east-1c", "us-east-1e"],
    "us-west-1": ["us-west-1a", "us-west-1b"],
    "us-west-2": ["us-west-2a", "us-west-2b", "us-west-2c"],
    "eu-west-1": ["eu-west-1a", "eu-west-1b", "eu-west-1c"],
    "eu-central-1": ["eu-central-1a", "eu-central-1b"],
    "ap-southeast-1": ["ap-southeast-1a", "ap-southeast-1b"],
    "ap-southeast-2": ["ap-southeast-2a", "ap-southeast-2b"],
    "ap-northeast-1": ["ap-northeast-1a", "ap-northeast-1c"],
    "sa-east-1": ["sa-east-1a", "sa-east-1b", "sa-east-1c"],
}
TAGS = {
    "region": list(REGIONS),
    "datacenter": [dc for dcs in REGIONS.values() for dc in dcs],
    "rack": [str(i) for i in range(100)],
    "os": ["Ubuntu16.10", "Ubuntu16.04LTS", "Ubuntu15.10"],
    "arch": ["x64", "x86"],
    "team": ["SF", "NYC", "LON", "CHI"],
    "service": [str(i) for i in range(20)],
    "service_version": ["0", "1"],
    "service_environment": ["production", "staging", "test"],
}
FIELDS = ("usage_user", "usage_system", "usage_idle", "usage_nice", "usage_iowait", "usage_irq", "usage_softirq",
          "usage_steal", "usage_guest", "usage_guest_nice")
# what tells one of the seed's generators from another, and from the harness's own [seed, minute]
TAG_STREAM, START_STREAM, STEP_STREAM = 1601, 1602, 1603


def seed_of(rng) -> int:
    """The run's seed, off the generator `gen.gen_minute` made from `[seed, minute]`."""
    return int(rng.bit_generator.seed_seq.entropy[0])


def host_tags(seed: int, scale: int) -> dict:
    """{tag: one code a host}: drawn once from the seed, the datacenter among its region's."""
    rng = np.random.default_rng([seed, TAG_STREAM])
    out = {name: rng.integers(0, len(values), scale) for name, values in TAGS.items() if name != "datacenter"}
    first = np.cumsum([0] + [len(dcs) for dcs in REGIONS.values()])
    sizes = np.diff(first)
    out["datacenter"] = first[out["region"]] + rng.integers(0, 2**31, scale) % sizes[out["region"]]
    return out


_tags: list = [None, None]  # (seed, scale) and their tags
_walk: dict = {}  # the one walk kept: "key" (seed, scale, ticks), "minute" the last one walked, "state" at its end, "rows" its states


def walk(seed: int, scale: int, ticks: int, minute: int) -> np.ndarray:
    """The walks' states in `minute`, float32 [ticks, scale, len(FIELDS)]."""
    key = (seed, scale, ticks)
    if _walk.get("key") == key and _walk["minute"] == minute:
        return _walk["rows"]
    if _walk.get("key") != key or _walk["minute"] != minute - 1:
        _walk.clear()
        _walk.update(key=key, minute=-1, rows=None,
                     state=np.random.default_rng([seed, START_STREAM]).uniform(0, 100, (scale, len(FIELDS))).astype(np.float32))
    while _walk["minute"] < minute:
        m = _walk["minute"] + 1
        steps = np.random.default_rng([seed, m, STEP_STREAM]).standard_normal((ticks, scale, len(FIELDS)), dtype=np.float32)
        rows, state = np.empty_like(steps), _walk["state"]
        for t in range(ticks):
            state = np.clip(state + steps[t], 0, 100)
            rows[t] = state
        _walk.update(minute=m, state=state, rows=rows)
    return _walk["rows"]


def draw(col, cfg, rng, minute, n):
    field, scale = col["gen"]["field"], cfg["scale"]
    ticks = -(-n // scale)  # a rehearsal's short minute still holds whole ticks of its first hosts
    row = np.arange(n)
    if field == "time":
        return cfg["time_end_ms"] - ((cfg["minutes"] - minute) * ticks - row // scale) * cfg["interval_ms"]
    if field == "hostname":
        return row % scale
    seed = seed_of(rng)
    if field in TAGS:
        if _tags[0] != (seed, scale):
            _tags[:] = (seed, scale), host_tags(seed, scale)
        return _tags[1][field][row % scale]
    states = walk(seed, scale, ticks, minute)[:, :, FIELDS.index(field)]
    return np.rint(states).reshape(-1)[:n].astype(np.float64)


def dictionary(col, minute):
    field = col["gen"]["field"]
    if field == "hostname":
        return [f"host_{i}" for i in range(col["gen"]["scale"])]
    if field not in TAGS:
        raise ValueError(f"column {field} holds values, not codes of a dictionary")
    return list(TAGS[field])


def cardinality(col):
    return len(dictionary(col, 0))


def arrow(col, minute, values):
    import pyarrow as pa

    field = col["gen"]["field"]
    if field == "time":
        return pa.array(values, pa.timestamp("ms"))
    if field in FIELDS:
        return pa.array(values)
    return pa.array(dictionary(col, minute), pa.string()).take(pa.array(values))
