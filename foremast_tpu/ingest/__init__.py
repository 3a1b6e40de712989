"""Push-based metric ingest plane (ISSUE 5).

The scrape-vs-remote-write inversion: instead of the worker HTTP-GETing
every document's `query_range` URL from Prometheus each tick (the
reference brain's shape, SURVEY §3.2 — and a large share of a cold
tick's wall clock), pushers remote-write samples INTO the
worker's resident ring TSDB and a warm fetch becomes an in-memory
columnar gather — the same shape as serving an inference stack from a
resident feature store instead of a remote database.

Modules:
    wire      push payload codecs (JSON compat + FMW1 binary columnar
              frame + pure-python snappy) + query_range key resolution
    ring      per-series pow2 (int64, float32) ring buffers
    shards    sharded, byte-budgeted, LRU-evicting RingStore
    backfill  cold-miss subscriptions + fallback-result backfill
    source    RingSource(MetricSource) — what the worker mounts
    receiver  HTTP push endpoint + foremast_ingest_* exposition
    snapshot  durable shard snapshots + append logs (warm restarts)

Opt-in via `FOREMAST_INGEST=1` (docs/operations.md "Ingest plane");
durability via `FOREMAST_SNAPSHOT_DIR` (docs/operations.md "Restarts
and upgrades").
"""

from foremast_tpu.ingest.backfill import SubscriptionBook, backfill
from foremast_tpu.ingest.receiver import (
    IngestCollector,
    WireStats,
    start_ingest_server,
    stop_ingest_server,
)
from foremast_tpu.ingest.ring import SeriesRing
from foremast_tpu.ingest.shards import RingShard, RingStore
from foremast_tpu.ingest.snapshot import (
    RingSnapshotter,
    SnapshotCollector,
    lock_snapshot_dir,
)
from foremast_tpu.ingest.source import RingSource
from foremast_tpu.ingest.wire import (
    BINARY_CONTENT_TYPE,
    WireError,
    canonical_series,
    decode_frame,
    encode_frame,
    parse_push,
    resolve_query_range,
    series_key,
    snappy_compress,
    snappy_decompress,
)

__all__ = [
    "BINARY_CONTENT_TYPE",
    "IngestCollector",
    "RingShard",
    "RingSnapshotter",
    "RingSource",
    "RingStore",
    "SeriesRing",
    "SnapshotCollector",
    "SubscriptionBook",
    "WireError",
    "WireStats",
    "backfill",
    "canonical_series",
    "decode_frame",
    "encode_frame",
    "lock_snapshot_dir",
    "parse_push",
    "resolve_query_range",
    "series_key",
    "snappy_compress",
    "snappy_decompress",
    "start_ingest_server",
    "stop_ingest_server",
]
