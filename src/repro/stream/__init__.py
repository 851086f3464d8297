"""Streaming graph subsystem: live ingestion, epoch snapshots, standing
queries.

TIMEST's motivating workloads (fraud monitoring, social streams) are
LIVE edge streams: counts must track a sliding window without rebuilding
the world per update.  This package layers that on the existing engine:

::

    from repro.stream import StandingQuery, StreamingSession

    ss = StreamingSession(horizon=100_000)        # sliding retention
    qid = ss.subscribe(StandingQuery("M5-3", delta=4_000, k=1 << 14))

    ss.ingest(src, dst, t)          # O(batch) append, repeatedly
    er = ss.advance()               # epoch 0: snapshot + re-estimate
    res = er.results[qid]
    print(er.epoch.index, res.estimate, res.rse)

Pieces
------
``StreamStore`` (stream/store.py)
    Tiered edge store: mutable tail buffer -> immutable time-sorted
    segments (compaction merges, the sliding horizon evicts) ->
    power-of-two **padded** ``TemporalGraph`` snapshots per
    ``advance()``.
``StandingQuery`` / ``StreamingSession`` (stream/session.py)
    Register a motif+delta+budget once; every advance re-estimates it
    through a fresh ``api.Session`` over the new snapshot.  Queries
    sharing a spanning tree fuse into one dispatch per window.
``replay_edge_list`` / ``replay_epochs`` (stream/replay.py)
    Feed recorded edge-list files (text / .gz / .npz) through the store
    in bounded batches — the CLI's ``--stream-replay``.
``Wal`` (stream/wal.py)
    Checksummed, length-prefixed write-ahead log: ingest batches are
    durable before the tail mutates, advances append epoch manifests,
    and ``StreamStore.recover(path)`` replays the valid prefix (torn
    tail truncated) so a SIGKILLed server resumes bit-identically —
    the CLI's ``--serve --stream --wal PATH``.

Why padded snapshots are the tentpole: jax specializes compiled programs
on array *shapes*, so naively re-materializing a snapshot per epoch
retraces the window programs and the preprocess DP every advance.
``core.graph.pad_snapshot`` buckets every edge/vertex/pair array to
powers of two (pad entries are zero-weight suffixes that samplers
provably never select), and ``Weights`` carries the real window count
``q`` as a *traced* scalar over bucket-shaped window arrays — epochs
sharing buckets re-hit every compiled program.  The serve loop exposes
all of this over NDJSON (``{"cmd": "ingest" | "advance" | "subscribe"}``,
see ``repro.gateway``), and ``launch/estimate.py --serve --stream``
runs it as a resident process.

**Epoch determinism contract**: each standing query's count at epoch
``e`` is bit-identical to a cold ``estimate()`` on that epoch's snapshot
graph (same seed) — padding, program reuse, fusion and the store's
segment/compaction history are all invisible to the numbers.
"""
from .replay import replay_edge_list, replay_epochs
from .session import (EpochResult, StandingQuery, StreamingSession,
                      StreamStats)
from .store import Epoch, StoreStats, StreamStore
from .wal import Wal

__all__ = [
    "Epoch", "EpochResult", "StandingQuery", "StoreStats", "StreamStats",
    "StreamStore", "StreamingSession", "Wal", "replay_edge_list",
    "replay_epochs",
]
