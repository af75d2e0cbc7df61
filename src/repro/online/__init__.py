"""Online compaction: the long-running service over snapshot swaps.

The paper's factorization is a one-shot batch transform, but Def. 4.8
makes compaction payoff a *live* quantity: inserts and deletes drift
molecule support, and the compact form decays unless frequent star
patterns are re-detected as the graph changes.  This package keeps a
:class:`~repro.api.snapshot.GraphSnapshot` continuously compacted:

* :mod:`~repro.online.wal` -- a write-ahead ingest queue batching triple
  inserts / deletes; a batch stays queued until its successor snapshot
  has swapped in, so a failed apply never loses writes;
* :mod:`~repro.online.drift` -- per-class support-drift tracking (raw-
  residue growth and sub-payoff counters maintained incrementally from
  ``UpdateReport`` / ``DeleteStats`` deltas), deciding WHICH classes are
  worth re-detecting;
* :mod:`~repro.online.metrics` -- accumulator channels (per-batch value
  + running summary) for queue depth, batch/recompaction latency,
  per-class savings, swap count; and the program's spans (``span``,
  ``SPANS``), which every layer from the store up records;
* :mod:`~repro.online.service` -- the single-writer loop tying them
  together: drain a batch, swap the successor snapshot atomically,
  re-detect ONLY the drifted classes through the candidate-batched
  sweep engine (wrapped in ``dist.fault`` retry so a failed or
  straggling re-detection never loses the queue).

Readers (``repro.serving.GraphQueryService``) hold the service's
snapshot handle and never block on any of this.

Exports resolve on first use: ``repro.core`` imports
:mod:`repro.online.metrics` for its spans, and the package's other
modules import ``repro.core`` in turn.
"""
import importlib

_EXPORTS = {
    "DriftTracker": "drift",
    "Channel": "metrics", "MetricsHub": "metrics",
    "RecoveryError": "recovery", "RecoveryReport": "recovery",
    "SnapshotCheckpointer": "recovery", "recover": "recovery",
    "BatchReport": "service", "OnlineCompactionService": "service",
    "DurableWAL": "wal", "IngestBatch": "wal", "IngestQueue": "wal",
}
__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    mod = _EXPORTS.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    return getattr(importlib.import_module(f".{mod}", __name__), name)
