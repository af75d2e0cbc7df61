"""The unified compaction pipeline: plan -> execute -> absorb updates.

``Compactor`` is the stable public surface over the paper's three
algorithms (detect-FSP -> factorize -> verify lossless):

    comp = Compactor(detector="gfsp", backend="device")
    report = comp.run(store)          # auto-plans every class, factorizes
    report.graph                      # G' (original store untouched)
    comp.update(new_triples)          # streaming inserts, no recomputation

As of the online-compaction refactor the class is a thin facade: all
graph state lives in an immutable :class:`~repro.api.snapshot.
GraphSnapshot` and every transform (plan, execute, update, delete,
redetect) is implemented by :class:`~repro.api.snapshot.
CompactionPlanner`, which builds a *successor* snapshot instead of
mutating anything.  The facade holds exactly one reference
(``self._snapshot``) and commits each transform by swapping it -- a
single atomic attribute assignment, so concurrent readers holding
``comp.snapshot`` (or the fgraph inside it) never observe torn state.
The long-running service in ``repro.online`` drives the same planner
against its own snapshot reference; this class keeps the one-shot
ergonomics.

* **Planning** ranks every class of the store by predicted ``#Edges``
  savings (Def. 4.8); classes whose predicted savings fall below
  ``min_predicted_savings`` are skipped -- the paper's Fig. 7
  factorization-overhead case never executes.
* **Execution** is transactional via ``core.factorize.factorize_classes``:
  the input store is never mutated, and the snapshot swaps in only after
  every class factorized successfully.
* **Incremental update / deletes** absorb streaming edits on the
  factorized form (surrogate reuse, continuing ordinals, payoff-sweep
  decompaction) with losslessness (Def. 4.10/4.11) preserved at every
  step -- each batch is one snapshot swap.
"""
from __future__ import annotations

from typing import Iterable, Sequence

from repro.core.fgraph import FactorizedGraph
from repro.core.gfsp import FSPResult
from repro.core.triples import TripleStore
from repro.online.metrics import span

from .backends import ExecutionBackend
from .detectors import Detector
# Plan/report dataclasses live with the planner now; re-exported here so
# ``from repro.api.compactor import CompactionPlan`` keeps working.
from .snapshot import (ClassPlan, CompactionPlan, CompactionPlanner,  # noqa: F401
                       CompactionReport, DeleteReport, GraphSnapshot,
                       RedetectReport, UpdateReport)


class Compactor:
    """Configurable detect -> plan -> factorize pipeline (Algorithms 1-3).

    ``detector``/``backend`` accept registered names ("gfsp"/"efsp"/
    "gspan", "host"/"device"/"sharded") or constructed strategy instances;
    ``detector_opts``/``backend_opts`` are forwarded when a name is given
    (e.g. ``backend="sharded", backend_opts={"mesh": mesh}``).

    Facade over :class:`CompactionPlanner` + one :class:`GraphSnapshot`:
    every mutating call builds a successor snapshot and commits it with
    one atomic reference swap.
    """

    def __init__(self, detector: str | Detector = "gfsp",
                 backend: str | ExecutionBackend = "host", *,
                 min_predicted_savings: int = 1,
                 surrogate_prefix: str = "repro:sg",
                 detector_opts: dict | None = None,
                 backend_opts: dict | None = None) -> None:
        self.planner = CompactionPlanner(
            detector, backend,
            min_predicted_savings=min_predicted_savings,
            surrogate_prefix=surrogate_prefix,
            detector_opts=detector_opts, backend_opts=backend_opts)
        self._snapshot: GraphSnapshot | None = None

    # -- planner configuration passthrough ---------------------------------
    @property
    def detector(self) -> Detector:
        return self.planner.detector

    @property
    def backend(self) -> ExecutionBackend:
        return self.planner.backend

    @property
    def min_predicted_savings(self) -> int:
        return self.planner.min_predicted_savings

    @property
    def surrogate_prefix(self) -> str:
        return self.planner.surrogate_prefix

    # -- detection / planning ----------------------------------------------
    def detect(self, store: TripleStore, class_id: int,
               props: Sequence[int] | None = None) -> FSPResult:
        """Run the configured detector on one class."""
        return self.planner.detect(store, class_id, props=props)

    def plan(self, store: TripleStore,
             classes: Iterable[int] | None = None, *,
             stream: bool = False) -> CompactionPlan:
        """Rank all (or the given) classes by predicted #Edges savings.
        ``stream=True`` drops the store's transient decode caches
        between classes (see :meth:`CompactionPlanner.plan`)."""
        return self.planner.plan(store, classes, stream=stream)

    # -- execution ---------------------------------------------------------
    def execute(self, store: TripleStore,
                plan: CompactionPlan) -> CompactionReport:
        """Factorize every planned class transactionally.

        The input store is never mutated; the snapshot (for ``update``/
        ``delete``) swaps in only after all classes succeed.
        """
        snap, report = self.planner.execute(store, plan)
        self._snapshot = snap
        return report

    def run(self, store: TripleStore,
            classes: Iterable[int] | None = None, *,
            stream: bool = False) -> CompactionReport:
        """plan + execute in one call (the common entry point)."""
        with span("compact.run"):
            return self.execute(store,
                                self.plan(store, classes, stream=stream))

    # -- snapshot state ----------------------------------------------------
    @property
    def snapshot(self) -> GraphSnapshot:
        """The committed immutable snapshot (fgraph + epoch)."""
        if self._snapshot is None:
            raise RuntimeError("Compactor.run()/execute() before .snapshot")
        return self._snapshot

    @property
    def fgraph(self) -> FactorizedGraph:
        """The committed factorized graph (molecule tables + CSR)."""
        if self._snapshot is None:
            raise RuntimeError("Compactor.run()/execute() before .fgraph")
        return self._snapshot.fgraph

    @property
    def graph(self) -> TripleStore:
        return self.fgraph.store

    # -- incremental path --------------------------------------------------
    def update(self, new_triples) -> UpdateReport:
        """Absorb streaming inserts into the factorized graph.

        ``new_triples``: an (n, 3) id array (shared dictionary) or an
        iterable of (subject, property, object) term triples.  New
        entities of factorized classes whose object tuple matches an
        existing star pattern are linked to its surrogate; novel tuples
        mint fresh surrogates (continuing per-class ordinals); incomplete
        molecules and unplanned classes stay raw.  No full recomputation.
        The successor snapshot commits atomically at the end.
        """
        snap, report = self.planner.apply_update(self.snapshot, new_triples)
        self._snapshot = snap
        return report

    def delete(self, triples=None, entities=None) -> DeleteReport:
        """Remove semantic triples and/or entities from the factorized
        graph transactionally.

        ``triples``: an (n, 3) id array or an iterable of term triples;
        ``entities``: an id array or an iterable of entity terms.  Both
        route through :class:`~repro.core.fgraph.FactorizedGraph` delete
        support -- molecule-covered triples dissolve memberships, and
        molecules whose support drops below payoff decompact in place.
        The successor snapshot commits only if every step succeeds.
        """
        snap, report = self.planner.apply_delete(
            self.snapshot, triples=triples, entities=entities)
        self._snapshot = snap
        return report

    def redetect(self, class_ids: Iterable[int]) -> RedetectReport:
        """Re-detect and re-factorize only the given (drifted) classes;
        see :meth:`CompactionPlanner.redetect`."""
        snap, report = self.planner.redetect(self.snapshot, class_ids)
        self._snapshot = snap
        return report
