"""Pieces the drivers share: the graph, the store, arrivals, tails."""
from __future__ import annotations

import functools
import time

import numpy as np

from bench.gen import SensorGraph

# salt that keeps the traffic's stream apart from the graph's (the graph
# is drawn from the seed itself)
TRAFFIC = 1


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def graph(cfg: dict, seed: int) -> SensorGraph:
    return SensorGraph(cfg, seed)


def load_store(g: SensorGraph):
    """The program's store over the generated terms and rows: term ids
    are positions in ``g.terms``."""
    from repro.core.triples import TermDict, TripleStore
    return TripleStore.from_ids(TermDict.from_terms(g.terms), g.spo)


# the chips of the run's cell; ``bench/run.py`` sets them before set-up
DEVICES: list = []


@functools.cache
def _mesh(devices: tuple):
    """The ``("data", "model")`` mesh of ``(len(devices), 1)``, built
    once per process."""
    from repro.launch.mesh import make_mesh
    return make_mesh((len(devices), 1), ("data", "model"),
                     devices=list(devices))


def compactor(cfg: dict):
    """The configuration's ``Compactor``; the sharded backend of a cell
    on several chips gets a mesh over all of them (FSP rows over the
    ``data`` axis)."""
    from repro.api import Compactor
    if cfg["backend"] != "sharded" or len(DEVICES) < 2:
        return Compactor(detector=cfg["detector"], backend=cfg["backend"])
    return Compactor(detector=cfg["detector"], backend=cfg["backend"],
                     backend_opts={"mesh": _mesh(tuple(DEVICES))})


def arrivals(r: np.random.Generator, rate: float, seconds: float
             ) -> np.ndarray:
    """An open loop's due times: ``rate * seconds`` Poisson arrivals over
    the window.  Every seed gets the same gaps (the exponential
    distribution's quantiles) in its own order, so seeds change when the
    load comes, not how much of it."""
    n = int(round(rate * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    r.shuffle(gaps)
    t = np.cumsum(gaps)
    return (t - t[0]) / t[-1] * seconds


def p95_ms(latencies_s) -> float:
    return float(np.percentile(np.asarray(latencies_s, float), 95) * 1e3)


def sleep_until(t0: float, t: float) -> None:
    dt = t0 + t - time.monotonic()
    if dt > 0:
        time.sleep(dt)
