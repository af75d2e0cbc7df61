"""Seeded, vectorized LinkedSensorData (SSN) graphs for the benchmark.

The same schema and draws as the sensor generator the paper's
evaluation uses: per observation an ``ssn:Observation`` with
``observedProperty / procedure / generatedBy / samplingTime /
observationResult`` arms and an ``ssn:Measurement`` with ``value / unit``
arms, 9 triples in all.  ``procedure`` and ``generatedBy`` name the same
sensor, ``unit`` follows the phenomenon, values are Zipf-distributed.

Terms are minted in a fixed order so that term ids are positions in
:attr:`SensorGraph.terms`: the two reserved terms of the store first,
then schema terms, then ``val/0 < val/1 < ...`` in value order (the BGP
filters compare value ids), then observations and measurements.  The
benchmark keeps its own copy so that no later change to the program's
generator moves the yardstick.
"""
from __future__ import annotations

import dataclasses

import numpy as np

RDF_TYPE = "rdf:type"
INSTANCE_OF = "repro:instanceOf"
OBSERVATION = "ssn:Observation"
MEASUREMENT = "ssn:Measurement"
P_PROPERTY = "ssn:observedProperty"
P_PROCEDURE = "ssn:procedure"
P_GENERATED_BY = "ssn:generatedBy"
P_TIME = "ssn:samplingTime"
P_RESULT = "ssn:observationResult"
P_VALUE = "ssn:value"
P_UNIT = "ssn:unit"
PHENOMENA = ("Temperature", "WindSpeed", "WindDirection", "RelativeHumidity",
             "Visibility", "Precipitation", "Pressure", "Rainfall", "Snowfall")
SCHEMA = (RDF_TYPE, INSTANCE_OF, OBSERVATION, MEASUREMENT, P_PROPERTY,
          P_PROCEDURE, P_GENERATED_BY, P_TIME, P_RESULT, P_VALUE, P_UNIT)


@dataclasses.dataclass
class Draws:
    """Per-observation draws: phenomenon, sensor, sampling time, value."""

    phen: np.ndarray
    sensor: np.ndarray
    time: np.ndarray
    value: np.ndarray


def draw(rng: np.random.Generator, n: int, *, n_sensors: int,
         n_timestamps: int, n_values: int, zipf_a: float) -> Draws:
    """The generator's draws, in its order."""
    phen = rng.integers(0, len(PHENOMENA), n)
    sensor = rng.integers(0, n_sensors, n)
    time = rng.integers(0, n_timestamps, n)
    value = np.minimum(rng.zipf(zipf_a, n) - 1, n_values - 1)
    return Draws(phen, sensor, time, value)


class SensorGraph:
    """A generated graph: its term list and its ``(n, 3)`` id rows."""

    def __init__(self, cfg: dict, seed: int) -> None:
        n = int(cfg["n_observations"])
        self.cfg = cfg
        self.n_obs = n
        rng = np.random.default_rng(seed)
        self.draws = draw(rng, n, n_sensors=cfg["n_sensors"],
                          n_timestamps=cfg["n_timestamps"],
                          n_values=cfg["n_values"], zipf_a=cfg["zipf_a"])
        self.terms: list[str] = list(SCHEMA)
        self.base: dict[str, int] = {}
        for prefix, names in (
                ("phenom/", PHENOMENA), ("unit/", PHENOMENA),
                ("sensor/", range(cfg["n_sensors"])),
                ("val/", range(cfg["n_values"])),
                ("time/", range(cfg["n_timestamps"]))):
            self.base[prefix] = len(self.terms)
            self.terms.extend(f"{prefix}{x}" for x in names)
        self.base["obs/"] = len(self.terms)
        self.terms.extend(f"obs/{i}" for i in range(n))
        self.base["meas/"] = len(self.terms)
        self.terms.extend(f"meas/{i}" for i in range(n))
        self.spo = self.rows(np.arange(n), self.draws)

    def id(self, term: str) -> int:
        return SCHEMA.index(term)

    def ids(self, prefix: str, idx) -> np.ndarray:
        return self.base[prefix] + np.asarray(idx, np.int64)

    def rows(self, obs: np.ndarray, d: Draws) -> np.ndarray:
        """The 9 triples of each observation ``obs[i]`` with draws ``d``,
        as id rows (observation and measurement ids from the base
        blocks; times index the ``time/`` block)."""
        o = self.ids("obs/", obs)
        m = self.ids("meas/", obs)
        sens = self.ids("sensor/", d.sensor)
        cols = [
            (o, RDF_TYPE, np.full_like(o, self.id(OBSERVATION))),
            (o, P_PROPERTY, self.ids("phenom/", d.phen)),
            (o, P_PROCEDURE, sens),
            (o, P_GENERATED_BY, sens),
            (o, P_TIME, self.ids("time/", d.time)),
            (o, P_RESULT, m),
            (m, RDF_TYPE, np.full_like(m, self.id(MEASUREMENT))),
            (m, P_VALUE, self.ids("val/", d.value)),
            (m, P_UNIT, self.ids("unit/", d.phen)),
        ]
        out = np.empty((len(cols) * len(o), 3), np.int32)
        for j, (s, p, ob) in enumerate(cols):
            out[j::len(cols), 0] = s
            out[j::len(cols), 1] = self.id(p)
            out[j::len(cols), 2] = ob
        return out

    @property
    def n_triples(self) -> int:
        return int(self.spo.shape[0])


def sort_rows(rows: np.ndarray) -> np.ndarray:
    """Unique rows in (s, p, o) order."""
    rows = np.ascontiguousarray(rows, np.int32)
    order = np.lexsort((rows[:, 2], rows[:, 1], rows[:, 0]))
    rows = rows[order]
    if rows.shape[0] > 1:
        keep = np.ones(rows.shape[0], bool)
        keep[1:] = (rows[1:] != rows[:-1]).any(axis=1)
        rows = rows[keep]
    return rows
