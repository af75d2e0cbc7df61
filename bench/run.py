"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its
configuration is the JSON file the ``configs`` entry names, its traffic
``bench/traffic/<traffic>.json``; the traffic file names the driver
(``bench/drivers/<driver>.py``) that runs it.  A driver has three
functions: ``setup(run)`` (generation, compaction, warm-up; counted as
set-up), ``window(run, state)`` (the measured loop; returns the
end-to-end values and the attempted / failed counts) and
``check(run, state)`` (the comparison with the plain reference, after
the window; returns ``(name, value, limit)`` triples).  With
``--trace 1`` the window runs under the profiler and the per-layer
metrics are read by ``bench/metrics/<metric>.py`` (``read(reading)``).

The run refuses (exit 2, no result) when JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
STATE = os.path.join(BENCH, "_state")


def load_module(path: str):
    """Import a benchmark file by path (names may hold dots)."""
    name = "bench_" + os.path.relpath(path, BENCH).replace(os.sep, "_") \
        .replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def resolve(spec: dict, workload: str) -> tuple[dict, dict, dict]:
    """(workload entry, configuration, traffic) of a cell, by name."""
    wl = {w["name"]: w for w in spec["workloads"]}.get(workload)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", wl["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return wl, cfg, traffic


def applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


@dataclasses.dataclass
class Run:
    """What a driver knows of its run."""

    workload: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    state_dir: str
    # host-clock timings and counts the driver records in the window,
    # read by the per-layer metrics
    counters: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader sees."""

    trace: object                      # bench.trace.Reduced
    counters: dict
    peaks: dict
    window_s: float


class CompileCounter:
    """Programs compiled or loaded from the persistent cache while on."""

    def __init__(self) -> None:
        import jax
        self.on = False
        self.n = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name: str, **_) -> None:
        if self.on and name == "/jax/compilation_cache/cache_hits":
            self.n += 1

    def _duration(self, name: str, _secs: float, **_) -> None:
        if self.on and name == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def _memory_peak(devices) -> int:
    """Peak device bytes on the fullest chip: the allocator's peak of
    live buffers or of what it reserved, whichever is larger."""
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)),
                   int(st.get("peak_bytes_reserved", 0)))
    return peak


def _filesystem(path: str) -> str:
    """Type of the filesystem that holds ``path`` (from /proc/mounts)."""
    path = os.path.realpath(path)
    best, kind = "", "unknown"
    try:
        with open("/proc/mounts") as f:
            for line in f:
                parts = line.split()
                if len(parts) >= 3 and path.startswith(parts[1]) \
                        and len(parts[1]) >= len(best):
                    best, kind = parts[1], parts[2]
    except OSError:
        pass
    return kind


def execute(spec: dict, wl: dict, cfg: dict, traffic: dict, *, seed: int,
            seconds: float, trace: bool, devices) -> tuple[dict, dict]:
    """Set up, measure and check one run; returns the result object and
    the driver's counters, and prints the numbers compared, each beside
    its limit, to stderr."""
    import jax
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    from bench import common, peaks, trace as tr

    if len(devices) < int(wl["chips"]):
        raise ValueError(f"{wl['name']} needs {wl['chips']} chips; the run "
                         f"has {len(devices)} devices")
    common.DEVICES[:] = devices[:int(wl["chips"])]
    state_dir = os.path.join(STATE, wl["name"])
    shutil.rmtree(state_dir, ignore_errors=True)
    os.makedirs(state_dir)
    print(f"bench: {wl['name']} seed {seed}; device "
          f"{devices[0].device_kind} x {len(devices)}; state on "
          f"{_filesystem(state_dir)}", file=sys.stderr)
    driver = load_module(os.path.join(BENCH, "drivers",
                                      traffic["driver"] + ".py"))
    run = Run(workload=wl, config=cfg, traffic=traffic, seed=seed,
              seconds=seconds, state_dir=state_dir)
    state = driver.setup(run)
    gc.collect()                 # the window starts without set-up garbage
    compiles = CompileCounter()
    trace_dir = os.path.join(state_dir, "trace")
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compiles.on = True
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        t0 = time.monotonic()
        setup_s = t0 - T_START
        out = driver.window(run, state)
        window_s = time.monotonic() - t0
    compiles.on = False
    run.counters["window_s"] = window_s
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        reduced = tr.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    memory_peak = _memory_peak(devices)
    t_check = time.monotonic()
    checks = driver.check(run, state)
    check_s = time.monotonic() - t_check
    del state
    shutil.rmtree(state_dir, ignore_errors=True)
    correct = all(v <= lim for _, v, lim in checks)

    metrics = {}
    if not trace:
        for m in spec["end_to_end"]:
            if applies(m, wl["name"]):
                v = setup_s if m["name"] == "setup_s" else out[m["name"]]
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        reading = Reading(trace=reduced, counters=run.counters,
                          peaks=peaks.peaks(devices[0].device_kind),
                          window_s=window_s)
        for m in spec["per_layer"]:
            if not applies(m, wl["name"]):
                continue
            v = load_module(os.path.join(BENCH, "metrics",
                                         m["name"] + ".py")).read(reading)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.top_ops(10),
                               "idle_gaps": reduced.idle_gaps(10)}
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in checks}
    print(f"bench: window {window_s:.3f} s, set-up {setup_s:.3f} s, "
          f"check {check_s:.3f} s, {out['attempted']} attempted, "
          f"{out['failed']} failed, generator late by up to "
          f"{run.counters.get('late_s', 0.0) * 1e3:.1f} ms, "
          f"{compiles.n} programs compiled or loaded in the window",
          file=sys.stderr)
    for n, v, lim in checks:
        print(f"check {n}: {v} (limit {lim})", file=sys.stderr)
    sys.stderr.flush()
    return result, run.counters


def chips(wl: dict):
    """The TPU devices for the cell, with the compile cache set; ``None``
    (and a message) when JAX finds no TPU or too few chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform!r})",
              file=sys.stderr)
        return None
    if len(devices) < int(wl["chips"]):
        print(f"bench: {wl['name']} needs {wl['chips']} chips, JAX has "
              f"{len(devices)}", file=sys.stderr)
        return None
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = os.path.join(BENCH, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"bench: compile cache {cache}", file=sys.stderr)
    return devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_spec()
    wl, cfg, traffic = resolve(spec, args.workload)

    devices = chips(wl)
    if devices is None:
        return 2
    result, _ = execute(spec, wl, cfg, traffic, seed=args.seed,
                        seconds=args.seconds, trace=bool(args.trace),
                        devices=devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
