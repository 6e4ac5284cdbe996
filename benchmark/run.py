"""One cell of the benchmark, in one new process that holds the chip.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout. The cell is an entry of `workloads` in
`BENCHMARK.json`; its configuration, its traffic mix, its driver and
its metrics are files found by the names that entry gives (see
`benchmark/README.md`), so a new cell is new files and new entries and
no edit here.

The run: refuse anything but a TPU with enough chips; turn on the
program's persistent compile cache (inside the checkout, or where
`JAX_COMPILATION_CACHE_DIR` says); let the driver build the system from
`--seed` and warm up this cell's shapes (all of it `setup_s`); measure
for `--seconds`; check the outputs against the float32 reference
outside the window; print the contract's line last. With `--trace 1` a
profiler trace covers the first `trace_seconds` of the window and the
per-layer metrics are printed instead of the end-to-end ones.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: jax's monitoring events that mean "a program was built": a compile by
#: the backend, or one fetched from the persistent cache.
COMPILE_EVENTS = (
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


class BenchmarkError(Exception):
    """The run cannot produce a result (no TPU, bad cell, ...)."""


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


class Cell:
    """One entry of `workloads`, with the files its names point to."""

    def __init__(self, name: str) -> None:
        spec = load_json("BENCHMARK.json")
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise BenchmarkError(
                f"unknown workload {name!r}; known: {sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        cfg = {c["name"]: c for c in spec["configs"]}[self.entry["config"]]
        self.config = load_json(cfg["file"])
        self.traffic = load_json(
            "benchmark", "traffic", self.entry["traffic"] + ".json")
        self.peaks_table = load_json("benchmark", "peaks.json")

        def mine(metric: Dict[str, Any]) -> bool:
            return name in metric.get("workloads", [name])

        self.end_to_end = [m for m in spec["end_to_end"] if mine(m)]
        self.per_layer = [m for m in spec["per_layer"] if mine(m)]

    def peaks(self, device_kind: str) -> Dict[str, float]:
        if device_kind not in self.peaks_table:
            raise BenchmarkError(
                f"no peaks known for device_kind {device_kind!r}: add it "
                "to benchmark/peaks.json with its source")
        return self.peaks_table[device_kind]


def reader(package: str, metric: str):
    """The reader of a metric: `benchmark/<package>/<reader>.py`, where
    the metric's name is `<reader>` or `<reader>.<variant>` (one reader,
    several cells whose entries differ in `moves`)."""
    return importlib.import_module(
        f"benchmark.{package}.{metric.split('.')[0]}")


class Harness:
    """What a driver is given: the cell, the seed, the devices, the
    window's clock, spans on the profiler's timeline, and the switch
    that starts and stops the trace."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 devices: List[Any], scratch: str) -> None:
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = seed
        self.seconds = float(seconds)
        self.trace = trace
        self.devices = devices
        self.scratch = scratch
        self.trace_seconds = min(
            float(cell.traffic.get("trace_seconds", 3.0)), self.seconds)
        self.trace_dir = os.path.join(scratch, "trace", cell.name)
        self.setup_s: Optional[float] = None
        self.t0: Optional[float] = None      # perf_counter at window begin
        self.t1: Optional[float] = None
        self.compiles_in_window = 0
        self._in_window = False
        self._tracing = False
        self._trace_lock = threading.Lock()
        self._timer: Optional[threading.Timer] = None

    # -- spans ---------------------------------------------------------
    def span(self, name: str):
        """A host span `bench.<name>` on the profiler's clock (costs a
        few hundred nanoseconds when no trace is running)."""
        import jax

        return jax.profiler.TraceAnnotation("bench." + name)

    def _mark(self, name: str) -> None:
        with self.span(name):
            pass

    # -- the window ----------------------------------------------------
    def on_compile(self, event: str, _duration: float, **_kw: Any) -> None:
        if self._in_window and event in COMPILE_EVENTS:
            self.compiles_in_window += 1

    def trace_start(self) -> None:
        """Start the profiler (a second or more on a TPU: call it just
        before `window_begin`, off the path that feeds the system)."""
        if not self.trace or self._tracing:
            return
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # host TraceMe events only
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._tracing = True

    def window_begin(self) -> float:
        """Set-up ends here. Returns the window's first instant."""
        self.trace_start()
        if self._tracing:
            self._mark("window_begin")
            self._timer = threading.Timer(self.trace_seconds, self.trace_stop)
            self._timer.daemon = True
            self._timer.start()
        self._in_window = True
        self.t0 = time.perf_counter()
        self.setup_s = self.t0 - T_PROCESS_START
        return self.t0

    def trace_stop(self) -> None:
        with self._trace_lock:
            if not self._tracing:
                return
            import jax

            self._mark("window_end")
            jax.profiler.stop_trace()
            self._tracing = False

    def window_end(self) -> float:
        self.t1 = time.perf_counter()
        self._in_window = False
        if self._timer is not None:
            self._timer.cancel()
        self.trace_stop()
        return self.t1


class Run:
    """What a metric's reader is given."""

    def __init__(self, harness: Harness, records: Dict[str, Any],
                 device_kind: str) -> None:
        self.config = harness.config
        self.traffic = harness.traffic
        self.chips = harness.cell.chips
        self.records = records
        self.peaks = harness.cell.peaks(device_kind)
        self.trace: Optional[Dict[str, Any]] = None


def device_record(devices: List[Any]) -> Dict[str, Any]:
    """The device as jax reports it, and the peak of the fullest chip:
    `peak_bytes_in_use` (the runtime's allocator: live arrays) plus
    `peak_bytes_reserved` (the region the runtime sets aside for the
    largest program's temporaries, which the allocator's own peak leaves
    out: PR 21 read 1.6 GB for a train step the compiler sizes at
    10.5 GiB; the two together give 11.4 GB, PR 22). The two peaks need
    not fall together, so this is an upper bound, and a close one."""
    import jax

    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearsal: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Run one cell and return the result line as a dict. `rehearsal` is
    reachable only from `benchmark/tests/rehearse.py`: it lets the cell
    run at a tiny size off the chip, and its result is never printed
    under the command's name."""
    cell = Cell(workload)
    if rehearsal:
        cell.config = {**cell.config, **rehearsal.get("config", {})}
        cell.traffic = _merge(cell.traffic, rehearsal.get("traffic", {}))
    # Every program goes to the persistent cache, however quick its
    # compile: with jax's one-second floor a program near it is kept by
    # one run and not by the next (chip_smoke.py, PR 21).
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    try:
        from determined_tpu.common import compile_cache
    except ImportError as e:
        raise BenchmarkError(f"the program is not in this checkout: {e}")
    import jax

    devices = jax.devices()
    if not rehearsal:
        if devices[0].platform != "tpu":
            raise BenchmarkError(
                f"jax found no accelerator (platform "
                f"{devices[0].platform!r}): the benchmark never runs off "
                "the chip")
    if len(devices) < cell.chips:
        raise BenchmarkError(
            f"cell {workload} needs {cell.chips} chips, jax reports "
            f"{len(devices)}")
    # (a rehearsal keeps the CPU backend's programs out of the cache)
    cache_dir = compile_cache.enable() if not rehearsal else "(rehearsal)"
    entries_before = compile_cache.entry_count(cache_dir)
    scratch = os.path.join(compile_cache.cache_root(), "benchmark")
    os.makedirs(scratch, exist_ok=True)
    harness = Harness(cell, seed, seconds, trace, devices[:cell.chips],
                      scratch)
    jax.monitoring.register_event_duration_secs_listener(harness.on_compile)

    driver = importlib.import_module(
        f"benchmark.drivers.{cell.traffic['kind']}")
    records = driver.run(harness)
    if harness.t1 is None:
        raise BenchmarkError("the driver never closed the window")

    run = Run(harness, records, devices[0].device_kind if not rehearsal
              else rehearsal["peaks_of"])
    device = device_record(harness.devices)
    result: Dict[str, Any] = {
        "correct": bool(records["correct"]["ok"])
        and harness.compiles_in_window == 0,
        "attempted": int(records["attempted"]),
        "failed": int(records["failed"]),
    }
    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        from benchmark import trace_reduce

        run.trace = trace_reduce.reduce_trace(
            trace_reduce.load(trace_reduce.find_xplane(harness.trace_dir)),
            n_devices=cell.chips,
            stand_in=rehearsal.get("device_lines") if rehearsal else None)
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        for m in cell.per_layer:
            value = reader("layer_metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        result["breakdown"] = trace_reduce.breakdown(run.trace)
    else:
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                value: Optional[float] = harness.setup_s
            else:
                value = reader("end_to_end", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    # An earlier line: what the contract's line has no key for.
    print(json.dumps({
        "cell": workload, "seed": seed, "seconds": seconds, "trace": trace,
        "window_s": harness.t1 - harness.t0,
        "setup_s": harness.setup_s,
        "total_s": time.perf_counter() - T_PROCESS_START,
        "compiles_in_window": harness.compiles_in_window,
        "compile_cache": {
            "dir": cache_dir, "entries_before": entries_before,
            "entries_after": compile_cache.entry_count(cache_dir)},
        "correct": records["correct"],
        "memory_stats": {k: v for k, v in (
            harness.devices[0].memory_stats() or {}).items()
            if isinstance(v, int)},
        "notes": records.get("notes", {}),
    }), flush=True)
    return result


def _merge(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = v
    return out


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
