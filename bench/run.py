"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python bench/run.py --workload <cell> ... --rehearse   # the CPU, tiny size

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix;
everything else is found by name:

* ``bench/configs/<config>.json``: the deployment's sizes, its source,
  its guarantee, and the tiny sizes of a CPU rehearsal;
* ``bench/traffic/<traffic>.json``: the mix's parameters, whose
  ``kind`` names the driver ``bench/traffic/<kind>.py``;
* ``bench/metrics/<metric>.py``: the reader of each per-layer metric;
* ``bench/limits/<cell>.json``: the limit of each number the cell's
  correctness check compares.

The driver makes the cell's data from ``--seed``, warms every shape the
window uses (set-up, reported as ``setup_s``), runs the window for
``--seconds``, and checks what the window produced against the plain
references of ``bench/reference.py``. With ``--trace 1`` the window is
traced and the cell's per-layer metrics are read from the trace and the
run's counters; with ``--trace 0`` the end-to-end metrics are printed.

The run needs a TPU with as many chips as the cell asks for; anywhere
else it exits 1 without a result. ``--rehearse`` runs the same driver on
the CPU at the configuration's tiny sizes, with the Pallas kernel
interpreted, and names the CPU: a rehearsal of the control flow, never a
chip result.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` (traced
runs) and ``checks``, each compared number with its limit. The checks
are also the last lines of standard error.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()                  # set-up starts with the process

import argparse
import contextlib
import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
# the benchmark's own modules (data, reference, reduce, roofline) and the
# system under test
sys.path[:0] = [str(BENCH), str(ROOT / "src")]


class BenchError(RuntimeError):
    """The cell cannot run here: missing files, wrong device."""


def load_module(path: pathlib.Path):
    """Import a file of the benchmark by path (names may hold dots)."""
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    """What a driver is given: the cell's configuration and traffic
    (rehearsal sizes already applied), its seed and where it runs."""
    name: str
    config: dict
    traffic: dict
    seed: int
    control: bool = False       # the reference in the program's place

    def seeds(self, count: int) -> list[int]:
        import data
        return data.seeds(self.seed, count)


def cell_spec(name: str, rehearse: bool):
    """``(workload entry, config, traffic, limits, benchmark)`` of a
    cell that ``BENCHMARK.json`` lists."""
    bench = read_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    config = read_json(ROOT / cfg_entry["file"])
    traffic = read_json(BENCH / "traffic" / f"{entry['traffic']}.json")
    if rehearse:
        config = {**config, **config.get("rehearsal", {})}
        traffic = {**traffic, **traffic.get("rehearsal", {})}
    limits = read_json(BENCH / "limits" / f"{name}.json")
    return entry, config, traffic, limits, bench


def metrics_for(bench: dict, cell: str, traced: bool) -> list[dict]:
    """The metrics this cell prints: its end-to-end metrics, or with a
    trace its per-layer metrics (listed for it, or unlisted and moving
    an end-to-end metric the cell reports)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [])
            or ("workloads" not in m and m["moves"] in names)]


class Tracer:
    """Profiler around the window, and the host spans drivers open."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = tempfile.mkdtemp(prefix="bench-trace-") if enabled else None

    def span(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        if not self.enabled:
            yield
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # spans come from TraceAnnotation
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()

    def reduce(self):
        import reduce
        try:
            return reduce.reduce_trace(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclasses.dataclass
class ReaderContext:
    """What a per-layer metric's reader gets."""
    trace: object               # reduce.Reduced
    counters: dict
    config: dict
    platform: str
    device_kind: str


def device_info(devices, chips: int) -> dict:
    used = devices[:chips]
    peaks = []
    for d in used:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": max(peaks)}


def compare(numbers: dict, limits: dict) -> dict:
    """Each compared number beside its limit; a number the limits file
    does not name is a fault of the benchmark."""
    missing = sorted(set(numbers) - set(limits))
    if missing:
        raise BenchError(f"no limit for {missing}")
    return {k: {"value": v, "limit": limits[k],
                "ok": bool(v <= limits[k])} for k, v in numbers.items()}


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the CPU at tiny sizes (never a chip result)")
    return ap.parse_args(argv)


def run(argv=None, *, control: bool = False, config: dict | None = None,
        traffic: dict | None = None, t0: float | None = None) -> dict:
    """One run; returns the result object (raises ``BenchError`` where
    the cell cannot run here). ``control=True`` puts the reference,
    computed a precision below the configuration's, in the program's
    place: the check must then come out false. ``config`` and
    ``traffic`` override sizes of the configuration and parameters of
    the mix (the tests' sizes)."""
    t0 = time.perf_counter() if t0 is None else t0
    args = parse(argv)
    entry, cfg, mix, limits, bench = cell_spec(args.workload, args.rehearse)
    config = {**cfg, **(config or {})}
    traffic = {**mix, **(traffic or {})}
    import jax
    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    want = "cpu" if args.rehearse else "tpu"
    if devices[0].platform != want:
        raise BenchError(f"JAX found {devices[0].platform!r} devices, not "
                         f"{want!r}" + ("" if args.rehearse else
                                        " (--rehearse runs on the CPU)"))
    if len(devices) < entry["chips"]:
        raise BenchError(f"{args.workload} needs {entry['chips']} chips, JAX "
                         f"found {len(devices)}")
    if not args.rehearse:
        from repro.platform import use_compile_cache
        use_compile_cache()
        # cache every program, however fast it compiles, so that only a
        # cell's first run in a checkout compiles
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    driver = load_module(BENCH / "traffic" / f"{traffic['kind']}.py")
    cell = Cell(args.workload, config, traffic, args.seed, control)
    state = driver.setup(cell)
    setup_s = time.perf_counter() - t0

    tracer = Tracer(bool(args.trace))
    with tracer.window():
        outcome = driver.window(state, args.seconds, tracer.span)
    device = device_info(devices, entry["chips"])
    reduced = tracer.reduce() if args.trace else None
    numbers = driver.check(state, outcome)
    del state
    gc.collect()
    checks = compare(numbers, limits)

    metrics = {}
    wanted = metrics_for(bench, args.workload, bool(args.trace))
    if args.trace:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        ctx = ReaderContext(reduced, outcome["counters"], config,
                            device["platform"], device["kind"])
        for m in wanted:
            value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {**outcome["e2e"], "setup_s": setup_s}
        for m in wanted:
            if m["name"] not in values:
                raise BenchError(f"{traffic['kind']} driver gives no "
                                 f"{m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    result = {"correct": all(c["ok"] for c in checks.values()),
              "attempted": outcome["attempted"], "failed": outcome["failed"],
              "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced.top_ops(),
                               "idle_gaps": reduced.idle_gaps()}
    result["info"] = outcome.get("info", {})
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    return result


def main(argv=None) -> int:
    try:
        result = run(argv, t0=T0)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for k, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {k} {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr, flush=True)
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
