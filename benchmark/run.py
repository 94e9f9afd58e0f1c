#!/usr/bin/env python3
"""The benchmark's command: one cell, one process, one JSON line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by the names in
``BENCHMARK.json``: the configuration's file, ``traffic/<traffic>.json``,
``kinds/<kind>.py`` (the configuration's ``kind``), and one reader per
metric in ``end_to_end/<name>.py`` or ``layer_metrics/<name>.py`` (or, for
``<quantity>.<cells>``, ``<quantity>.py``). See
``benchmark/README.md``. The last line of standard output is the result;
everything else worth reading is on the lines before it.

``--rehearse`` swaps in ``configs/_rehearsal-<kind>.json`` and
``traffic/_rehearsal-<kind>.json`` (tiny shapes), allows the CPU with four
virtual devices, and always reports ``correct: false``: a check of paths
and control flow, never a measurement.
"""

import time

_T0 = time.perf_counter()  # before every other import: set-up starts here

import argparse
import contextlib
import importlib.util
import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_DIR = ROOT / ".bench_trace"


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import one file of the benchmark by its path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def peaks_for(device_kind: str):
    """The published peaks of this kind of chip; unknown is an error."""
    kind = device_kind.lower()
    for entry in load_json(HERE / "peaks.json")["chips"]:
        if any(k in kind for k in entry["kinds"]):
            return entry
    raise SystemExit(f"benchmark: no published peaks for device kind "
                     f"{device_kind!r}; add it to benchmark/peaks.json")


class Run:
    """What a kind gets: the cell's data, the clock and the profiler."""

    def __init__(self, *, config, traffic, seed, seconds, trace, chips):
        self.config, self.traffic = config, traffic
        self.seed, self.seconds = seed, seconds
        self.trace, self.chips = trace, chips
        self.setup_s = None
        self.say = say

    def load(self, relative: str):
        """A module of the benchmark, e.g. ``reference/decoder_lm.py``."""
        return load_module(HERE / relative)

    def setup_done(self) -> None:
        """Called by the kind as the first measured operation starts."""
        if self.setup_s is None:
            self.setup_s = time.perf_counter() - _T0

    def span(self, name: str):
        """A host span on the profiler's clock (traced runs only)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench." + name)

    @contextlib.contextmanager
    def profile(self):
        """Trace what runs inside; the window is the ``bench.window`` span."""
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            jax.profiler.stop_trace()


def applies(metric, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def reader_of(where: str, name: str):
    """``<where>/<name>.py``; a quantity split over cells that report
    different end-to-end metrics (``device_idle_share.train``, ``.coll``)
    shares the reader named before the first dot."""
    path = HERE / where / f"{name}.py"
    if not path.exists():
        path = HERE / where / f"{name.split('.')[0]}.py"
    return load_module(path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window "
                         "(default: BENCHMARK.json's run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes, CPU allowed, correct is always false")
    args = ap.parse_args(argv)

    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"benchmark: no workload {args.workload!r}; BENCHMARK.json has "
              f"{sorted(cells)}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT / entry["file"])
    kind = config["kind"]
    traffic_name = cell["traffic"]
    if args.rehearse:
        config = load_json(HERE / "configs" / f"_rehearsal-{kind}.json")
        traffic_name = f"_rehearsal-{kind}"
    traffic = load_json(HERE / "traffic" / f"{traffic_name}.json")
    seconds = bench["run_seconds"] if args.seconds is None else args.seconds

    sys.path[:0] = [str(ROOT), str(HERE)]  # the system; flops, trace_reduce
    try:
        from mpi_tpu.utils.platform import compile_cache_dir, force_platform
    except ImportError as exc:
        print(f"benchmark: the system under test is not in this checkout "
              f"({exc})", file=sys.stderr)
        return 3
    cache = compile_cache_dir()  # before jax is imported
    import jax

    if args.rehearse:
        force_platform("cpu", 4)
    else:
        # Small programs too: a second run in a checkout compiles nothing.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"benchmark: needs a TPU; JAX found {dev.platform!r} "
              f"({dev.device_kind}). Refusing to run.", file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"benchmark: {args.workload} needs {cell['chips']} chips, JAX "
              f"found {len(devices)}.", file=sys.stderr)
        return 1
    used = devices[:cell["chips"]]
    say(f"benchmark: {args.workload} seed {args.seed} window {seconds} s "
        f"trace {args.trace}{' REHEARSAL' if args.rehearse else ''} on "
        f"{len(devices)} x {dev.device_kind} ({dev.platform}), jax "
        f"{jax.__version__}, compile cache {cache}")

    run = Run(config=config, traffic=traffic, seed=args.seed, seconds=seconds,
              trace=bool(args.trace), chips=cell["chips"])
    result = load_module(HERE / "kinds" / f"{kind}.py").run(run)
    run.setup_done()

    reading = {
        "record": dict(result["record"], setup_s=run.setup_s),
        "trace": {}, "config": config, "traffic": traffic,
        "chips": cell["chips"], "device_kind": dev.device_kind,
        "peaks": peaks_for(dev.device_kind) if dev.platform == "tpu" else None,
    }
    stats = [d.memory_stats() or {} for d in used]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(
                  s.get("peak_bytes_in_use", 0) for s in stats)}
    out = {"correct": bool(result["correct"]) and not args.rehearse,
           "attempted": result["attempted"], "failed": result["failed"]}
    if args.trace:
        import trace_reduce

        reduced = trace_reduce.reduce_dir(TRACE_DIR)  # kept to be read by hand
        reading["trace"] = reduced
        if reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            out["breakdown"] = trace_reduce.breakdown(reduced)
        group, where = "per_layer", "layer_metrics"
    else:
        group, where = "end_to_end", "end_to_end"
    metrics = {}
    for metric in bench[group]:
        if not applies(metric, args.workload):
            continue
        value = reader_of(where, metric["name"]).read(reading)
        if value is not None:  # a reader that finds nothing returns nothing
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
    out.update(metrics=metrics, device=device, workload=args.workload,
               seed=args.seed, rehearsal=args.rehearse,
               notes=dict(result.get("notes", {}), memory_stats=stats[0]))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
