"""One command, one cell, one run.

    python3 -m grid.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that refuses to start where JAX finds no TPU or fewer chips
than the cell asks for, builds the model on the device from ``--seed``,
warms only the cell's own shapes, measures for ``--seconds``, checks
correctness outside the window and prints ONE JSON object as the last line
of its standard output: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, when traced ``breakdown``, and last ``compared``: each number
the driver's ``check`` held to a limit, as ``[number, limit]`` under a short
plain name, which are also the last lines on standard error. Earlier lines
(one JSON object each, ``{"note": ...}``) carry whatever else is worth
reading. ``--trace 0`` gives the cell's end-to-end metrics; ``--trace 1``
profiles a few seconds after the window and gives its per-layer metrics.

The compile cache is JAX's persistent one, at ``JAX_COMPILATION_CACHE_DIR``
where that is set and else at ``<checkout>/.jax_cache``
(``paddle_tpu/compile_cache.py`` places it; the grid sets none of its own).
"""

import time

_T_START = time.perf_counter()   # before any heavy import: set-up counts

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

EXIT_NO_ACCELERATOR = 3
TRACE_SECONDS = 4.0


class Job:
    """What a driver is handed: the cell's files and the run's arguments."""

    def __init__(self, cell, args, meter, profiler):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.chips = cell.chips
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace_seconds = TRACE_SECONDS
        self.meter = meter
        self.profiler = profiler

    @staticmethod
    def log(doc) -> None:
        print(json.dumps({"note": doc}, default=str), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from . import manifest, runtime

    cell = manifest.Cell(args.workload)
    try:
        device = runtime.require_chips(cell.chips)
    except runtime.NoAccelerator as e:
        print("grid.run: %s" % e, file=sys.stderr)
        return EXIT_NO_ACCELERATOR
    from . import flops, reduce

    peaks = flops.device_peaks(device["kind"])   # unknown device: an error
    import paddle_tpu  # noqa: F401  (places the compile cache)

    trace_dir = os.path.join(manifest.ROOT, "grid_out", args.workload,
                             "trace") if args.trace else None
    job = Job(cell, args, runtime.CompileMeter(),
              runtime.Profiler(trace_dir))
    job.log({"workload": cell.name, "seed": args.seed, "device": device,
             "compile_cache_dir": paddle_tpu.compile_cache.compile_cache_dir()})
    record = manifest.driver(cell.kind).run(job)
    record["peaks"] = peaks
    record["setup_s"] = record["marks"]["open"] - _T_START
    record["first_compile_s"] = job.meter.seconds

    trace = None
    spent = {}     # seconds after the traced stretch, by what took them
    if args.trace:
        t0 = time.perf_counter()
        trace = reduce.load(reduce.find_xplane(trace_dir))
        record["trace_window"] = reduce.window(trace)
        spent["load_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    metrics = {}
    for name in cell.reported(bool(args.trace)):
        spec = cell.metrics[name]
        # a reader that finds nothing returns nothing; one that raises
        # fails the run, with no last line
        value = manifest.reader(spec["reader"])(record, trace)
        if value is not None:
            metrics[name] = {"value": value, "unit": spec["unit"]}
    spent["readers_s"] = time.perf_counter() - t0
    device["memory_peak_bytes"] = sum(record["memory"].values())
    last = {"correct": bool(record["correct"]),
            "attempted": int(record["attempted"]),
            "failed": int(record["failed"]), "metrics": metrics,
            "device": device}
    if trace is not None:
        lo, hi = record["trace_window"]
        device["busy_s"] = reduce.busy_seconds(trace, (lo, hi))
        device["window_s"] = hi - lo
        t0 = time.perf_counter()
        last["breakdown"] = reduce.breakdown(trace, (lo, hi))
        spent["breakdown_s"] = time.perf_counter() - t0
        job.log({"phase": "reduce", "device_ops": sum(
            len(v) for v in trace.ops.values()),
            "grid_spans": len(trace.spans), **spent})
    # each number compared beside its limit: last in the line, and the
    # last lines on standard error
    compared = record.get("compared") or {}
    last["compared"] = compared
    job.log({k: record[k] for k in ("problems", "compiles", "setup_s",
                                    "first_compile_s", "memory",
                                    "generator_late_ms",
                                    "reference_margins", "loss")
             if k in record})
    from .readers import window as window_readers

    job.log(window_readers.summary(record))
    print(json.dumps(last), flush=True)
    for name, (value, limit) in compared.items():
        print("grid.run: compared %s %r limit %r" % (name, value, limit),
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
