"""Finds a serving mix's capacity and the rates below it, ONCE, on the chip.

    python3 -m grid.sweep --workload <serve cell> --seed <n> --seconds 20 \
        [--fractions 0.5,0.6,0.7,0.8]

One process: the engine is built and warmed once, then one window with a
standing backlog (arrivals far above capacity) gives the capacity of the
mix in requests a second (tokens a second completed over the mean output
length), and one window at each fraction of it shows where the queue
stops emptying. Prints one JSON line a window. The rate a cell then runs
at is written as a number into its traffic file: the benchmark itself
never searches.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys

BACKLOG_RATE = 30.0   # requests/s: several times any capacity, < max_queue


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--fractions", default="")
    args = ap.parse_args(argv)

    from . import generate, manifest, runtime
    from .drivers import serve
    from .readers import window as rd
    from .run import Job

    cell = manifest.Cell(args.workload)
    runtime.require_chips(cell.chips)
    import paddle_tpu  # noqa: F401

    job = Job(cell, args, runtime.CompileMeter(), runtime.Profiler(None))
    engine = serve.build(job)
    vocab = cell.config["model"]["vocab_size"]
    with engine:
        serve.warm(engine, vocab)

        def window(rate, label):
            traffic = copy.deepcopy(cell.traffic)
            traffic["arrivals"].update(rate_per_s=rate)
            if label == "backlog":
                traffic["arrivals"].update(process="uniform")
            plan = generate.serve_plan(traffic, vocab, args.seed,
                                       args.seconds)
            record = serve.drive(engine, plan, args.seconds,
                                 float(traffic["preroll_s"]), 0.0,
                                 job.profiler, lambda doc: None)
            record["min_tokens_for_gap"] = traffic["min_tokens_for_gap"]
            record["kind"] = "serve"
            out = {"window": label, "rate_per_s": rate,
                   "serve_tokens_per_s": rd.serve_tokens_per_s(record),
                   "tpot_p50_ms": rd.tpot_p50_ms(record),
                   "tpot_p95_ms": rd.tpot_p95_ms(record),
                   "decode_dispatch_ms_mean":
                       rd.decode_dispatch_ms_mean(record),
                   "prefill_ms_mean": rd.prefill_ms_mean(record)}
            out.update(rd.summary(record))
            print(json.dumps(out), flush=True)
            engine.run()    # drain before the next window
            return out

        mean_out = float(generate.stratified(
            cell.traffic["output_len"], 1000).mean())
        backlog = window(BACKLOG_RATE, "backlog")
        capacity = backlog["serve_tokens_per_s"] / mean_out
        print(json.dumps({"capacity_requests_per_s": capacity,
                          "mean_output_tokens": mean_out}), flush=True)
        for f in [float(x) for x in args.fractions.split(",") if x]:
            window(round(f * capacity, 3), "%.2f of capacity" % f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
