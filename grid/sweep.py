"""Finds a serving mix's capacity and the rates below it, ONCE, on the chip.

    python3 -m grid.sweep --workload <serve cell> --seed 7 --seconds 40 \
        [--fractions 0.6,0.7,0.8,0.9]

One process, any serve cell: the driver is the one ``grid/run.py`` would
take (``manifest.driver(cell.kind)``) and every window goes through that
module's ``build``, ``warm`` and ``drive`` and, where it has one, its
``plan`` (``drivers/serve_moe.plan`` owns the order of lengths), with the
cell's own ``preroll_s``. The engine is built and warmed once. Then:

* a BACKLOG window (uniform arrivals above what the server takes) gives
  the capacity of the mix: tokens a second completed over the mean output
  length. The server was FULL in it where a queue stood: not empty at the
  end of more than ``EMPTY_SHARE_MAX`` of the window's cycles, longer at
  the window's end than at its opening, nothing refused. (Not a slot
  count: a slot is empty between a retirement and the next admission's
  prefill.) The first window offers ``BACKLOG_RATE``; one that was not
  full is run again at twice the rate, as far as the queue has room: the
  backlog grows by (offered - served) x (pre-roll + window) requests and
  has to stay under ``QUEUE_ROOM`` of the configuration's ``max_queue``.
  Where no window within that room was full the sweep FAILS: it prints no
  capacity from a server that was not full;
* one Poisson window at each of ``--fractions`` of that capacity shows how
  many slots are busy, and where the queue stops emptying.

Prints one JSON line a window and one for the capacity. The rate a cell
then runs at is written BY HAND, as a number, into its traffic file
(``arrivals.rate_per_s``, the sweep's line quoted in ``rate_from``): 1.25
x capacity for a ``-sat`` cell, the stated fraction for a steady one. The
benchmark itself never searches.

When a rate is found again: after any accepted gain that takes a ``-sat``
cell's ``slot_occupancy_mean`` under 0.9 of its slots or leaves its queue
empty at the window's end, or a steady cell's occupancy under half its
slots, by a ``benchmark`` PR, for every cell of that configuration at once.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys
from typing import Any, Callable, Dict, Tuple

BACKLOG_RATE = 30.0       # requests/s the first backlog window offers
EMPTY_SHARE_MAX = 0.05    # of a full window's cycles may end on no queue
QUEUE_ROOM = 0.9          # of max_queue the backlog may grow to


class NotFull(RuntimeError):
    """No backlog window within the queue's room kept the server full."""


def standing(out: Dict[str, Any]) -> bool:
    """Whether a queue stood all through the window ``out`` describes."""
    return (out["queue_empty_cycle_share"] <= EMPTY_SHARE_MAX
            and out["queue_depth_at_close"] > out["queue_depth_at_open"]
            and not out["refused"])


def find_capacity(window: Callable[[float, str], Dict[str, Any]],
                  mean_out: float, max_queue: int, span_s: float
                  ) -> Tuple[float, Dict[str, Any]]:
    """Backlog windows at rising rates until a queue stands in one; its
    requests a second served, and its line. ``span_s`` is pre-roll plus
    window: what the backlog grows over."""

    def ceiling(served: float) -> float:
        return served + QUEUE_ROOM * max_queue / span_s

    rate = min(BACKLOG_RATE, ceiling(0.0))
    while True:
        out = window(round(rate, 3), "backlog")
        served = out["serve_tokens_per_s"] / mean_out
        if standing(out):
            return served, out
        higher = min(2.0 * rate, ceiling(served))
        if out["refused"] or higher < 1.05 * rate:
            raise NotFull(
                "the server was not full in the backlog window at %.3f "
                "requests/s (queue empty after %.1f%% of its cycles, %d at "
                "its opening and %d at its end, %d refused, %.3f requests/s "
                "served) and max_queue %d leaves no room for a higher rate "
                "over %.0f s: no capacity to print"
                % (rate, 100 * out["queue_empty_cycle_share"],
                   out["queue_depth_at_open"], out["queue_depth_at_close"],
                   out["refused"], served, max_queue, span_s))
        rate = higher


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--fractions", default="")
    args = ap.parse_args(argv)

    from . import generate, manifest, runtime
    from .readers import window as rd
    from .run import Job

    cell = manifest.Cell(args.workload)
    runtime.require_chips(cell.chips)
    import paddle_tpu  # noqa: F401

    driver = manifest.driver(cell.kind)
    if not hasattr(driver, "drive"):
        raise SystemExit("grid.sweep: %s is a %r cell; only a cell whose "
                         "driver serves requests has a rate to find"
                         % (cell.name, cell.kind))
    plan_of = getattr(driver, "plan", generate.serve_plan)
    job = Job(cell, args, runtime.CompileMeter(), runtime.Profiler(None))
    engine = driver.build(job)
    vocab = int(cell.config["vocab_size"])
    preroll_s = float(cell.traffic["preroll_s"])
    mean_out = float(generate.stratified(
        cell.traffic["output_len"], 1000).mean())
    with engine:
        driver.warm(engine, vocab)

        def window(rate, label):
            traffic = copy.deepcopy(cell.traffic)
            traffic["arrivals"].update(rate_per_s=rate)
            if label == "backlog":
                traffic["arrivals"].update(process="uniform")
            plan = plan_of(traffic, vocab, args.seed, args.seconds, 0.0)
            record = driver.drive(engine, plan, args.seconds, preroll_s,
                                  0.0, job.profiler, lambda doc: None)
            record["min_tokens_for_gap"] = traffic["min_tokens_for_gap"]
            record["kind"] = "serve"
            out = {"window": label, "rate_per_s": rate,
                   "offered_tokens_per_s": rate * mean_out,
                   "serve_tokens_per_s": rd.serve_tokens_per_s(record),
                   "tpot_p50_ms": rd.tpot_p50_ms(record),
                   "tpot_p95_ms": rd.tpot_p95_ms(record),
                   "queue_wait_ms_p50": rd.queue_wait_ms_p50(record),
                   "decode_dispatch_ms_mean":
                       rd.decode_dispatch_ms_mean(record),
                   "prefill_ms_mean": rd.prefill_ms_mean(record)}
            out.update(rd.summary(record))
            print(json.dumps(out), flush=True)
            # the next window starts on an empty server: what waits is shed
            # (it never held a slot or a page), what runs is finished
            engine.scheduler.drain_queue()
            engine.run()
            return out

        try:
            capacity, full = find_capacity(
                window, mean_out, engine.cfg.max_queue,
                preroll_s + args.seconds)
        except NotFull as e:
            print("grid.sweep: %s" % e, file=sys.stderr)
            return 1
        print(json.dumps({
            "capacity_requests_per_s": capacity,
            "capacity_tokens_per_s": full["serve_tokens_per_s"],
            "mean_output_tokens": mean_out,
            "backlog_rate_per_s": full["rate_per_s"],
            "slot_occupancy_mean": full["slot_occupancy_mean"],
            "slots": engine.cfg.slots, "driver": driver.__name__,
            "plan": "%s.%s" % (plan_of.__module__, plan_of.__name__),
            "seed": args.seed, "seconds": args.seconds,
            "preroll_s": preroll_s}), flush=True)
        for f in [float(x) for x in args.fractions.split(",") if x]:
            window(round(f * capacity, 3), "%.2f of capacity" % f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
