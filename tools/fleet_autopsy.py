"""Request autopsy CLI: phase waterfalls, budgets, and breach verdicts.

    python -m tools.fleet_autopsy <trace_dir> --trace-id ID
        Replay one request out of a finished traced fleet run: print its
        phase waterfall (every attributed interval in start order —
        queue/admission/prefill/ship/decode/verify/retry/tail with cause,
        replica and attempt), the per-phase totals, and the TTFT
        decomposition checked against the engine-measured ``ttft_ms`` the
        terminal instant carries.

    python -m tools.fleet_autopsy <trace_dir> [--window] [--event-log F]
                                  [--telemetry-base D] [--json]
        Aggregate table over every request of the run: per-phase
        per-replica p50/p99/total budgets (the same fold the router
        publishes as ``fleet/phase/<name>/ms`` histograms and snapshot
        ``phases`` blocks). With --event-log, recorded ``slo_breach``
        events are joined against the ledger and one ``BreachAutopsy``
        verdict per distinct breach is printed (dominant phase, offending
        replica(s), exemplar trace_ids, actionable hint) — the offline
        twin of the verdicts the router journals at close.

    python -m tools.fleet_autopsy --selftest
        <10s, JAX_PLATFORMS=cpu: runs a traced+SLO-armed 2-replica
        process-mode sim fleet with a decode-latency fault injected into
        replica 0 only, and asserts the breach autopsy names the decode
        phase and replica 0 (exemplar trace_ids present in the merged
        timeline, verdict journaled in the event log under the run's
        run_id); that every finished request's TTFT decomposition sums to
        the engine-measured ``serving/ttft_ms`` within tolerance; and
        that the same fleet WITHOUT the fault emits zero autopsies. The
        smoke-gate entry (ROADMAP).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


# -- rendering ----------------------------------------------------------------

def _fmt_ms(v) -> str:
    return "%.2f" % v if v is not None else "-"


def waterfall(trace_dir: str, trace_id: str) -> dict:
    """Single-request phase waterfall; returns the ledger doc."""
    from paddle_tpu.fleet import autopsy

    res = autopsy.run_autopsy(trace_dir)
    led = res["ledgers"].get(trace_id)
    if led is None:
        raise SystemExit("trace_id %r not found (have %d requests; e.g. %s)"
                         % (trace_id, len(res["ledgers"]),
                            sorted(res["ledgers"])[:4]))
    t0 = min((iv.t0_us for iv in led.intervals),
             default=led.submitted_us or 0)
    if led.submitted_us is not None:
        t0 = min(t0, led.submitted_us)
    print("request %s  state=%s attempts=%d replicas=%s"
          % (led.trace_id, led.state, led.attempts, led.replicas))
    print("%10s %10s %9s  %-9s %-10s %-7s %s"
          % ("start_ms", "end_ms", "ms", "phase", "cause", "replica",
             "attempt"))
    for iv in led.intervals:
        print("%10.2f %10.2f %9.2f  %-9s %-10s %-7s %s"
              % ((iv.t0_us - t0) / 1e3, (iv.t1_us - t0) / 1e3, iv.ms,
                 iv.phase, iv.cause or "-",
                 iv.replica if iv.replica is not None else "-",
                 iv.attempt if iv.attempt is not None else "-"))
    print("phase totals: %s" % json.dumps(
        {k: round(v, 2) for k, v in led.phase_ms().items() if v > 0}))
    ttft = led.ttft_decomposition()
    print("ttft: explained=%sms (queue=%s admission=%s prefill=%s) "
          "measured=%sms  e2e=%sms"
          % (_fmt_ms(ttft["explained_ms"]), _fmt_ms(ttft["queue_ms"]),
             _fmt_ms(ttft["admission_ms"]), _fmt_ms(ttft["prefill_ms"]),
             _fmt_ms(ttft.get("measured_ttft_ms")), _fmt_ms(led.e2e_ms())))
    return led.to_doc()


def window(trace_dir: str, event_log: str = None, telemetry_base: str = None,
           as_json: bool = False) -> dict:
    """Aggregate per-phase budgets (+ breach verdicts when an event log
    is given); returns the printable doc."""
    from paddle_tpu.fleet import autopsy

    res = autopsy.run_autopsy(trace_dir, event_log=event_log,
                              telemetry_base=telemetry_base)
    stats = res["stats"]
    doc = {"requests": stats["requests"],
           "run_id": (res["manifest"] or {}).get("run_id"),
           "fleet": stats["fleet"], "replicas": stats["replicas"],
           "autopsies": [a.to_doc() for a in res["autopsies"]],
           "problems": res["problems"]}
    if as_json:
        print(json.dumps(doc, indent=1, default=str))
        return doc
    print("run %s: %d requests, %d trace problem(s)"
          % (doc["run_id"], doc["requests"], len(doc["problems"])))
    print("%-10s %-9s %6s %10s %10s %12s"
          % ("scope", "phase", "count", "p50_ms", "p99_ms", "total_ms"))
    scopes = [("fleet", stats["fleet"])]
    scopes += [("replica %s" % r, v)
               for r, v in sorted(stats["replicas"].items())]
    for scope, folds in scopes:
        for phase, st in folds.items():
            print("%-10s %-9s %6d %10.2f %10.2f %12.2f"
                  % (scope, phase, st["count"], st["p50_ms"], st["p99_ms"],
                     st["total_ms"]))
    for a in doc["autopsies"]:
        print("BREACH %s [%s%s]: dominant=%s (%.0f%% of attributed time) "
              "offenders=%s exemplars=%s\n  hint: %s"
              % (a["slo"], a["scope"],
                 "" if a["replica"] is None else ":%s" % a["replica"],
                 a["dominant_phase"], a["dominant_share"] * 100.0,
                 [o.get("replica") for o in a["offenders"]],
                 a["exemplars"], a["hint"]))
    if not doc["autopsies"]:
        print("no SLO breaches recorded%s"
              % ("" if event_log else " (no --event-log given)"))
    return doc


# -- selftest -----------------------------------------------------------------

def _drill(td: str, faulted: bool) -> dict:
    """One traced, SLO-armed 2-replica sim fleet run; with ``faulted``,
    replica 0 decodes with a 60ms injected step latency."""
    from paddle_tpu.fleet import FleetConfig, Router
    from paddle_tpu.monitor.slo import parse_slos

    tag = "faulted" if faulted else "clean"
    trace_dir = os.path.join(td, "trace_%s" % tag)
    base = os.path.join(td, "tele_%s" % tag)
    elog = os.path.join(td, "events_%s.jsonl" % tag)
    overrides = {}
    if faulted:
        overrides = {0: {"fault_plan": "serving.decode@1=latency:999:60"}}
    router = Router(FleetConfig(
        replicas=2, mode="process", affinity="round_robin",
        engine_spec={"engine": "sim", "sim": {"slots": 4, "step_ms": 2.0}},
        max_outstanding=16, trace_dir=trace_dir, telemetry_base=base,
        event_log=elog,
        slos=parse_slos("serving/request_latency_ms:p99<=150"),
        spec_overrides=overrides))
    try:
        frs = [router.submit([3, i], 8) for i in range(8)]
        assert router.wait_all(60.0), router.accounting()
        assert all(f.state == "finished" for f in frs), router.accounting()
    finally:
        router.close()  # workers flush samples -> SLO pass -> autopsy
    return {"trace_dir": trace_dir, "event_log": elog,
            "telemetry_base": base, "router": router,
            "trace_ids": [f.trace_id for f in frs]}


def selftest() -> int:
    t0 = time.perf_counter()
    from paddle_tpu.fleet import autopsy
    from paddle_tpu.fleet.events import (KIND_BREACH_AUTOPSY,
                                         KIND_SLO_BREACH, read_events)
    from paddle_tpu.monitor import metrics as mx

    mx.enable()
    # pin the workers' export interval above the run length: one final
    # flushed sample per worker -> the close()-time SLO pass judges the
    # whole run deterministically
    prev = os.environ.get("PADDLE_TPU_TELEMETRY_INTERVAL_S")
    os.environ["PADDLE_TPU_TELEMETRY_INTERVAL_S"] = "60"
    try:
        with tempfile.TemporaryDirectory() as td:
            run = _drill(td, faulted=True)

            # 1. the breach fired and the router journaled a typed
            # autopsy verdict under the same run_id
            evs = read_events(run["event_log"])
            rids = {e["run_id"] for e in evs}
            assert len(rids) == 1, rids
            breaches = [e for e in evs if e["kind"] == KIND_SLO_BREACH]
            assert breaches, "faulted run recorded no slo_breach"
            verdicts = [e for e in evs if e["kind"] == KIND_BREACH_AUTOPSY]
            assert verdicts, "no breach_autopsy journaled at close"

            # 2. every verdict names the decode phase; the replica-scope
            # verdict (and every offender ranking) names replica 0
            for v in verdicts:
                assert v["dominant_phase"] == "decode", v
                assert v["offenders"], v
                assert v["offenders"][0]["replica"] == 0, v["offenders"]
                assert "decode" in v["hint"], v["hint"]
            rep_scoped = [v for v in verdicts if v["scope"] == "replica"]
            assert rep_scoped and all(v["replica"] == 0
                                      for v in rep_scoped), verdicts

            # 3. exemplar trace_ids exist in the merged timeline's
            # request set (and on the offending replica)
            res = autopsy.run_autopsy(run["trace_dir"],
                                      event_log=run["event_log"],
                                      telemetry_base=run["telemetry_base"])
            for v in verdicts:
                assert v["exemplars"], v
                for tid in v["exemplars"]:
                    led = res["ledgers"].get(tid)
                    assert led is not None, (tid, sorted(res["ledgers"]))
                    assert 0 in led.replicas, (tid, led.replicas)

            # 4. TTFT decomposition: queue+admission+prefill explains the
            # engine-measured serving/ttft_ms for EVERY finished request
            finished = [led for led in res["ledgers"].values()
                        if led.state == "finished"]
            assert len(finished) == 8, len(finished)
            for led in finished:
                ttft = led.ttft_decomposition()
                m = ttft["measured_ttft_ms"]
                assert m is not None, led.trace_id
                tol = max(1.0, 0.05 * m)
                assert abs(ttft["explained_ms"] - m) <= tol, \
                    "request %s: explained %.3fms vs measured %.3fms" \
                    % (led.trace_id, ttft["explained_ms"], m)

            # 5. the decomposition is on the ordinary metrics surfaces:
            # fleet/phase/* histograms observed per request, and the
            # snapshot carries per-replica phase budgets with replica 0's
            # decode p50 past the injected 60ms step latency
            assert mx.histogram("fleet/phase/decode/ms").count >= 8
            snap = run["router"].snapshot()
            assert "phases" in snap and "decode" in snap["phases"], \
                sorted(snap.get("phases", {}))
            r0 = next(r for r in snap["replicas"]
                      if r["name"] == "replica-0")
            r1 = next(r for r in snap["replicas"]
                      if r["name"] == "replica-1")
            d0 = r0["phases"]["decode"]
            d1 = r1["phases"]["decode"]
            assert d0["p50_ms"] >= 60.0 > d1["p50_ms"], (d0, d1)
            assert snap.get("autopsies"), "snapshot lost the verdicts"

            # 6. the CLI renders both views without error
            waterfall(run["trace_dir"], run["trace_ids"][0])
            window(run["trace_dir"], event_log=run["event_log"],
                   telemetry_base=run["telemetry_base"])

            # 7. a clean run (same shape, no fault) emits ZERO autopsies
            clean = _drill(td, faulted=False)
            evs_clean = read_events(clean["event_log"])
            assert not [e for e in evs_clean
                        if e["kind"] == KIND_BREACH_AUTOPSY], \
                "clean run produced autopsy verdicts"
            assert not [e for e in evs_clean
                        if e["kind"] == KIND_SLO_BREACH], \
                "clean run breached"
    finally:
        if prev is None:
            os.environ.pop("PADDLE_TPU_TELEMETRY_INTERVAL_S", None)
        else:
            os.environ["PADDLE_TPU_TELEMETRY_INTERVAL_S"] = prev

    print("fleet_autopsy selftest: OK (%.1fs)  %d breach(es) -> %d "
          "verdict(s), dominant=decode@replica0 (r0 decode p50 %.0fms vs "
          "r1 %.1fms), TTFT explained within tolerance on %d requests, "
          "clean run: 0 autopsies"
          % (time.perf_counter() - t0, len(breaches), len(verdicts),
             d0["p50_ms"], d1["p50_ms"], len(finished)))
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] in ("-h", "--help"):
        print(__doc__.strip())
        return 0
    if argv and argv[0] == "--selftest":
        return selftest()

    def opt(name, default=None):
        if name in argv:
            i = argv.index(name)
            argv.pop(i)
            return argv.pop(i)
        return default

    trace_id = opt("--trace-id")
    event_log = opt("--event-log")
    telemetry_base = opt("--telemetry-base")
    as_json = "--json" in argv
    if as_json:
        argv.remove("--json")
    if "--window" in argv:
        argv.remove("--window")
    if len(argv) != 1:
        print("usage: python -m tools.fleet_autopsy <trace_dir> "
              "[--trace-id ID | --window] [--event-log F] "
              "[--telemetry-base D] [--json]", file=sys.stderr)
        return 2
    trace_dir = argv[0]
    if trace_id:
        doc = waterfall(trace_dir, trace_id)
        if as_json:
            print(json.dumps(doc, indent=1, default=str))
        return 0
    window(trace_dir, event_log=event_log, telemetry_base=telemetry_base,
           as_json=as_json)
    return 0


if __name__ == "__main__":
    sys.exit(main())
