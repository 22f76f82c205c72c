"""Chaos drills: injected-fault recovery invariants as a CI smoke gate.

    python -m tools.chaos_drill --selftest
        JAX_PLATFORMS=cpu; drills 1-4 run in-process in a few seconds,
        the fleet drill adds real worker-process spawns. Asserts the
        recovery invariants (the ROADMAP smoke-gate entry):

        1. TRAINING — an injected preemption signal mid-run makes
           run_supervised finish the in-flight fused chunk, write a
           rotating checkpoint and stop; a fresh supervised run resumes
           from it and the combined loss trajectory is BIT-IDENTICAL to an
           uninterrupted twin (dropout included — the per-step RNG counter
           is rewound on resume). A second leg injects transient dispatch
           failures and asserts bounded retry absorbs them with the same
           bit-exact trajectory.

        2. SERVING — an injected decode failure fails the in-flight batch:
           its pages return to the pool, its requests are marked FAILED,
           and the engine keeps serving (queued requests complete). A
           second leg injects page-pool exhaustion and asserts admission
           degrades to backpressure, never a crash. Page accounting must
           balance at every terminal state.

        3. SELF-HEAL — NaN-poisoned records in the shard stream trip the
           divergence sentinel: the run rolls back to the last good
           checkpoint (model + RNG counter + reader position), quarantines
           the poisoned data window (JSONL names each record) and resumes
           PAST it — final losses are BIT-IDENTICAL (hex float32) to a
           twin trained on a stream that never contained those records.

        4. EXACTLY-ONCE — a preemption mid-run + auto-resume with a FRESH
           CheckpointableReader (zero caller-side feed_source(start)
           logic): the per-step record-id ledger of the stitched run shows
           every record consumed exactly once, matching the uninterrupted
           twin's ledger.

        5. FLEET — two real-engine worker PROCESSES behind the fleet
           router; one is SIGKILLed mid-traffic. Every request reaches
           exactly one terminal state (zero silent drops, zero duplicate
           results), the requeued seeded requests replay BIT-IDENTICAL to
           an unkilled in-process twin, and a rolling restart under
           traffic terminates nothing as 'rejected'. The leg runs with
           distributed tracing + the fleet event log armed: afterwards
           the merged clock-aligned timeline must VALIDATE (killed
           attempt 1 closed synthetically + tagged, requeued attempt 2 of
           the same trace_id finished) and the event journal must carry
           the kill/requeue/restart story on one run_id. (This leg
           dominates the gate's wall time: it spawns and warms real
           workers.)

    python -m tools.chaos_drill --parse 'site@N=kind[:times[:ms]];...'
        Validate a PADDLE_TPU_FAULT_PLAN grammar string and print the
        parsed schedule.
"""

from __future__ import annotations

import os
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import numpy as np  # noqa: E402


def _bits(v) -> bytes:
    return np.float32(v).tobytes()


# -- drill 1: preemption-aware training ---------------------------------------

def _build_train():
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1234
    # fresh name scope per build: a resumed "process" regenerates the same
    # var names (in-process twin of a real restart)
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[8])
            y = fluid.layers.data("y", shape=[1], dtype="int64")
            h = fluid.layers.fc(x, size=16, act="relu")
            # dropout on purpose: resume parity must include the per-step
            # RNG stream, not just the weights
            h = fluid.layers.dropout(h, dropout_prob=0.3)
            logits = fluid.layers.fc(h, size=4)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, y))
            fluid.optimizer.Momentum(0.05, 0.9).minimize(loss)
    return main, startup, loss


def _feed_source(start):
    def gen():
        s = start
        while True:
            r = np.random.RandomState(1000 + s)
            yield {"x": r.randn(8, 8).astype("float32"),
                   "y": r.randint(0, 4, (8, 1)).astype("int64")}
            s += 1
    return gen()


def _supervised(ckpt_dir, plan=None, total=6):
    import paddle_tpu as fluid
    from paddle_tpu.reliability import FaultPlan, run_supervised

    main, startup, loss = _build_train()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        with (plan if plan is not None else FaultPlan([])):
            return run_supervised(
                exe, main, _feed_source, total, [loss],
                checkpoint_dir=ckpt_dir, fetch_every=2,
                checkpoint_every_steps=2, backoff_s=0.0,
                exit_on_preempt=False)


def drill_training(tmp) -> None:
    from paddle_tpu.reliability import FaultPlan, faults

    full = _supervised(os.path.join(tmp, "full"))
    ref = [_bits(row[0]) for row in full.losses]
    assert full.steps_done == 6 and not full.preempted, full

    # injected preemption at the 2nd fused-chunk dispatch -> checkpoint at
    # step 4 (the in-flight chunk FINISHES first), marked stop
    ck = os.path.join(tmp, "preempt")
    plan = FaultPlan([faults.FaultSpec("executor.dispatch", "preempt", at=2)])
    first = _supervised(ck, plan)
    assert first.preempted, first
    assert first.steps_done == 4, "chunk not finished before exit: %r" % first
    assert first.checkpoints_written >= 1

    second = _supervised(ck)
    assert second.resumed and second.start_step == 4, second
    assert second.steps_done == 6 and not second.preempted, second
    stitched = [_bits(r[0]) for r in first.losses] + \
               [_bits(r[0]) for r in second.losses]
    assert stitched == ref, \
        "kill/resume loss trajectory diverged from the uninterrupted run"

    # transient dispatch failures: bounded retry absorbs them and the
    # trajectory STILL matches bit-for-bit (RNG counter rewound per retry)
    plan = FaultPlan([faults.FaultSpec("executor.dispatch", "transient",
                                       at=2, times=2)])
    retried = _supervised(os.path.join(tmp, "retry"), plan)
    assert retried.retries == 2 and retried.steps_done == 6, retried
    assert [_bits(r[0]) for r in retried.losses] == ref, \
        "retry changed the loss trajectory"
    print("chaos_drill: training drill OK "
          "(preempt@chunk2 -> resume bit-exact; 2 transient retries absorbed)")


# -- drills 3+4: sentinel self-heal + exactly-once data pipeline --------------

def _write_shards(dirname, n, poison=()):
    """Two text shards of 8-float + 1-label records (deterministic per
    record index); indices in ``poison`` get all-NaN features — parseable,
    schema-valid, numerically poisonous (that is the sentinel's job, not
    the corruption quarantine's)."""
    os.makedirs(dirname, exist_ok=True)
    paths, idx, per = [], 0, n // 2
    for si in range(2):
        p = os.path.join(dirname, "shard_%d.txt" % si)
        with open(p, "w") as f:
            for _ in range(per):
                r = np.random.RandomState(4000 + idx)
                x = np.full(8, np.nan) if idx in poison else r.randn(8)
                f.write(" ".join("%r" % float(v) for v in x)
                        + " %d\n" % r.randint(0, 4))
                idx += 1
        paths.append(p)
    return paths


def _parse_rec(line):
    t = line.split()
    return {"x": np.asarray([float(v) for v in t[:8]], np.float32),
            "y": np.asarray([int(t[8])], np.int64)}


def _reader(paths, quarantine=None):
    from paddle_tpu import data

    schema = [data.FieldSpec("x", (8,), np.float32),
              data.FieldSpec("y", (1,), np.int64)]
    return data.CheckpointableReader(paths, _parse_rec, batch_size=8,
                                     schema=schema, epochs=1,
                                     quarantine_path=quarantine)


def _supervised_reader(ckpt, reader, plan=None, total=8, sentinel=None,
                       on_chunk=None):
    """Reader-fed run_supervised over the SAME model geometry as drill 1
    (batch 8 — the compile cache collapses the rebuilds)."""
    import paddle_tpu as fluid
    from paddle_tpu.reliability import FaultPlan, run_supervised

    main, startup, loss = _build_train()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        with (plan if plan is not None else FaultPlan([])):
            return run_supervised(
                exe, main, reader, total, [loss],
                checkpoint_dir=ckpt, fetch_every=2,
                checkpoint_every_steps=2, backoff_s=0.0,
                exit_on_preempt=False, sentinel=sentinel,
                on_chunk=on_chunk)


def drill_self_heal(tmp) -> None:
    import json

    from paddle_tpu.reliability import DivergenceSentinel

    # 8 steps x batch 8 = 64 committed records; poison the 16 records of
    # steps 4-5 (one fused chunk, right after the step-4 checkpoint)
    poison = set(range(32, 48))
    d_p = _write_shards(os.path.join(tmp, "heal_poison"), 80, poison)
    d_c = os.path.join(tmp, "heal_clean")
    os.makedirs(d_c, exist_ok=True)
    idx = 0
    clean = []
    for p in d_p:  # the twin's stream simply never contains the window
        q = os.path.join(d_c, os.path.basename(p))
        with open(q, "w") as f:
            for line in open(p):
                if idx not in poison:
                    f.write(line)
                idx += 1
        clean.append(q)

    qfile = os.path.join(tmp, "quarantine.jsonl")
    sent = DivergenceSentinel(nan=True, max_trips=2)
    healed = _supervised_reader(os.path.join(tmp, "ck_heal"),
                                _reader(d_p, qfile), sentinel=sent)
    twin = _supervised_reader(os.path.join(tmp, "ck_twin"), _reader(clean))
    assert len(healed.trips) == 1 and healed.trips[0].rule == "nan", healed
    assert healed.rollbacks == 1 and healed.steps_done == 8, healed
    assert healed.records_quarantined == 16, healed
    rows = [json.loads(ln) for ln in open(qfile)]
    expect = sorted("shard_%d.txt#%d" % (i // 40, i % 40)
                    for i in poison)  # 40 records per shard
    assert len(rows) == 16 and \
        sorted(r["id"] for r in rows) == expect, rows[:2]
    assert all("sentinel nan trip at step 4" in r["reason"] for r in rows)

    assert twin.steps_done == 8 and not twin.trips, twin
    hb = [_bits(r[0]) for r in healed.losses]
    tb = [_bits(r[0]) for r in twin.losses]
    assert hb == tb, \
        "healed losses not bit-identical to the never-poisoned twin"
    print("chaos_drill: self-heal drill OK (NaN window tripped the "
          "sentinel -> rollback to step 4, 16 records quarantined, "
          "healed run bit-identical to the clean twin)")


def drill_exactly_once(tmp) -> None:
    from paddle_tpu.reliability import FaultPlan, faults

    d = _write_shards(os.path.join(tmp, "once"), 80)

    def run(ckpt, plan=None):
        ledger = {}
        reader = _reader(d)  # FRESH reader: zero caller-side bookkeeping

        def on_chunk(step0, rows):
            for i, ids in enumerate(reader.last_batch_ids(len(rows))):
                ledger[step0 + i] = ids

        res = _supervised_reader(ckpt, reader, plan=plan,
                                 on_chunk=on_chunk)
        return res, ledger

    ref, ref_ledger = run(os.path.join(tmp, "ck_ref"))
    assert ref.steps_done == 8, ref

    ck = os.path.join(tmp, "ck_once")
    plan = FaultPlan([faults.FaultSpec("executor.dispatch", "preempt", at=2)])
    first, led1 = run(ck, plan)
    assert first.preempted and 0 < first.steps_done < 8, first
    second, led2 = run(ck)
    assert second.resumed and second.start_step == first.steps_done, second
    assert second.steps_done == 8 and not second.preempted, second

    stitched = dict(led1)
    stitched.update(led2)
    consumed = [rid for s in sorted(stitched) for rid in stitched[s]]
    assert sorted(stitched) == list(range(8)), sorted(stitched)
    assert len(consumed) == 64 and len(set(consumed)) == 64, \
        "records skipped or re-trained across the kill/resume boundary"
    assert stitched == ref_ledger, \
        "stitched record ledger differs from the uninterrupted twin"
    sb = [_bits(r[0]) for r in first.losses] + \
         [_bits(r[0]) for r in second.losses]
    assert sb == [_bits(r[0]) for r in ref.losses]
    print("chaos_drill: exactly-once drill OK (preempt@chunk2 + fresh-"
          "reader resume: 64 records each consumed once, ledger == twin)")


# -- drill 2: serving failure recovery ----------------------------------------

def drill_serving() -> None:
    from paddle_tpu import serving
    from paddle_tpu.models import decoder_lm
    from paddle_tpu.monitor import metrics as mx
    from paddle_tpu.reliability import FaultPlan, faults

    # one-layer toy model + a single prompt bucket: the drill exercises the
    # recovery ladder, not the model — keep every compile tiny so the gate
    # stays under its 5s budget
    cfg = decoder_lm.DecoderConfig(vocab_size=64, n_layer=1, d_model=16,
                                   n_head=2, max_seq=32)
    model = decoder_lm.DecoderLM(cfg, seed=0)
    rng = np.random.RandomState(0)

    def prompts(n):
        return [(list(rng.randint(0, 64, int(rng.randint(4, 9)))),
                 int(rng.randint(2, 7))) for _ in range(n)]

    # injected decode failure (fatal after the retry budget): the in-flight
    # batch fails, the queue still drains, the engine never dies
    eng = serving.ServingEngine(model, serving.ServingConfig(
        slots=2, page_size=8, max_seq=32, decode_retries=1))
    plan = FaultPlan([
        faults.FaultSpec("serving.decode", "transient", at=2, times=1),
        faults.FaultSpec("serving.decode", "fatal", at=4, times=1),
    ])
    with plan:
        reqs = [eng.submit(p, m) for p, m in prompts(5)]
        done = eng.run(max_steps=200)
    states = sorted(r.state for r in reqs)
    assert len(done) == len(reqs), "engine lost requests: %r" % states
    assert "failed" in states, "injected decode failure produced no FAILED"
    assert "finished" in states, "queue did not keep serving after failure"
    assert eng.pool.num_used == 0, "failed batch leaked pages"
    assert eng.page_accounting_ok()
    h = eng.health()
    assert h["faults_absorbed"] >= 1 and h["page_accounting_ok"], h
    for r in reqs:
        if r.state == "failed":
            assert r.error and not r.pages, r
    eng.close()

    # pool exhaustion: injected at alloc -> admission backpressures (the
    # request queues), pages retire, everything completes
    eng2 = serving.ServingEngine(model, serving.ServingConfig(
        slots=2, page_size=8, max_seq=32))
    blocked0 = mx.snapshot()["serving/admission_blocked_on_pages"]["value"]
    plan = FaultPlan([faults.FaultSpec("page_pool.alloc", "exhausted",
                                       at=2, times=2)])
    with plan:
        reqs2 = [eng2.submit(p, m) for p, m in prompts(4)]
        done2 = eng2.run(max_steps=200)
    assert len(done2) == len(reqs2), "exhaustion drill did not drain"
    assert all(r.state == "finished" for r in reqs2), \
        [r.state for r in reqs2]
    assert eng2.pool.num_used == 0 and eng2.page_accounting_ok()
    blocked = mx.snapshot()["serving/admission_blocked_on_pages"]["value"]
    assert blocked > blocked0, "injected exhaustion never backpressured"
    eng2.close()

    # deadline ladder: an expired request is retired TIMEOUT, not served
    eng3 = serving.ServingEngine(model, serving.ServingConfig(
        slots=2, page_size=8, max_seq=32))
    late = eng3.submit([1, 2, 3], 4, deadline_s=0.0)
    ok = eng3.submit([1, 2, 3], 4)
    eng3.run(max_steps=100)
    eng3.close()
    assert late.state == "timeout" and ok.state == "finished", \
        (late.state, ok.state)
    snap = mx.snapshot()
    for name in ("serving/faults", "serving/retries", "serving/timeouts",
                 "serving/requests_failed"):
        assert name in snap, "missing instrument %s" % name
    assert snap["serving/timeouts"]["value"] >= 1
    assert snap["serving/retries"]["value"] >= 1
    assert snap["serving/faults"]["value"] >= 1
    print("chaos_drill: serving drill OK "
          "(decode failure absorbed, exhaustion backpressured, "
          "deadline retired TIMEOUT; zero page leaks)")


def drill_fleet(tmp) -> None:
    """ISSUE 15's fleet chaos drill, on REAL engines in REAL processes:
    SIGKILL a replica mid-traffic -> exactly one terminal outcome per
    request, zero silent drops, and the requeued seeded requests replay
    bit-identical to an unkilled in-process twin; then a rolling restart
    under traffic terminates nothing as 'rejected'. The whole leg runs
    with distributed tracing + the fleet event log armed (ISSUE 16): the
    merged clock-aligned timeline must VALIDATE after the SIGKILL — the
    killed attempt 1 closed synthetically and tagged, the requeued
    attempt 2 of the SAME trace_id finished."""
    from paddle_tpu.fleet import FleetConfig, Router
    from paddle_tpu.fleet import metrics as fm
    from paddle_tpu.models.decoder_lm import DecoderConfig, DecoderLM
    from paddle_tpu.serving.engine import ServingConfig, ServingEngine

    # drill_serving's geometry: one layer, one prompt bucket, tiny
    # compiles — workers warm up fast off the shared compile cache
    mcfg = dict(vocab_size=64, n_layer=1, d_model=16, n_head=2, max_seq=32)
    scfg = dict(slots=2, page_size=8, max_seq=32)
    spec = {"engine": "real", "model": mcfg, "model_seed": 0,
            "serving": scfg, "warmup": True}
    jobs = [([1 + i, 2, 3, 4], 5) for i in range(10)]

    trace_dir = os.path.join(tmp, "fleet_trace")
    event_log = os.path.join(tmp, "fleet_events.jsonl")
    router = Router(FleetConfig(replicas=2, mode="process",
                                affinity="round_robin", engine_spec=spec,
                                max_outstanding=2, trace_dir=trace_dir,
                                event_log=event_log))
    frs = [router.submit(p, m, temperature=0.6, seed=900 + i)
           for i, (p, m) in enumerate(jobs)]
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline \
            and not router._replicas[0].inflight:
        router.pump()
        time.sleep(0.005)
    assert router._replicas[0].inflight, "no traffic reached the victim"
    req0, r0 = fm.REQUEUED.value, fm.REPLICA_RESTARTS.value
    dup0 = fm.DUPLICATE_RESULTS.value
    router._replicas[0].kill()  # real SIGKILL, KV pages and all
    assert router.wait_all(120.0), "fleet never drained after SIGKILL"
    acc = router.accounting()
    assert set(acc.values()) == {"finished"}, \
        "SIGKILL produced drops/failures: %s" % acc
    assert fm.REQUEUED.value > req0, "kill lost no in-flight work?"
    assert fm.REPLICA_RESTARTS.value > r0, "dead worker not respawned"
    assert fm.DUPLICATE_RESULTS.value == dup0, "double-terminal after kill"

    # the unkilled twin: same model seed, same request seeds, one
    # in-process engine — streams must match bit for bit
    def factory(i):
        model = DecoderLM(DecoderConfig(**mcfg), seed=0)
        return ServingEngine(model, ServingConfig(**scfg))

    twin = Router(FleetConfig(replicas=1, mode="inprocess",
                              engine_factory=factory))
    frs_t = [twin.submit(p, m, temperature=0.6, seed=900 + i)
             for i, (p, m) in enumerate(jobs)]
    assert twin.wait_all(60.0)
    assert [f.tokens for f in frs] == [f.tokens for f in frs_t], \
        "requeued replay diverged from the unkilled twin"
    twin.close()

    # rolling restart under fresh traffic: drain -> respawn each replica
    # in turn; shed work is re-routed, never terminal 'rejected'
    frs2 = [router.submit(p, m, temperature=0.6, seed=990 + i)
            for i, (p, m) in enumerate(jobs[:6])]
    rr0 = fm.ROLLING_RESTARTS.value
    router.rolling_restart(60.0)
    assert router.wait_all(120.0), "fleet never drained after restart"
    assert fm.ROLLING_RESTARTS.value > rr0
    acc = router.accounting()
    assert "rejected" not in acc.values(), \
        "rolling restart terminally rejected a request: %s" % acc
    assert all(f.state == "finished" and f.tokens for f in frs2)
    router.close()  # writes the router fragment + merge manifest

    # the merged cross-process timeline tells the same story the
    # accounting did — and validates: killed attempt 1 closed + tagged,
    # attempt 2 of the SAME trace_id finished, worker spans joined
    from tools import fleet_trace

    digest = fleet_trace.merge(trace_dir)
    digests = fleet_trace.validate(trace_dir)
    meta = digests.pop("_meta")
    assert meta["requests"] == len(jobs) + len(frs2), meta
    replayed = {t: d for t, d in digests.items() if d["killed"]}
    assert replayed, "no killed attempt in the merged trace"
    for tid, d in replayed.items():
        assert d["state"] == "finished", (tid, d)
        assert d["killed"][0] == 1 and d["attempts"][-1] >= 2, (tid, d)

    from paddle_tpu.fleet.events import read_events

    evs = read_events(event_log)
    kinds = {e["kind"] for e in evs}
    assert {"fleet_start", "kill_detected", "requeue", "restart",
            "rolling_restart", "fleet_stop"} <= kinds, kinds
    assert len({e["run_id"] for e in evs}) == 1

    print("chaos_drill: fleet drill OK (SIGKILL absorbed exactly-once, "
          "replay bit-identical to unkilled twin, rolling restart "
          "rejected nothing; merged trace validated — %d requests, "
          "killed attempt 1 -> finished attempt >=2 on %d request(s))"
          % (meta["requests"], len(replayed)))
    print("chaos_drill: fleet trace %s (merged: %s), events %s"
          % (trace_dir, digest["out"], event_log))


def selftest() -> int:
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as tmp:
        # self-heal first: its hex-identity assert is the tightest
        # determinism gate in the suite (it caught the donated-alias
        # state-buffer corruption fixed in executor._place — keep it the
        # canary), and the later drills then reuse its compiled shapes
        drill_self_heal(tmp)
        drill_exactly_once(tmp)
        drill_training(tmp)
        drill_serving()
        drill_fleet(tmp)
    dt = time.perf_counter() - t0
    print("chaos_drill selftest: OK (%.1fs)" % dt)
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] in ("-h", "--help"):
        print(__doc__.strip())
        return 0
    if argv and argv[0] == "--parse":
        from paddle_tpu.reliability import FaultPlan

        plan = FaultPlan.parse(argv[1] if len(argv) > 1 else "")
        for spec in plan.specs:
            print(spec)
        return 0
    if not argv or argv[0] == "--selftest":
        return selftest()
    print("unknown flag %r" % argv[0], file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main())
