"""Measured autotuning CLI (paddle_tpu.tune).

    python -m tools.autotune --all [--reps K] [--table FILE] [--dry-run]
        Sweep every registered tunable (flash-attention BlockSizes,
        sparse-adam row blocks, softmax-xent tiles, paged-attention page
        blocks, per-program pass gates) over its default shape points on the
        CURRENT backend, write the winners into the persistent config
        table (PADDLE_TPU_TUNE_TABLE, or autotune_table.json in the
        compile cache's directory), and print a before/after table.

    python -m tools.autotune --kernel flash_attention
        Sweep one tunable (see --list for names).

    python -m tools.autotune --model DIR
        Pass-gate selection measured end-to-end on a saved inference
        model directory (io.save_inference_model layout).

    python -m tools.autotune --selftest
        ~10s, CPU: table round-trip from a cold dir, determinism of the
        table produced from a fixed candidate list, corrupt-table
        fallback, shipped v5e seed lookup, real (interpret-mode)
        sparse-adam + paged-attention micro-sweeps, and the autotune/*
        counters. The CI smoke gate (ROADMAP).

On CPU the sweeps run the same code path as on TPU (Pallas interpret /
XLA:CPU timing) — mechanism numbers, not shipping numbers; run the same
commands on real hardware to populate the table with TPU medians.
"""

from __future__ import annotations

import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _fmt_ms(v):
    return "-" if v is None else ("%.3f" % v)


def print_results(results) -> None:
    """Human before/after table: one row per (kernel, shape) sweep."""
    from paddle_tpu import tune

    header = ("kernel", "shape", "bucket", "cands", "pruned",
              "default_ms", "best_ms", "speedup", "best_config")
    rows = []
    for res in results:
        n_pruned = sum(1 for r in res.rows if "pruned" in r)
        shape_lbl = ",".join("%s=%s" % (k, res.shape[k])
                             for k in sorted(res.shape)
                             if not isinstance(res.shape[k], (dict, list)))
        sp = res.speedup_vs_default
        rows.append((res.kernel, shape_lbl[:38], res.bucket,
                     str(len(res.rows)), str(n_pruned),
                     _fmt_ms(res.default_ms), _fmt_ms(res.best_ms),
                     "-" if sp is None else "%.2fx" % sp,
                     json.dumps(res.best, sort_keys=True)))
    widths = [max(len(header[i]), *(len(r[i]) for r in rows)) if rows
              else len(header[i]) for i in range(len(header))]
    line = "  ".join(h.ljust(w) for h, w in zip(header, widths))
    print(line)
    print("-" * len(line))
    for r in rows:
        print("  ".join(c.ljust(w) for c, w in zip(r, widths)))
    path = tune.table_path()
    written = [r.written_path for r in results if r.written_path]
    if written:
        print("\ntable: %s (%d entries written, device=%s)"
              % (written[-1], len(written), tune.device_kind()))
    else:
        print("\ntable: %s (dry run — nothing written)" % path)


def run_sweeps(kernels, *, reps=5, warmup=1, persist=True, table_file=None,
               model_dir=None):
    from paddle_tpu import tune

    results, failures = [], []
    for name in kernels:
        t = tune.get_tunable(name)
        try:
            shapes = t.default_shapes()
            if name == "pass_gates" and model_dir:
                shapes = [dict(workload="model", model_dir=model_dir,
                               batch=16)]
            for shape in shapes:
                t0 = time.perf_counter()
                try:
                    res = tune.search(t, shape, reps=reps, warmup=warmup,
                                      persist=persist, table_file=table_file)
                except Exception as e:
                    # one broken tunable must not sink the report for the
                    # kernels that already swept (their entries ARE written)
                    failures.append((name, shape, e))
                    print("# SWEEP FAILED %s %r: %s: %s"
                          % (name, shape, type(e).__name__, e),
                          file=sys.stderr)
                    continue
                print("# swept %s %s in %.1fs -> %s"
                      % (name, res.bucket, time.perf_counter() - t0,
                         json.dumps(res.best, sort_keys=True)),
                      file=sys.stderr)
                results.append(res)
        finally:
            t.cleanup()
    return results, failures


# -- selftest -----------------------------------------------------------------


class _ToyTunable:
    """Deterministic synthetic tunable: cost is a pure function of the
    config, so the search machinery (pruning, ranking, persistence) can be
    asserted bit-for-bit without device timing noise."""

    kernel = "selftest.toy"

    def default_shapes(self):
        return [{"n": 64}]

    def bucket(self, shape):
        return "n%d" % shape["n"]

    def candidates(self, shape):
        return [{"x": x} for x in (1, 2, 3, 4, 5)]

    def default_config(self, shape):
        return {"x": 1}

    def cost(self, shape, config):
        # x=5 is "memory-blown": the prune path must fire deterministically
        return {"vmem_bytes": 1 << 40} if config["x"] == 5 else {}

    def build(self, shape, config):
        return (lambda: config["x"]), ()

    def cleanup(self):
        pass


def _toy_measure(fn, args, config=None, **_kw):
    # deterministic "measurement": best at x=3, tie between 2 and 4
    return float(abs(config["x"] - 3) + 1)


def selftest() -> int:
    import tempfile

    t0 = time.time()
    from paddle_tpu import tune
    from paddle_tpu.monitor import metrics as mx
    from paddle_tpu.tune import table as tt

    mx.enable()
    mx.reset()
    with tempfile.TemporaryDirectory() as td:
        tpath = os.path.join(td, "autotune_table.json")
        prev = os.environ.get("PADDLE_TPU_TUNE_TABLE")
        os.environ["PADDLE_TPU_TUNE_TABLE"] = tpath
        try:
            # 1. shipped seeds: the hand-tuned v5e entries answer cold
            cfg, src = tune.lookup("flash_attention",
                                   tune.bucket_seq(8192, 8192),
                                   device="tpu-v5e")
            assert src == "shipped" and cfg["block_q"] == 512 \
                and cfg["block_k"] == 512, (cfg, src)
            cfg, src = tune.lookup("sparse_adam", tune.bucket_rows(4096, 64),
                                   device="tpu-v5e")
            assert src == "shipped" and cfg["block"] == 128, (cfg, src)
            # a bucket the chip sweep measured (GPT-2 small's), and one
            # it did not: the wildcard
            cfg, src = tune.lookup("paged_attention",
                                   tune.bucket_ctx(1024, 768),
                                   device="tpu-v5e")
            assert src == "shipped" and cfg["block_pages"] == 8, (cfg, src)
            cfg, src = tune.lookup("paged_attention",
                                   tune.bucket_ctx(2048, 512),
                                   device="tpu-v5e")
            assert src == "shipped" and cfg["block_pages"] == 16, (cfg, src)
            # unknown device -> default (hardcoded fallbacks stay in charge)
            cfg, src = tune.lookup("flash_attention",
                                   tune.bucket_seq(8192, 8192),
                                   device="made-up-chip")
            assert cfg is None and src == "default"

            # 2. determinism: same fixed candidate list + deterministic
            #    measure twice -> byte-identical table entries, best=x3,
            #    the blown candidate pruned not timed
            toy = _ToyTunable()
            r1 = tune.search(toy, reps=3, measure=_toy_measure)
            e1 = tt.read_entries(tpath)
            r2 = tune.search(toy, reps=3, measure=_toy_measure)
            e2 = tt.read_entries(tpath)
            assert r1.best == r2.best == {"x": 3}, (r1.best, r2.best)
            assert e1 == e2 and e1, "table not deterministic"
            assert any("pruned" in row for row in r1.rows), r1.rows
            assert r1.default_ms == 3.0 and r1.best_ms == 1.0

            # 3. round-trip: the tuned entry answers lookups (and wins
            #    over shipped/default)
            cfg, src = tune.lookup("selftest.toy", "n64")
            assert src == "tuned" and cfg == {"x": 3}, (cfg, src)

            # 4. a REAL micro-sweep through the Pallas interpreter: tiny
            #    sparse-adam candidate space, then the rerouted
            #    _block_size picks the tuned winner up
            sa = tune.get_tunable("sparse_adam")
            shape = dict(vocab=64, dim=8, n=24)
            res = tune.search(sa, shape,
                              candidates=[{"block": 8}, {"block": 16}],
                              reps=1, warmup=1)
            # search() appends the default config (block 24 here) so every
            # sweep carries a before/after — any of the three may win
            assert res.best["block"] in (8, 16, 24) and res.written_path
            from paddle_tpu.ops.pallas_kernels.sparse_adam import _block_size

            got = _block_size(None, shape["n"], shape["dim"])
            assert got == res.best["block"], (got, res.best)

            # 4b. same mechanism for the paged-attention wave width: a
            #     tiny interpret-mode sweep, then the kernel's trace-time
            #     _block_pages serves the tuned winner
            pa = tune.get_tunable("paged_attention")
            pshape = dict(slots=2, max_ctx=32, page_size=8, n_head=2,
                          d_head=8)
            pres = tune.search(pa, pshape,
                               candidates=[{"block_pages": 1},
                                           {"block_pages": 2}],
                               reps=1, warmup=1)
            assert pres.best["block_pages"] in (1, 2, 4) \
                and pres.written_path, pres.best
            from paddle_tpu.ops.pallas_kernels.paged_attention import \
                _block_pages

            got = _block_pages(None, 8, 4, 32, 16)
            assert got == pres.best["block_pages"], (got, pres.best)

            # 5. corrupt table: logs once, falls back — never raises
            with open(tpath, "w") as f:
                f.write('{"format": "paddle_tpu.tune/1", "entries": {tor')
            cfg, src = tune.lookup("selftest.toy", "n64")
            assert cfg is None and src == "default", (cfg, src)
            from paddle_tpu.ops.attention_ops import _tuned_block_sizes

            bs = _tuned_block_sizes(8192, 8192)  # must not raise
            assert bs.block_q == 512  # hardcoded fallback preserved
            # ...and the paged-attention lookup ladder degrades the same
            # way: corrupt table -> the analytic VMEM-budget default
            got = _block_pages(None, 8, 4, 32, 16)
            assert got == 4, got  # _default_block_pages(8, 4, 16)

            # 6. the autotune/* instruments all exist and counted the above
            snap = mx.snapshot()
            for name in ("autotune/lookups", "autotune/lookup_tuned",
                         "autotune/lookup_shipped", "autotune/lookup_default",
                         "autotune/sweeps", "autotune/candidates_timed",
                         "autotune/candidates_pruned",
                         "autotune/candidates_failed",
                         "autotune/table_writes", "autotune/table_errors"):
                assert name in snap, "missing instrument %s" % name
            assert snap["autotune/sweeps"]["value"] == 4
            assert snap["autotune/lookup_shipped"]["value"] >= 2
            assert snap["autotune/lookup_tuned"]["value"] >= 2
            assert snap["autotune/candidates_pruned"]["value"] >= 2
            assert snap["autotune/table_errors"]["value"] >= 1
            assert snap["autotune/table_writes"]["value"] >= 3
        finally:
            if prev is None:
                os.environ.pop("PADDLE_TPU_TUNE_TABLE", None)
            else:
                os.environ["PADDLE_TPU_TUNE_TABLE"] = prev
    dt = time.time() - t0
    # two interpret-mode kernel micro-sweeps (sparse_adam, paged_attention)
    # dominate; the Pallas interpreter traces slowly but honestly. Wall
    # time is judged where every gate's is: ci_smokes.BUDGETS (warns)
    print("autotune selftest: OK (%.1fs): shipped v5e seeds, deterministic "
          "search, tuned-table round-trip + reroute (sparse_adam + "
          "paged_attention), corrupt-table fallback, autotune/* counters"
          % dt)
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] in ("-h", "--help"):
        print(__doc__.strip())
        return 0
    if "--selftest" in argv:
        return selftest()
    from paddle_tpu import tune

    if "--list" in argv:
        for name in tune.registered_tunables():
            print(name)
        return 0

    def opt(name, default=None):
        if name in argv:
            i = argv.index(name)
            if i + 1 >= len(argv):
                print("%s requires a value" % name, file=sys.stderr)
                raise SystemExit(2)
            argv.pop(i)
            return argv.pop(i)
        return default

    reps = int(opt("--reps", "5"))
    warmup = int(opt("--warmup", "1"))
    table_file = opt("--table")
    model_dir = opt("--model")
    kernel = opt("--kernel")
    persist = "--dry-run" not in argv
    argv = [a for a in argv if a not in ("--all", "--dry-run")]
    if argv:
        print("unknown arguments: %s" % " ".join(argv), file=sys.stderr)
        return 2
    if kernel:
        kernels = [kernel]
    elif model_dir:
        kernels = ["pass_gates"]
    else:
        kernels = tune.registered_tunables()
    results, failures = run_sweeps(kernels, reps=reps, warmup=warmup,
                                   persist=persist, table_file=table_file,
                                   model_dir=model_dir)
    print_results(results)
    for name, shape, e in failures:
        print("SWEEP FAILED %s %r: %s: %s"
              % (name, shape, type(e).__name__, e), file=sys.stderr)
    # machine tail: the sweep digest as one JSON line (bench-style),
    # carrying the run_id (+ ledger record when PADDLE_TPU_RUN_LEDGER is
    # armed) so tuned-table provenance joins the perf trend data
    tail = {
        "autotune": [r.to_dict() for r in results],
        "failures": ["%s %r: %r" % (n, s, str(e)[:120])
                     for n, s, e in failures],
    }
    try:
        from paddle_tpu.monitor import runlog

        configs = {}
        for r in results:
            row = {}
            if r.best_ms is not None:
                row["best_ms"] = r.best_ms
            if r.speedup_vs_default is not None:
                row["speedup_vs_default"] = r.speedup_vs_default
            if row:
                configs["%s/%s" % (r.kernel, r.bucket)] = row
        runlog.record_run("autotune", configs,
                          extra={"n_failures": len(failures)})
        tail.update(runlog.tail_info())
    except Exception as e:
        tail["run_ledger_error"] = repr(e)[:80]
    print(json.dumps(tail, default=str))
    return 1 if failures and not results else 0


if __name__ == "__main__":
    sys.exit(main())
