"""Run every ROADMAP smoke gate sequentially — the pre-tier-1 CI entry.

    python -m tools.ci_smokes [--only FRAGMENT] [--timeout SECONDS]

Each gate is one ``JAX_PLATFORMS=cpu python -m <module> --selftest``
subprocess (a fresh interpreter per gate, exactly how CI and a human run
them — no shared registry state between gates). Prints one PASS/FAIL
line per gate with its wall time, a failing gate's last output lines,
and exits nonzero iff any gate failed.

Each gate also has a wall-time BUDGET (the ROADMAP's per-gate bound): a
passing gate that runs over budget prints a visible ``SLOW`` warning —
never a failure, so a loaded CI host cannot flake the gate, but drift
shows up in the log the day it starts, not the day the suite times out.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (label, module)
GATES = (
    ("dump_metrics", "tools.dump_metrics"),
    ("dump_program", "tools.dump_program"),
    ("sparse_adam", "paddle_tpu.ops.pallas_kernels.sparse_adam"),
    ("paged_attention", "paddle_tpu.ops.pallas_kernels.paged_attention"),
    ("chaos_drill", "tools.chaos_drill"),
    ("fleet_trace", "tools.fleet_trace"),
    ("fleet_autopsy", "tools.fleet_autopsy"),
    ("autotune", "tools.autotune"),
    ("check_budgets", "tools.check_budgets"),
    ("numerics_report", "tools.numerics_report"),
)

# label -> wall-time budget in seconds.
# Exceeding a budget WARNS (visibly, in the gate line) but never fails:
# budgets catch drift, timeouts catch hangs.
BUDGETS = {
    "dump_metrics": 10.0,
    "dump_program": 10.0,
    "sparse_adam": 15.0,
    "paged_attention": 20.0,  # eleven interpreted kernel calls
    # its restarted-process twins compile cold: JAX's own thresholds keep
    # sub-second CPU executables out of the persistent cache
    "chaos_drill": 75.0,
    "fleet_trace": 10.0,
    "fleet_autopsy": 10.0,
    "autotune": 20.0,  # two interpret-mode kernel micro-sweeps dominate
    "check_budgets": 10.0,
    "numerics_report": 15.0,
}


def run_gate(module: str, timeout: float = 120.0):
    """One smoke gate in a clean subprocess; returns (rc, seconds, tail)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.perf_counter()
    try:
        proc = subprocess.run(
            [sys.executable, "-m", module, "--selftest"],
            cwd=_REPO, env=env, timeout=timeout,
            capture_output=True, text=True)
        rc, out = proc.returncode, proc.stdout + proc.stderr
    except subprocess.TimeoutExpired as e:
        rc = 124
        out = "%s%s\nTIMEOUT after %.0fs" % (
            (e.stdout or b"").decode("utf-8", "replace") if
            isinstance(e.stdout, bytes) else (e.stdout or ""),
            (e.stderr or b"").decode("utf-8", "replace") if
            isinstance(e.stderr, bytes) else (e.stderr or ""), timeout)
    dt = time.perf_counter() - t0
    tail = "\n".join(out.strip().splitlines()[-12:])
    return rc, dt, tail


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] in ("-h", "--help"):
        print(__doc__.strip())
        return 0

    def opt(name, default=None):
        if name in argv:
            i = argv.index(name)
            argv.pop(i)
            return argv.pop(i)
        return default

    only = opt("--only")
    timeout = float(opt("--timeout", "120"))
    if argv:
        print("unknown arguments: %s" % " ".join(argv), file=sys.stderr)
        return 2
    gates = [(lbl, mod) for lbl, mod in GATES
             if only is None or only in lbl]
    if not gates:
        print("no gate matches --only %r" % only, file=sys.stderr)
        return 2
    failed = []
    slow = []
    t0 = time.perf_counter()
    for label, module in gates:
        rc, dt, tail = run_gate(module, timeout=timeout)
        status = "PASS" if rc == 0 else "FAIL(rc=%d)" % rc
        budget = BUDGETS.get(label)
        drift = ""
        if rc == 0 and budget is not None and dt > budget:
            slow.append(label)
            drift = "  SLOW: %.1fs > %.0fs budget" % (dt, budget)
        print("%-16s %-10s %6.1fs   python -m %s --selftest%s"
              % (label, status, dt, module, drift))
        if rc != 0:
            failed.append(label)
            print("  | " + tail.replace("\n", "\n  | "), file=sys.stderr)
    total = time.perf_counter() - t0
    print("-" * 60)
    if slow:
        print("ci_smokes: WARNING %d gate(s) over wall-time budget (%s) — "
              "not fatal, but the drift is real; re-budget or re-tighten"
              % (len(slow), ", ".join(slow)))
    if failed:
        print("ci_smokes: %d/%d gates FAILED (%s) in %.1fs"
              % (len(failed), len(gates), ", ".join(failed), total))
        return 1
    print("ci_smokes: all %d gates passed in %.1fs" % (len(gates), total))
    return 0


if __name__ == "__main__":
    sys.exit(main())
