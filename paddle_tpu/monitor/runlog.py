"""Run ledger: one provenance-stamped JSONL record per bench/selftest run.

PR 8 made a single process observable while it runs (telemetry ring, SLO
monitor); nothing connected runs to each other — the measured trajectory
lived in log tails a human had to reread. This module is the ACROSS-run
layer: with ``PADDLE_TPU_RUN_LEDGER=/path/ledger.jsonl`` armed, every
``bench.py`` / ``tools/autotune.py`` invocation appends one record
carrying

* ``run_id`` — one id per process (also printed in the summary tail and
  embedded in flight-recorder dumps, so ledger <-> telemetry <-> crash
  artifacts join on a single key),
* provenance — git sha + dirty flag, device kind, backend, JAX version,
  opt level + disabled pass gates, tune-table path + per-kernel config
  provenance, and the ``PADDLE_TPU_*``/``FLAGS_*`` env knob snapshot,
* ``configs`` — the {config: {metric: value}} map the run already prints
  in its truncation-proof tail.

Write discipline mirrors the telemetry ring (telemetry.py ``_write``):
every append is flushed + fsynced so a crash loses at most the in-flight
line; the file rotates to ``<path>.<k>`` every
``PADDLE_TPU_RUN_LEDGER_ROTATE`` records keeping
``PADDLE_TPU_RUN_LEDGER_KEEP`` rotated files; the first write error logs
once and disables the on-disk ledger — it never masks the run it records.
Read-back (:func:`read_ledger`) tolerates torn trailing lines and skips
foreign schemas, so a ledger shared across versions stays loadable.
"""

from __future__ import annotations

import json
import logging
import os
import subprocess
import sys
import time
import uuid
from typing import Any, Dict, List, Optional

from . import metrics as _mx

__all__ = [
    "RUN_SCHEMA", "RunLedger", "run_id", "provenance", "ledger_path",
    "record_run", "read_ledger", "tail_info",
]

RUN_SCHEMA = "paddle_tpu.runlog/v1"

_log = logging.getLogger("paddle_tpu")

_c_records = _mx.counter(
    "runlog/records", help="run-ledger records appended (or handed back "
                           "unwritten when no ledger is armed)")
_c_rotations = _mx.counter(
    "runlog/rotations", help="run-ledger file rotations")
_c_write_errors = _mx.counter(
    "runlog/write_errors", help="run-ledger write failures (first one "
                                "disables the on-disk ledger)")


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


# -- run identity -------------------------------------------------------------

_run_id: Optional[str] = None


def run_id() -> str:
    """One id per process, generated on first use:
    ``r<utc-stamp>-<pid>-<4 hex>``. Every artifact a run leaves (ledger
    record, summary tail, flight dump) carries the same value."""
    global _run_id
    if _run_id is None:
        _run_id = "r%s-%d-%s" % (
            time.strftime("%Y%m%dT%H%M%S", time.gmtime()),
            os.getpid(), uuid.uuid4().hex[:4])
    return _run_id


# -- provenance ---------------------------------------------------------------

_git_cache: Optional[Dict[str, Any]] = None


def _git_state() -> Dict[str, Any]:
    """HEAD sha + dirty flag of the repo containing this package; every
    failure mode (no git binary, not a checkout, timeout) degrades to
    ``{"sha": None}`` — provenance must never sink a bench. Cached per
    process (two subprocess spawns once, not per record)."""
    global _git_cache
    if _git_cache is not None:
        return dict(_git_cache)
    _git_cache = _read_git_state()
    return dict(_git_cache)


def _read_git_state() -> Dict[str, Any]:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=root, timeout=5,
            capture_output=True, text=True)
        if sha.returncode != 0:
            return {"sha": None}
        dirty = subprocess.run(
            ["git", "status", "--porcelain"], cwd=root, timeout=5,
            capture_output=True, text=True)
        return {"sha": sha.stdout.strip(),
                "dirty": bool(dirty.stdout.strip())
                if dirty.returncode == 0 else None}
    except Exception:
        return {"sha": None}


def provenance() -> Dict[str, Any]:
    """The full context stamp: everything needed to ask "what produced
    this number" of a ledger record months later. Each section degrades
    independently (a broken tune table must not cost the git sha)."""
    out: Dict[str, Any] = {"git": _git_state(),
                           "python": sys.version.split()[0],
                           "pid": os.getpid()}
    try:
        import jax

        out["jax"] = jax.__version__
        out["backend"] = jax.default_backend()
    except Exception:
        out["jax"] = None
    try:
        from .device import raw_device_kind

        out["device_kind"] = raw_device_kind()
    except Exception:
        out["device_kind"] = "unknown"
    try:
        from ..passes.pipeline import (DEFAULT_PASS_NAMES, opt_level,
                                       pass_enabled)

        out["opt_level"] = opt_level()
        out["pass_gates_off"] = [n for n in DEFAULT_PASS_NAMES
                                 if not pass_enabled(n)]
    except Exception:
        out["opt_level"] = None
    try:
        from .. import tune

        out["tune_table"] = tune.table_path()
        out["tune_provenance"] = {
            k: p.get("source") for k, p in
            sorted(tune.provenance_snapshot().items())}
    except Exception:
        out["tune_table"] = None
    # same knob families the flight recorder snapshots (device.py dump())
    out["env"] = {k: v for k, v in sorted(os.environ.items())
                  if k.startswith(("PADDLE_TPU_", "FLAGS_"))}
    return out


# -- the ledger ---------------------------------------------------------------

def ledger_path() -> Optional[str]:
    p = os.environ.get("PADDLE_TPU_RUN_LEDGER", "").strip()
    return p or None


class RunLedger:
    """Append-only JSONL ledger at ``path`` (telemetry-ring discipline:
    fsync per append, bounded rotation, disable-on-write-error)."""

    def __init__(self, path: str, rotate_records: Optional[int] = None,
                 keep_files: Optional[int] = None):
        self.path = path
        self.rotate_records = max(1, rotate_records if rotate_records
                                  is not None else
                                  _env_int("PADDLE_TPU_RUN_LEDGER_ROTATE",
                                           4096))
        self.keep_files = max(1, keep_files if keep_files is not None else
                              _env_int("PADDLE_TPU_RUN_LEDGER_KEEP", 4))
        self.disabled = False
        self._records_in_file: Optional[int] = None  # counted lazily

    def _count_records(self) -> int:
        try:
            with open(self.path) as f:
                return sum(1 for line in f if line.strip())
        except OSError:
            return 0

    def _rotate(self) -> None:
        """Shift the live file to ``<path>.<k>`` (k monotonically
        increasing) and prune rotated files past ``keep_files``."""
        idx = 1
        existing = _rotated_paths(self.path)
        if existing:
            idx = existing[-1][0] + 1
        os.replace(self.path, "%s.%d" % (self.path, idx))
        _c_rotations.inc()
        keep = _rotated_paths(self.path)
        excess = len(keep) - (self.keep_files - 1)
        for _, p in keep[:max(0, excess)]:
            try:
                os.remove(p)
            except OSError:
                pass

    def append(self, record: dict) -> Optional[str]:
        """Write one record; returns the ledger path, or ``None`` once
        the ledger disabled itself after a write error."""
        if self.disabled:
            return None
        try:
            parent = os.path.dirname(os.path.abspath(self.path))
            os.makedirs(parent, exist_ok=True)
            if self._records_in_file is None:
                self._records_in_file = self._count_records()
            if self._records_in_file >= self.rotate_records:
                self._rotate()
                self._records_in_file = 0
            with open(self.path, "a") as f:
                f.write(json.dumps(record, default=str) + "\n")
                f.flush()
                os.fsync(f.fileno())
            self._records_in_file += 1
            return self.path
        except OSError as e:
            # the telemetry-ring rule: a broken ledger path must never
            # mask the run it records — log once, keep returning records
            self.disabled = True
            _c_write_errors.inc()
            _log.error(
                "runlog: cannot write PADDLE_TPU_RUN_LEDGER=%r (%s) — "
                "on-disk ledger disabled for this process", self.path, e)
            return None


_ledger: Optional[RunLedger] = None


def _active_ledger() -> Optional[RunLedger]:
    """Process ledger for the current ``PADDLE_TPU_RUN_LEDGER`` value
    (None when unarmed); a changed path mid-process opens a fresh one."""
    global _ledger
    p = ledger_path()
    if p is None:
        return None
    if _ledger is None or _ledger.path != p:
        _ledger = RunLedger(p)
    return _ledger


def record_run(kind: str, configs: Dict[str, dict],
               extra: Optional[dict] = None) -> dict:
    """Build (and, when the ledger is armed, append) one run record.

    ``configs`` is the {config: {metric: value}} map the caller's summary
    tail prints; ``kind`` names the producing surface ("bench",
    "autotune"). Returns the record either
    way — callers embed ``run_id`` in their tails unconditionally, and
    ``record["ledger_path"]`` says whether it also landed on disk."""
    record = {
        "schema": RUN_SCHEMA,
        "run_id": run_id(),
        "t": time.time(),
        "kind": kind,
        "provenance": provenance(),
        "configs": configs,
    }
    if extra:
        record["extra"] = extra
    try:
        # with PADDLE_TPU_NUMERICS armed, the record carries the run's
        # final per-op range stats — joins the perf trajectory to the
        # numerics trajectory on the same run_id (each section of a record
        # degrades independently, same rule as provenance())
        from . import numerics as _numerics

        if _numerics.stats_level() >= 1:
            snap = _numerics.snapshot()
            if snap:
                record["numerics_last"] = snap
    except Exception:
        pass
    led = _active_ledger()
    record["ledger_path"] = led.append(record) if led is not None else None
    _c_records.inc()
    return record


def tail_info() -> Dict[str, Any]:
    """The cross-linking keys every summary tail carries: the process
    ``run_id``, plus the ledger path when one is armed."""
    out: Dict[str, Any] = {"run_id": run_id()}
    p = ledger_path()
    if p:
        out["run_ledger"] = p
    return out


# -- read-back ----------------------------------------------------------------

def _rotated_paths(path: str) -> List[tuple]:
    """[(idx, path)] of rotated shards, oldest first."""
    d = os.path.dirname(os.path.abspath(path)) or "."
    base = os.path.basename(path)
    out = []
    try:
        names = os.listdir(d)
    except OSError:
        return []
    for name in names:
        if name.startswith(base + "."):
            suffix = name[len(base) + 1:]
            if suffix.isdigit():
                out.append((int(suffix), os.path.join(d, name)))
    return sorted(out)


def read_ledger(path: Optional[str] = None) -> List[dict]:
    """Load the ledger back, rotated shards first, in append order.
    Torn trailing lines (a crash mid-append) and foreign-schema lines
    are skipped, not fatal — the ledger is a baseline source first."""
    path = path or ledger_path()
    if not path:
        return []
    out: List[dict] = []
    files = [p for _, p in _rotated_paths(path)] + [path]
    for p in files:
        try:
            with open(p) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        doc = json.loads(line)
                    except ValueError:
                        continue  # torn tail line
                    if doc.get("schema") == RUN_SCHEMA:
                        out.append(doc)
        except OSError:
            continue
    return out
