"""Persistent autotuned-config table — the storage half of ``paddle_tpu.tune``.

Tuned configs are keyed ``(kernel, shape-bucket, device_kind)`` — the Tensor
Processing Primitives argument (PAPERS.md): optimal blocking is shape- AND
microarchitecture-specific, so a v5e-tuned 512x512 flash tile must never be
served to a v4 chip or to a 384-long sequence as if it were universal. Three
layers answer every lookup, best first:

1. **tuned** — the runtime JSON table written by ``tools/autotune.py`` /
   :func:`paddle_tpu.tune.search`. Lives in the persistent XLA compile
   cache's directory (``compile_cache.compile_cache_dir()``, file
   ``autotune_table.json``) so tuned configs survive restarts exactly like
   compiled executables do;
   ``PADDLE_TPU_TUNE_TABLE=<file>`` overrides the location.
2. **shipped** — ``paddle_tpu/tune/shipped.json``, checked into the repo and
   seeded with today's hand-tuned entries (the v5e 512x512 flash BlockSizes
   from the round-4 sweep, the sparse-adam 128-id blocks) as the cold-start
   lookup for known device kinds.
3. **default** — ``(None, "default")``: the caller keeps its hardcoded
   fallback. This is the answer on unknown devices, unknown shapes, a
   missing table, and — critically — a CORRUPT or partially-written table
   file, which logs once per file and never raises: a broken table must
   never crash a training run that was healthy without it.

Buckets are coarse on purpose (power-of-two floors): a tuned config for
s=8192 serves s=9000 too, and callers clamp tile sizes to the divisibility
constraints of the actual shape. A ``*`` bucket is the kernel-wide wildcard
(shipped entries use it so one hand-tuned row covers every shape the sweep
validated the trend for).

Every lookup ticks ``autotune/lookups`` plus a per-source counter and
records per-kernel provenance (:func:`provenance_snapshot`) so bench tails
can report whether the hot kernels ran ``tuned``, ``shipped`` or
``default`` configs.
"""

from __future__ import annotations

import json
import logging
import os
import threading
from typing import Any, Dict, Optional, Tuple

from ..monitor import metrics as _mx

__all__ = [
    "FORMAT", "WILDCARD_BUCKET",
    "device_kind", "normalize_device_kind",
    "pow2_floor", "bucket_seq", "bucket_rows", "bucket_nv", "bucket_slots",
    "bucket_ctx",
    "table_path", "shipped_path", "entry_key",
    "lookup", "record", "read_entries", "write_entries",
    "resolve_decode_fuse", "resolve_fleet_roles", "resolve_fleet_router",
    "provenance_snapshot", "reset_provenance",
]

FORMAT = "paddle_tpu.tune/1"
WILDCARD_BUCKET = "*"

_log = logging.getLogger("paddle_tpu")

# Registered at import so the counters exist (value 0) before the first
# lookup — tools/dump_metrics --selftest asserts their presence.
_m_lookups = _mx.counter(
    "autotune/lookups",
    help="tuned-config table lookups (any source)")
_m_by_src = {
    src: _mx.counter("autotune/lookup_" + src,
                     help="lookups answered by the %s layer" % src)
    for src in ("tuned", "shipped", "default")
}
_m_writes = _mx.counter(
    "autotune/table_writes",
    help="atomic runtime-table writes (tools/autotune.py / tune.search)")
_m_errors = _mx.counter(
    "autotune/table_errors",
    help="corrupt/unreadable table files tolerated (logged once, fell "
         "back to shipped/default configs)")

_lock = threading.RLock()
# path -> (stat signature, entries dict | None-when-corrupt); re-read only
# when the file changes, so trace-time lookups cost one os.stat
_file_cache: Dict[str, Tuple[Tuple[int, int], Optional[Dict[str, dict]]]] = {}
_warned_paths: set = set()
# kernel -> {"source", "bucket", "device", "config"} of the LAST lookup —
# the bench tail's provenance evidence
_provenance: Dict[str, dict] = {}


# -- device identity ----------------------------------------------------------

_KIND_ALIASES = {
    "tpu v2": "tpu-v2",
    "tpu v3": "tpu-v3",
    "tpu v4": "tpu-v4",
    "tpu v4 lite": "tpu-v4i",
    "tpu v5": "tpu-v5p",
    "tpu v5p": "tpu-v5p",
    "tpu v5 lite": "tpu-v5e",
    "tpu v5e": "tpu-v5e",
    "tpu v5litepod": "tpu-v5e",
    "tpu v6 lite": "tpu-v6e",
    "tpu v6e": "tpu-v6e",
}


def normalize_device_kind(raw: str) -> str:
    """Canonical table key for a raw ``jax.Device.device_kind`` string
    (``"TPU v5 lite"`` -> ``"tpu-v5e"``); unknown kinds lowercase with
    spaces dashed so they still key consistently."""
    k = str(raw or "unknown").strip().lower()
    return _KIND_ALIASES.get(k, k.replace(" ", "-"))


def device_kind() -> str:
    """Normalized device kind of the current default backend."""
    from ..monitor.device import raw_device_kind

    return normalize_device_kind(raw_device_kind())


# -- shape buckets ------------------------------------------------------------


def pow2_floor(x: int) -> int:
    """Largest power of two <= x (min 1) — the bucket edge."""
    x = int(x)
    return 1 if x <= 1 else 1 << (x.bit_length() - 1)


def bucket_seq(sq: int, sk: int) -> str:
    """Flash-attention bucket over (q_len, kv_len)."""
    return "s%dx%d" % (pow2_floor(sq), pow2_floor(sk))


def bucket_rows(n_ids: int, dim: int) -> str:
    """Sparse row-update bucket over (merged id count, row width)."""
    return "n%dxd%d" % (pow2_floor(n_ids), pow2_floor(dim))


def bucket_nv(n: int, v: int) -> str:
    """Softmax-xent bucket over (batch rows, vocab)."""
    return "n%dxv%d" % (pow2_floor(n), pow2_floor(v))


def bucket_slots(slots: int) -> str:
    """Serving-knob bucket over the decode batch width."""
    return "slots%d" % pow2_floor(slots)


def bucket_ctx(max_ctx: int, hd: int) -> str:
    """Paged-attention bucket over (slot context capacity, H*D row width) —
    the two shapes that size the kernel's per-wave VMEM scratch."""
    return "c%dxhd%d" % (pow2_floor(max_ctx), pow2_floor(hd))


# -- file locations -----------------------------------------------------------


def table_path() -> str:
    """Where the runtime (tuned) table lives: ``PADDLE_TPU_TUNE_TABLE``
    wins; else ``autotune_table.json`` in the persistent compile cache's
    directory (``JAX_COMPILATION_CACHE_DIR``, else the checkout's fixed
    ``.jax_cache``). Until something is recorded there the file does not
    exist and lookups see only shipped + default."""
    p = os.environ.get("PADDLE_TPU_TUNE_TABLE", "").strip()
    if p:
        return p
    from ..compile_cache import compile_cache_dir

    return os.path.join(compile_cache_dir(), "autotune_table.json")


def shipped_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "shipped.json")


def entry_key(kernel: str, bucket: str, device: str) -> str:
    return "%s|%s|%s" % (kernel, bucket, device)


# -- load / store -------------------------------------------------------------


def _valid_entries(doc: Any, path: str, fmt: str = FORMAT) -> Dict[str, dict]:
    """Schema-check a parsed table document; raises ValueError on anything
    a partially-written or foreign file could look like. ``fmt`` lets other
    subsystems (monitor.numerics calibration tables) reuse the whole
    read/validate/publish discipline under their own format tag."""
    if not isinstance(doc, dict) or not isinstance(doc.get("entries"), dict):
        raise ValueError("%s: not a tune-table document" % path)
    got = doc.get("format")
    if got != fmt:
        raise ValueError("%s: unknown format %r (want %r)" % (path, got, fmt))
    out = {}
    for key, ent in doc["entries"].items():
        if not (isinstance(key, str) and key.count("|") == 2
                and isinstance(ent, dict)
                and isinstance(ent.get("config"), dict)):
            raise ValueError("%s: malformed entry %r" % (path, key))
        out[key] = ent
    return out


def read_entries(path: Optional[str],
                 fmt: str = FORMAT) -> Optional[Dict[str, dict]]:
    """Entries of the table file at ``path`` (mtime-cached), or None when
    the file is absent OR corrupt — corruption is logged ONCE per file and
    counted, never raised (lookups fall through to the next layer)."""
    if not path:
        return None
    try:
        st = os.stat(path)
    except OSError:
        return None
    sig = (st.st_mtime_ns, st.st_size, fmt)
    with _lock:
        cached = _file_cache.get(path)
        if cached is not None and cached[0] == sig:
            return cached[1]
    entries: Optional[Dict[str, dict]]
    try:
        with open(path) as f:
            entries = _valid_entries(json.load(f), path, fmt)
    except Exception as e:
        entries = None
        if _mx._enabled:
            _m_errors.inc()
        with _lock:
            if path not in _warned_paths:
                _warned_paths.add(path)
                _log.warning(
                    "paddle_tpu.tune: ignoring unreadable/corrupt config "
                    "table %s (%s: %s) — falling back to shipped/default "
                    "configs. Re-run tools/autotune.py to rebuild it.",
                    path, type(e).__name__, e)
    with _lock:
        _file_cache[path] = (sig, entries)
    return entries


def write_entries(path: str, entries: Dict[str, dict],
                  fmt: str = FORMAT) -> str:
    """Atomically publish ``entries`` as the table at ``path`` (tmp file +
    ``os.replace`` in the same directory, so readers only ever see a
    complete document)."""
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    doc = {"format": fmt, "entries": entries}
    tmp = os.path.join(d, ".%s.tmp.%d" % (os.path.basename(path), os.getpid()))
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    if _mx._enabled:
        _m_writes.inc()
    with _lock:
        # a rebuilt table supersedes any remembered corruption
        _warned_paths.discard(path)
        _file_cache.pop(path, None)
    return path


def record(kernel: str, bucket: str, config: dict, *,
           device: Optional[str] = None, median_ms: Optional[float] = None,
           note: Optional[str] = None,
           path: Optional[str] = None) -> str:
    """Merge one tuned entry into the runtime table (read-modify-write,
    atomic publish). Returns the table path."""
    path = path or table_path()
    dev = device or device_kind()
    ent: Dict[str, Any] = {"config": dict(config), "source": "tuned"}
    if median_ms is not None:
        ent["median_ms"] = round(float(median_ms), 6)
    if note:
        ent["note"] = str(note)
    with _lock:
        entries = dict(read_entries(path) or {})
        entries[entry_key(kernel, bucket, dev)] = ent
        return write_entries(path, entries)


# -- lookup -------------------------------------------------------------------


def _note(kernel: str, source: str, bucket: str, device: str,
          config: Optional[dict]) -> None:
    with _lock:
        _provenance[kernel] = {"source": source, "bucket": bucket,
                               "device": device,
                               "config": dict(config) if config else None}


def lookup(kernel: str, bucket: str, device: Optional[str] = None,
           table_file: Optional[str] = None) -> Tuple[Optional[dict], str]:
    """``(config, source)`` for ``(kernel, bucket, device)``.

    Precedence: runtime table exact bucket, runtime wildcard, shipped
    exact, shipped wildcard, then ``(None, "default")``. NEVER raises —
    any failure (corrupt file, bad env, no backend) degrades to the
    default answer, because this is called from trace-time kernel-config
    hooks inside training runs.
    """
    try:
        dev = device or device_kind()
        if _mx._enabled:
            _m_lookups.inc()
        layers = (("tuned", read_entries(table_file or table_path())),
                  ("shipped", read_entries(shipped_path())))
        for source, entries in layers:
            if not entries:
                continue
            for b in (bucket, WILDCARD_BUCKET):
                ent = entries.get(entry_key(kernel, b, dev))
                if ent is not None:
                    cfg = dict(ent["config"])
                    _note(kernel, source, b, dev, cfg)
                    if _mx._enabled:
                        _m_by_src[source].inc()
                    return cfg, source
        _note(kernel, "default", bucket, dev, None)
        if _mx._enabled:
            _m_by_src["default"].inc()
        return None, "default"
    except Exception as e:  # pragma: no cover - belt and braces
        _log.warning("paddle_tpu.tune: lookup(%s,%s) failed (%s: %s); "
                     "using default config", kernel, bucket,
                     type(e).__name__, e)
        return None, "default"


def resolve_decode_fuse(slots: int) -> Tuple[int, str]:
    """(decode_fuse, source) for a serving engine with ``slots`` batch
    slots — the resolution behind ``ServingConfig(decode_fuse="auto")``.
    (1, "default") on no entry or any table failure: serving must come up
    even with a corrupt table."""
    try:
        cfg, src = lookup("serving.decode_fuse", bucket_slots(slots))
        if cfg and int(cfg.get("decode_fuse", 0)) > 0:
            return int(cfg["decode_fuse"]), src
    except Exception:
        pass
    return 1, "default"


def resolve_fleet_router(cpus: Optional[int] = None
                         ) -> Tuple[Dict[str, object], str]:
    """(router config, source) for the fleet router — the resolution
    behind ``fleet.FleetConfig(replicas="auto")``. The config dict carries ``replicas``
    (int) and ``affinity`` (``"prefix"``/``"round_robin"``), bucketed by
    host CPU count (replica workers are processes — the useful count
    tracks cores, not devices). ``({"replicas": 2, "affinity": "prefix"},
    "default")`` on no entry or any table failure: the fleet must come up
    with no table on disk."""
    default = {"replicas": 2, "affinity": "prefix"}
    try:
        if cpus is None:
            cpus = os.cpu_count() or 1
        cfg, src = lookup("fleet.router", bucket_slots(int(cpus)))
        if cfg and int(cfg.get("replicas", 0)) > 0:
            out = {"replicas": int(cfg["replicas"]),
                   "affinity": cfg.get("affinity", "prefix")}
            if out["affinity"] in ("prefix", "round_robin"):
                return out, src
    except Exception:
        pass
    return default, "default"


def resolve_fleet_roles(cpus: Optional[int] = None
                        ) -> Tuple[Dict[str, int], str]:
    """(role mix, source) for a disaggregated fleet — the resolution
    behind ``fleet.FleetConfig(roles="auto")``. The config dict carries ``prefill``
    and ``decode`` (replica counts per role), bucketed by host CPU count
    like ``fleet.router``. ``({"prefill": 1, "decode": 1}, "default")``
    on no entry or any table failure: a role-split fleet must come up
    with no table on disk."""
    default = {"prefill": 1, "decode": 1}
    try:
        if cpus is None:
            cpus = os.cpu_count() or 1
        cfg, src = lookup("fleet.roles", bucket_slots(int(cpus)))
        if cfg and int(cfg.get("prefill", 0)) > 0 \
                and int(cfg.get("decode", 0)) > 0:
            return ({"prefill": int(cfg["prefill"]),
                     "decode": int(cfg["decode"])}, src)
    except Exception:
        pass
    return default, "default"


def provenance_snapshot() -> Dict[str, dict]:
    """Per-kernel record of the most recent lookup's answer — the bench
    summary tail's ``autotune`` section evidence."""
    with _lock:
        return {k: dict(v) for k, v in _provenance.items()}


def reset_provenance() -> None:
    with _lock:
        _provenance.clear()
