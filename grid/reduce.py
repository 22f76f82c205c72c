"""From the profiler's ``.xplane.pb`` to numbers: device busy and idle time,
time per operation, the part of the collectives no compute hides, and what
the host was doing in each idle gap.

The reduction has two stages so that ``grid/tests`` can pin the arithmetic
on a small recorded trace. :func:`load` reads the file with nothing but JAX
(``jax.profiler.ProfileData``) into a :class:`Trace` of plain tuples;
everything else works on a :class:`Trace`, which a test can also build by
hand or load from ``grid/tests/data/*.json``.

What a TPU trace looks like (jax 0.9, libtpu 0.0.34; looked at by hand on a
v5e before this was written): one plane ``/device:TPU:<n>`` a chip, whose
line ``XLA Ops`` holds one event for each operation the chip ran, named by
the whole text of its HLO instruction (``%copy.112 = bf16[12,32768,12,64]{..}
copy(..)``; a Pallas kernel is a ``custom-call`` whose text holds
``custom_call_target="tpu_custom_call"``), and whose line ``XLA Modules``
holds one event for each executable run, named
``jit_<function>(<fingerprint>)``; asynchronous copies sit on a line of
their own (``Async XLA Ops``) and are not counted as busy time; the plane
``/host:CPU`` holds a line a thread, in which ``TraceAnnotation`` spans
appear under the name they were given. All on one clock, in nanoseconds.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # start, end, in seconds

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "grid/"
# HLO opcodes that move data between chips; "-start"/"-done" halves of an
# asynchronous one count with it
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)")


class Op(NamedTuple):
    name: str        # HLO instruction name, e.g. "fusion.123"
    module: str      # the executable it ran in, e.g. "jit_step"
    start: float
    end: float
    opcode: str      # e.g. "copy", "fusion", "custom-call", "all-reduce"
    shape: str       # result shape where the trace gives one, else ""
    text: str        # the event's whole name (the HLO instruction's text)


_HLO = re.compile(r"^%(?P<name>\S+) = (?P<res>\(.*?\)|\S+) "
                  r"(?P<op>[\w\-]+)\(")
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
_parsed: Dict[str, Tuple[str, str, str]] = {}


def parse_hlo(text: str) -> Tuple[str, str, str]:
    """(instruction name, opcode, result shape) of an ``XLA Ops`` event's
    name. A name that is no HLO text (a CPU trace's ``dot_general.1``) is
    its own instruction name, and its opcode the name without its number."""
    hit = _parsed.get(text)
    if hit is None:
        m = _HLO.match(text)
        if m:
            shape = _SHAPE.search(m.group("res"))
            hit = (m.group("name"), m.group("op"),
                   shape.group(0) if shape else "")
        else:
            hit = (text, re.sub(r"[.\d]+$", "", text), "")
        _parsed[text] = hit
    return hit


class Trace(NamedTuple):
    ops: Dict[int, List[Op]]            # chip ordinal -> its operations
    modules: Dict[int, List[Op]]        # chip ordinal -> executable runs
    spans: List[Tuple[str, float, float]]   # the grid's host spans

    def to_json(self) -> str:
        """Compact: each distinct instruction text once."""
        texts: Dict[str, int] = {}

        def rows(ops):
            return [[texts.setdefault(o.text, len(texts)), o.start, o.end]
                    for o in ops]

        doc = {"ops": {str(c): rows(v) for c, v in self.ops.items()},
               "modules": {str(c): rows(v)
                           for c, v in self.modules.items()},
               "spans": self.spans}
        doc["texts"] = list(texts)
        return json.dumps(doc)

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        doc = json.loads(text)
        texts = doc["texts"]
        modules = {int(c): [_module(texts[i], s, e) for i, s, e in v]
                   for c, v in doc["modules"].items()}
        ops = {int(c): _attach_modules(
                   [_op(texts[i], s, e) for i, s, e in v],
                   modules.get(int(c), []))
               for c, v in doc["ops"].items()}
        return cls(ops, modules, [tuple(s) for s in doc["spans"]])

    def cut(self, lo: float, hi: float) -> "Trace":
        """The events that lie wholly inside [lo, hi]."""
        def inside(ops):
            return [o for o in ops if lo <= o.start and o.end <= hi]

        return Trace({c: inside(v) for c, v in self.ops.items()},
                     {c: inside(v) for c, v in self.modules.items()},
                     [s for s in self.spans if lo <= s[1] and s[2] <= hi])


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return found[-1]


def _module_name(event_name: str) -> str:
    return event_name.split("(")[0]


def load(xplane_path: str) -> Trace:
    """Stage one: the file, read with JAX alone."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    ops: Dict[int, List[Op]] = {}
    modules: Dict[int, List[Op]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[chip] = sorted(
                        (_module(e.name, e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9)
                         for e in line.events), key=lambda o: o.start)
                elif line.name == OPS_LINE:
                    ops[chip] = sorted(
                        (_op(e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                         for e in line.events), key=lambda o: o.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9))
    for chip, chip_ops in ops.items():
        ops[chip] = _attach_modules(chip_ops, modules.get(chip, []))
    spans.sort(key=lambda s: s[1])
    return Trace(ops, modules, spans)


def _module(text: str, start: float, end: float) -> Op:
    name = _module_name(text)
    return Op(name, name, start, end, "module", "", text)


def _op(text: str, start: float, end: float) -> Op:
    name, opcode, shape = parse_hlo(text)
    return Op(name, "", start, end, opcode, shape, text)


def _attach_modules(ops: List[Op], modules: List[Op]) -> List[Op]:
    """Name each operation's executable: the module run that contains its
    start (both lists are sorted by start)."""
    out, i = [], 0
    for op in ops:
        while i < len(modules) and modules[i].end <= op.start:
            i += 1
        inside = i < len(modules) and modules[i].start <= op.start
        out.append(op._replace(module=modules[i].name if inside else ""))
    return out


# -- interval arithmetic ------------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint, sorted intervals covering the same instants."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The instants of ``a`` (disjoint, sorted) that no interval of ``b``
    (disjoint, sorted) covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# -- the reductions -------------------------------------------------------------


def window(trace: Trace) -> Interval:
    """The traced stretch: from the start of the first ``grid/`` span to the
    end of the last, so that the profiler's own start-up and shut-down are
    outside it. Without spans, from the first device event to the last."""
    if trace.spans:
        return (min(s for _, s, _ in trace.spans),
                max(e for _, _, e in trace.spans))
    evs = [o for ops in trace.ops.values() for o in ops]
    if not evs:
        raise ValueError("the trace holds no device operation and no span")
    return min(o.start for o in evs), max(o.end for o in evs)


def is_collective(op: Op) -> bool:
    return bool(COLLECTIVE.match(op.opcode) or COLLECTIVE.match(op.name))


def busy(trace: Trace, chip: int, win: Optional[Interval] = None
         ) -> List[Interval]:
    """The instants in which an operation ran on ``chip``."""
    lo, hi = win or window(trace)
    return clip(union((o.start, o.end) for o in trace.ops.get(chip, [])),
                lo, hi)


def busy_seconds(trace: Trace, win: Optional[Interval] = None) -> float:
    """Seconds in which an operation ran, averaged over the chips used."""
    chips = sorted(trace.ops)
    if not chips:
        return 0.0
    return sum(total(busy(trace, c, win)) for c in chips) / len(chips)


def idle_share(trace: Trace, win: Optional[Interval] = None) -> float:
    lo, hi = win or window(trace)
    return 1.0 - busy_seconds(trace, (lo, hi)) / (hi - lo)


def op_label(op: Op) -> str:
    """A name that survives renumbering: executable, instruction and
    shape, as in ``jit_chunk:copy_bf16[12,32768,12,64]``; the digits that
    only number an instruction are dropped."""
    base = re.sub(r"[.\d]+$", "", op.name)
    label = "%s:%s" % (op.module or "?", base)
    return label + ("_" + op.shape if op.shape else "")


def time_by_label(trace: Trace, win: Optional[Interval] = None
                  ) -> Dict[str, float]:
    """Device seconds of each label, averaged over the chips."""
    lo, hi = win or window(trace)
    out: Dict[str, float] = {}
    n = max(len(trace.ops), 1)
    for ops in trace.ops.values():
        for o in ops:
            d = min(o.end, hi) - max(o.start, lo)
            if d > 0:
                out[op_label(o)] = out.get(op_label(o), 0.0) + d / n
    return out


def time_where(trace: Trace, pred, win: Optional[Interval] = None) -> float:
    """Device seconds (union, so nested events count once) of the
    operations ``pred`` accepts, averaged over the chips."""
    lo, hi = win or window(trace)
    chips = sorted(trace.ops)
    if not chips:
        return 0.0
    return sum(total(clip(union((o.start, o.end) for o in trace.ops[c]
                                if pred(o)), lo, hi))
               for c in chips) / len(chips)


def exposed_collective_seconds(trace: Trace, win: Optional[Interval] = None
                               ) -> float:
    """Seconds in which a collective ran on a chip and no compute did,
    averaged over the chips: what the step really waits for."""
    lo, hi = win or window(trace)
    chips = sorted(trace.ops)
    if not chips:
        return 0.0
    exposed = 0.0
    for c in chips:
        coll = clip(union((o.start, o.end) for o in trace.ops[c]
                          if is_collective(o)), lo, hi)
        comp = clip(union((o.start, o.end) for o in trace.ops[c]
                          if not is_collective(o)), lo, hi)
        exposed += total(subtract(coll, comp))
    return exposed / len(chips)


def module_runs(trace: Trace, name: str, win: Optional[Interval] = None
                ) -> List[Interval]:
    """The runs of executable ``name`` that lie wholly inside the window,
    on the first chip (each chip of a data-parallel step runs it once)."""
    lo, hi = win or window(trace)
    chips = sorted(trace.modules)
    if not chips:
        return []
    return [(m.start, m.end) for m in trace.modules[chips[0]]
            if m.name == name and lo <= m.start and m.end <= hi]


def idle_gaps_by_span(trace: Trace, win: Optional[Interval] = None
                      ) -> Dict[str, float]:
    """Each idle instant of the first chip, given to the ``grid/`` span
    that covers it (the innermost where spans nest; ``(no span)`` where
    none does): seconds by span name."""
    lo, hi = win or window(trace)
    chips = sorted(trace.ops)
    if not chips:
        return {}
    gaps = subtract([(lo, hi)], busy(trace, chips[0], (lo, hi)))
    out: Dict[str, float] = {}
    # what is left unclaimed, disjoint and sorted, as two lists, so that a
    # span looks only at the gaps it overlaps: a GPT-2 stretch holds some
    # hundred thousand gaps and two thousand spans, and passing over every
    # gap once a span took ten minutes of a traced run
    starts = [g[0] for g in gaps]
    ends = [g[1] for g in gaps]
    # shorter spans first, so a nested span claims its part before the
    # span around it
    for name, s, e in sorted(trace.spans, key=lambda x: x[2] - x[1]):
        i = bisect.bisect_right(ends, s)
        j = bisect.bisect_left(starts, e)
        if i >= j:
            continue
        out[name] = out.get(name, 0.0) + sum(
            min(ends[k], e) - max(starts[k], s) for k in range(i, j))
        keep = []
        if starts[i] < s:
            keep.append((starts[i], s))
        if ends[j - 1] > e:
            keep.append((e, ends[j - 1]))
        starts[i:j] = [k[0] for k in keep]
        ends[i:j] = [k[1] for k in keep]
    rest = sum(b - a for a, b in zip(starts, ends))
    if rest > 0:
        out["(no span)"] = rest
    return out


def breakdown(trace: Trace, win: Optional[Interval] = None, top: int = 10
              ) -> Dict[str, List[List]]:
    """The last line's ``breakdown``: the device operations that took most
    time and the longest idle gaps by what the host was doing."""
    def first(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"device_ops": first(time_by_label(trace, win)),
            "idle_gaps": first(idle_gaps_by_span(trace, win))}
