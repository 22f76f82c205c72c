"""Where a traced ``evabyte-file-sat`` step's device time goes, by the
``jax.named_scope`` the instructions ran under: the cell's own traced run
through ``grid.run`` (its command line is this script's), and after its
last line one JSON line to ``chiprun_out/diag_eva_step.json`` and to
standard error: for the decode and the prefill executables, the device
seconds of the traced stretch under each of ``grid/readers/eva.SCOPES``
(``attn/eva_prefill`` is the prefill's attention, whichever form
``attn/eva_prefill_calls.kernel|blocked``, printed beside, says it took),
under none of them (and that time by the instructions' kind: the waits
for asynchronous copies are among them), the whole module's, the decode
steps and prefills in the stretch, every Pallas call and ``while`` by name
and result (``eva_prefill_attention_bf16[4096,8192]``: the prefill's
attention, too short for the ten labels of the run's own ``breakdown``), and
the twenty instructions that took most time with the scopes that claim
them; and, from the decode
executable's own scheduled text, the asynchronous copies into fast memory
(``S(1)``) with their bytes, by what they fetch, and which of them are in
flight ACROSS a paged kernel's call (started before it, waited for after
it): the weights' bytes that the products' own time does not hold
(``grid/readers/eva.eva_weight_stream_roofline``); the text itself goes
to ``chiprun_out/diag_eva_chunk_<fuse>.hlo.txt``. About three minutes of
chip.

    python benchmarks/diag_eva_step.py --workload evabyte-file-sat \
        --seed 7 --seconds 40 --trace 1
"""

from __future__ import annotations

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


_ITEM = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1, "s8": 1}
_LINE = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\((.*)$")


def _bytes(rtype: str) -> int:
    """Bytes of an instruction's FIRST array type, e.g. of
    ``bf16[1024,4096]{1,0:T(8,128)(2,1)S(1)}``."""
    m = re.search(r"(\w+)\[([\d,]*)\]", rtype)
    n = 1
    for d in filter(None, m.group(2).split(",")):
        n *= int(d)
    return n * _ITEM.get(m.group(1), 4)


def async_copies(text: str) -> dict:
    """The ``copy-start``/``slice-start`` pairs of a scheduled module (a
    slice's may be printed as ``async-start``/``async-done``) whose result
    lies in ``S(1)``: bytes by the operand they fetch (a layer's
    number taken out of a parameter's name), and those whose start stands
    before a Pallas call and whose done after it."""
    order, starts, calls = [], {}, []
    for n, line in enumerate(text.split("\n")):
        m = _LINE.match(line)
        if not m:
            continue
        name, rtype, op, rest = m.groups()
        if op in ("copy-start", "slice-start", "async-start"):
            starts[name] = [n, None, re.match(r"%?([^,)\s]+)", rest).group(1)]
        elif op in ("copy-done", "slice-done", "async-done") \
                and "S(1)" in rtype:
            src = re.match(r"%?([^,)\s]+)", rest).group(1)
            if src in starts:
                starts[src][1] = n
                order.append((src, _bytes(rtype)))
        elif "tpu_custom_call" in line:
            calls.append(n)
    by_what, across = {}, {}
    for src, size in order:
        at, done, operand = starts[src]
        what = re.sub(r"layers___\d+___", "layers___N___",
                      re.sub(r"\.\d+$", "", operand))
        by_what[what] = by_what.get(what, 0) + size
        if any(at < c < done for c in calls):
            across[what] = across.get(what, 0) + size
    return {"pallas_calls": len(calls), "bytes_by_operand": by_what,
            "bytes_across_a_pallas_call": across,
            "total_bytes": sum(by_what.values()),
            "total_across": sum(across.values())}


def main(argv) -> int:
    from grid import manifest, reduce, run
    from grid.drivers import serve_eva
    from grid.readers import eva

    seen = {}
    scoped_ops = serve_eva.scoped_ops

    def keeping_the_text(engine):
        seen["copies"] = {}
        for k, x in engine._decode_exe.items():
            text = x.as_text()
            seen["copies"][str(k)] = async_copies(text)
            os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
            with open(os.path.join(ROOT, "chiprun_out",
                                   "diag_eva_chunk_%s.hlo.txt" % k), "w") as f:
                f.write(text)
        return scoped_ops(engine)

    serve_eva.scoped_ops = keeping_the_text
    reader = manifest.reader

    def capturing(spec):
        fn = reader(spec)

        def read(record, trace):
            seen.update(record=record, trace=trace)
            return fn(record, trace)

        return read

    manifest.reader = capturing
    rc = run.main(argv)
    record, trace = seen.get("record"), seen.get("trace")
    if rc or trace is None:
        return rc
    win = tuple(record["trace_window"])
    from paddle_tpu.monitor import metrics

    buckets = eva._traced_buckets(record)
    out = {"decode_steps": eva._tail(record, "steps_n"),
           "prefills": len(buckets), "traced_buckets": buckets,
           # which form each traced prefill attention took (a layer a
           # prefill executable; an executable from the persistent cache
           # is still traced in this process)
           "eva_prefill_calls": {form: int(metrics.counter(
               "attn/eva_prefill_calls." + form).value)
               for form in ("kernel", "blocked")},
           "busy_s": reduce.busy_seconds(trace, win),
           "decode_async_copies": seen.get("copies")}
    for module, ops in record["scoped_ops"].items():
        claimed = {}
        for scope, names in ops.items():
            for name in names:
                claimed.setdefault(name, []).append(scope)
        whole = reduce.time_where(trace, lambda o: o.module == module, win)
        by_scope = {scope: reduce.time_where(
            trace, eva._named(record, module, scope), win) for scope in ops}
        by_scope["(none)"] = reduce.time_where(
            trace, lambda o: o.module == module and o.name not in claimed,
            win)
        by_name = {}
        for chip_ops in trace.ops.values():
            for o in chip_ops:
                if o.module == module and win[0] <= o.start <= win[1]:
                    t = by_name.setdefault(o.name, [0.0, o.opcode, o.shape])
                    t[0] += o.end - o.start
        top = sorted(by_name.items(), key=lambda kv: -kv[1][0])[:20]
        unscoped = {}
        for name, (t, op, _) in by_name.items():
            if name not in claimed:
                kind = re.sub(r"\.\d+$", "", name)
                kind = kind if "start" in kind or "done" in kind else op
                unscoped[kind] = unscoped.get(kind, 0.0) + t
        calls = {}      # the Pallas calls by kernel and result, the loops
        for name, (t, op, shape) in by_name.items():
            if op in ("custom-call", "while"):
                label = "%s_%s" % (re.sub(r"\.\d+$", "", name), shape)
                calls[label] = calls.get(label, 0.0) + t
        out[module] = {"whole_s": whole, "by_scope_s": by_scope,
                       "kernels_and_loops_s": calls,
                       "unscoped_by_kind_s": dict(sorted(
                           unscoped.items(), key=lambda kv: -kv[1])[:12]),
                       "top": [[n, round(t, 5), op, shape, claimed.get(n, [])]
                               for n, (t, op, shape) in top]}
    text = json.dumps(out)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "diag_eva_step.json"),
              "w") as f:
        f.write(text + "\n")
    print(text, file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
