"""Where a traced training step's device time goes by TENSOR SHAPE: the
summed time a step of every operation of ``jit_step`` with an operand or
a result of a given shape (``--shape 96,8,256,256``: the attention's score
tensors), read from the trace a ``grid.run --trace 1`` left behind, and of
those the part whose fusion draws random bits (threefry: told by the
fusion's own instructions in the executable's text, where a dump of it is
given).

    XLA_FLAGS="--xla_dump_to=<dir> --xla_dump_hlo_as_text \\
        --xla_dump_hlo_module_re=jit_step" \\
    python3 -m grid.run --workload tfbase-train-1chip --seed 7 --seconds 40 --trace 1
    python benchmarks/diag_train_split.py --trace grid_out/tfbase-train-1chip/trace \\
        --hlo <dir> --shape 96,8,256,256

An event of the trace is named by its instruction's whole text, operands'
shapes included, so the shapes need no executable; what a fusion holds
inside does. One JSON document: steps traced, milliseconds a step of the
whole step, of the operations that touch the shape (by label, the largest
first), of those that draw bits, and of every Pallas kernel.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from grid import reduce  # noqa: E402

MODULE = "jit_step"
_COMPUTATION = re.compile(r"^%?([\w.\-]+) \(.*\) -> .* \{$")
_FUSION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .* fusion\(.*calls=%([\w.\-]+)")
# a threefry round is a rotate (two shifts and an or) and an xor on u32:
# 20 rounds a draw, so a fusion that draws holds dozens of each
DRAWS_AT = 16


def drawing_fusions(hlo_text: str):
    """Names of the fusion instructions whose computation holds at least
    ``DRAWS_AT`` ``xor`` and as many ``shift-right-logical``."""
    counts, comp = {}, None
    calls = {}
    for line in hlo_text.split("\n"):
        m = _COMPUTATION.match(line.strip())
        if m:
            comp = m.group(1)
            counts[comp] = [0, 0]
            continue
        m = _FUSION.match(line)
        if m:
            calls[m.group(1)] = m.group(2)
        if comp is not None:
            counts[comp][0] += " xor(" in line
            counts[comp][1] += " shift-right-logical(" in line
    return {name for name, c in calls.items()
            if min(counts.get(c, (0, 0))) >= DRAWS_AT}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--trace", required=True)
    ap.add_argument("--hlo", default="")
    ap.add_argument("--shape", default="96,8,256,256")
    ap.add_argument("--top", type=int, default=12)
    args = ap.parse_args(argv)
    trace = reduce.load(reduce.find_xplane(args.trace))
    win = reduce.window(trace)
    runs = reduce.module_runs(trace, MODULE, win)
    if not runs:
        print("diag_train_split: no whole run of %s in the trace" % MODULE,
              file=sys.stderr)
        return 1
    whole = (runs[0][0], runs[-1][1])
    n = len(runs)
    shape = "[%s]" % args.shape

    def ms(pred):
        return reduce.time_where(
            trace, lambda o: o.module == MODULE and pred(o), whole) * 1e3 / n

    touches = lambda o: shape in o.text   # noqa: E731
    by_label = {}
    chip = sorted(trace.ops)[0]
    for o in trace.ops[chip]:
        if o.module == MODULE and touches(o) and whole[0] <= o.start \
                and o.end <= whole[1]:
            lab = reduce.op_label(o)
            by_label[lab] = by_label.get(lab, 0.0) + (o.end - o.start)
    doc = {
        "steps": n, "shape": shape,
        "step_ms": ms(lambda o: True),
        "touching_shape_ms": ms(touches),
        "pallas_ms": ms(lambda o: "tpu_custom_call" in o.text),
        "touching_by_label_ms": {
            k: round(v * 1e3 / n, 3) for k, v in sorted(
                by_label.items(), key=lambda kv: -kv[1])[:args.top]},
        "touching_ops_a_step": sum(
            1 for o in trace.ops[chip] if o.module == MODULE and touches(o)
            and runs[0][0] <= o.start and o.end <= runs[0][1]),
    }
    texts = sorted(glob.glob(os.path.join(
        args.hlo, "*%s*after_optimizations.txt" % MODULE))) if args.hlo else []
    if texts:
        with open(max(texts, key=os.path.getsize)) as f:
            draws = drawing_fusions(f.read())
        doc["drawing_fusions"] = len(draws)
        doc["drawing_ms"] = ms(lambda o: o.name in draws)
        doc["drawing_and_touching_ms"] = ms(
            lambda o: o.name in draws and touches(o))
    print(json.dumps(doc, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
