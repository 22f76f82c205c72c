"""Where a traced training step's device time goes by TENSOR SHAPE: the
summed time a step of every operation of ``jit_step`` with an operand or
a result of a given shape (``--shape 96,8,256,256``: the attention's score
tensors), read from the trace a ``grid.run --trace 1`` left behind, and,
where a dump of the executable's text is given, WHAT ITS FUSIONS HOLD
(told by a fused computation's own instructions): the fusions that draw a
dropout mask, by generator (a threefry chain; the coordinate hash of
``paddle_tpu/ops/keep_hash.py``, told by its two multipliers), their time
a step by label, and for each of the ten largest labels of the step
whether its fusions hold a matrix product (``convolution``), a ``divide``
that comes from ``optimizer_ops.py`` (the Adam update:
``diag_adam_fusion.py``'s test) and a draw. A label is a fusion's FIRST
result, so the label alone does not say.

    XLA_FLAGS="--xla_dump_to=<dir> --xla_dump_hlo_as_text \\
        --xla_dump_hlo_module_re=jit_step" \\
    python3 -m grid.run --workload tfbase-train-1chip --seed 7 --seconds 40 --trace 1
    python benchmarks/diag_train_split.py --trace grid_out/tfbase-train-1chip/trace \\
        --hlo <dir> --shape 96,8,256,256

(a step loaded from the compile cache is not dumped: give that run an
empty ``JAX_COMPILATION_CACHE_DIR``.) An event of the trace is named by its
instruction's whole text, operands' shapes included, so the shapes need no
executable; what a fusion holds inside does. One JSON document: steps
traced, milliseconds a step of the whole step, of the operations that touch
the shape (by label, the largest first), of those that draw, by generator,
and of every Pallas kernel. Two traced runs, a parent's and a change's,
give the before and after of the drawing fusions.
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
from paddle_tpu.ops import keep_hash  # noqa: E402

MODULE = "jit_step"
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_FUSION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .* fusion\(.*calls=%([\w.\-]+)")
# a threefry round is a rotate (two shifts and an or) and an xor on u32:
# 20 rounds a draw, so a fusion that draws holds dozens of each. The hash
# has no shift-left: three sites' hashes in one fusion are not a chain
DRAWS_AT = 16
# what is counted a computation: a name and the text an instruction has
_COUNTED = {
    "xor": " xor(", "shift_right": " shift-right-logical(",
    "shift_left": " shift-left(", "product": " convolution(",
    "mul_a": " constant(%d)" % keep_hash.MUL_A,
    "mul_b": " constant(%d)" % keep_hash.MUL_B}
_ADAM_FILE = "optimizer_ops.py"
_FRAME = re.compile(r"stack_frame_id=(\d+)")


def frames_of(hlo_text: str, file_name: str):
    """The ``stack_frame_id``s of the text's own tables (``FileNames``,
    ``FileLocations``, ``StackFrames``) with a frame in ``file_name``: an
    instruction's metadata names a frame, not a file."""
    tables, name = {}, None
    for line in hlo_text.split("\n"):
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            name = line
        elif name and line[:1].isdigit():
            key, _, rest = line.partition(" ")
            tables.setdefault(name, {})[int(key)] = rest
        elif line.startswith(("ENTRY", "%")):
            break
    files = {k for k, v in tables.get("FileNames", {}).items()
             if v.strip('"').endswith(file_name)}
    field = lambda v, f: int(re.search(f + r"=(\d+)", v).group(1))  # noqa: E731
    places = {k for k, v in tables.get("FileLocations", {}).items()
              if field(v, "file_name_id") in files}
    frames = {k: (field(v, "file_location_id"), field(v, "parent_frame_id"))
              for k, v in tables.get("StackFrames", {}).items()}
    inside = set()
    for k in sorted(frames):        # a parent's id is below its child's
        place, parent = frames[k]
        if place in places or parent in inside:
            inside.add(k)
    return inside


def fusion_contents(hlo_text: str):
    """``{fusion instruction: set of what its computation holds}``, what
    the fusions nested in it hold included, out of ``threefry`` (at least
    ``DRAWS_AT`` each of ``xor``, ``shift-right-logical`` and ``shift-left``
    in one computation), ``hash`` (both of the mixer's multipliers),
    ``product`` (a ``convolution``) and ``adam`` (a ``divide`` from
    ``optimizer_ops.py``)."""
    adam_frames = frames_of(hlo_text, _ADAM_FILE)
    counts, nested, comp, calls = {}, {}, None, {}
    for line in hlo_text.split("\n"):
        m = _COMPUTATION.match(line.strip())
        if m:
            comp = m.group(1)
            counts[comp], nested[comp] = dict.fromkeys(_COUNTED, 0), []
            counts[comp]["adam"] = 0
            continue
        if comp is None:
            continue
        m = _FUSION.match(line)
        if m:
            calls[m.group(1)] = m.group(2)
            nested[comp].append(m.group(2))
        for name, text in _COUNTED.items():
            counts[comp][name] += text in line
        if " divide(" in line:
            frame = _FRAME.search(line)
            counts[comp]["adam"] += _ADAM_FILE in line or (
                frame is not None and int(frame.group(1)) in adam_frames)

    def held(called, seen=()):
        c = counts.get(called)
        kinds = set() if c is None else {kind for kind, has in (
            ("threefry", min(c["xor"], c["shift_right"],
                             c["shift_left"]) >= DRAWS_AT),
            ("hash", c["mul_a"] and c["mul_b"]),
            ("product", c["product"]), ("adam", c["adam"])) if has}
        for inner in nested.get(called, ()):
            if inner not in seen:
                kinds |= held(inner, seen + (called,))
        return kinds

    return {name: held(called) for name, called in calls.items()}


def drawing_fusions(hlo_text: str):
    """Names of the fusion instructions that hold a threefry chain."""
    return {name for name, held in fusion_contents(hlo_text).items()
            if "threefry" in held}


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
            holds = fusion_contents(f.read())
        step_ops = [o for o in trace.ops[chip] if o.module == MODULE
                    and whole[0] <= o.start and o.end <= whole[1]]

        def per_step(ops):
            return round(sum(o.end - o.start for o in ops) * 1e3 / n, 3)

        def largest(ops, top):
            """(label, its ops) of the ``top`` labels with most time."""
            labels = {}
            for o in ops:
                labels.setdefault(reduce.op_label(o), []).append(o)
            return sorted(labels.items(),
                          key=lambda kv: -per_step(kv[1]))[:top]

        def holding(kind):
            return lambda o: kind in holds.get(o.name, ())

        for kind in ("threefry", "hash"):
            drawn = list(filter(holding(kind), step_ops))
            doc["drawing_" + kind] = {
                "fusions": len({o.name for o in drawn}),
                "ms": ms(holding(kind)),
                "touching_shape_ms": ms(
                    lambda o: holding(kind)(o) and touches(o)),
                "with_a_product_ms": per_step(
                    filter(holding("product"), drawn)),
                "by_label_ms": {lab: per_step(ops)
                                for lab, ops in largest(drawn, args.top)},
            }
        doc["largest_labels"] = {
            lab: dict(
                {"ms": per_step(ops),
                 "instructions": len({o.name for o in ops})},
                **{"with_" + kind: len({
                    o.name for o in filter(holding(kind), ops)})
                   for kind in ("product", "adam", "threefry", "hash")})
            for lab, ops in largest(step_ops, 10)}
    print(json.dumps(doc, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
