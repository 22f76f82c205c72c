"""Histogram large tensors in the compiled paddle vs raw-JAX Transformer
steps, localizing the bytes-accessed gap from diag_overhead.py (which dumps
/tmp/hlo_paddle.txt and /tmp/hlo_raw.txt — run it first on the TPU).
"""
import collections
import re
import sys

import numpy as np

DTYPE_BYTES = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "pred": 1,
               "s64": 8, "u64": 8, "f16": 2, "s8": 1, "u8": 1}

SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s64|u64|pred|s8|u8)\[([\d,]+)\]")


def big_shapes(path, min_mb=64):
    counts = collections.Counter()
    with open(path) as f:
        for line in f:
            # only instruction definitions (lhs shape), not operand uses
            head = line.split("=", 1)
            if len(head) != 2:
                continue
            m = SHAPE_RE.search(head[1].strip())
            if not m or not head[1].strip().startswith(("f32[", "bf16[", "f16[",
                                                        "s32[", "u32[", "s64[",
                                                        "u64[", "pred[", "s8[",
                                                        "u8[", "(")):
                continue
            for m in SHAPE_RE.finditer(head[1].split(")", 1)[0]
                                       if head[1].strip().startswith("(")
                                       else m.group(0)):
                dt, dims = m.group(1), m.group(2)
                n = int(np.prod([int(d) for d in dims.split(",")]))
                mb = n * DTYPE_BYTES[dt] / 1e6
                if mb >= min_mb:
                    counts["%s[%s] %.0fMB" % (dt, dims, mb)] += 1
    return counts


def main(min_mb=64):
    pc = big_shapes("/tmp/hlo_paddle.txt", min_mb)
    rc = big_shapes("/tmp/hlo_raw.txt", min_mb)
    keys = sorted(set(pc) | set(rc),
                  key=lambda k: -(pc.get(k, 0) + rc.get(k, 0)))
    print("%-44s %8s %8s" % ("shape (instruction outputs)", "paddle", "raw"))
    for k in keys:
        if pc.get(k, 0) != rc.get(k, 0):
            print("%-44s %8d %8d" % (k, pc.get(k, 0), rc.get(k, 0)))


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 64)
