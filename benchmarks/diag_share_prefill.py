"""What ONE expert layer's share costs a prefill on the chip, apart from
everything around it: ``moe_ops._share`` alone at the five served shares'
largest prompt bucket (pairs, experts held of experts, widths from
``grid/configs/*.json``), the pairs drawn by an even router and sorted as
``expert_layer`` sorts them, in each form a pass can give its rows back in
(``scatter``, ``gather``: ``moe_ops.combine_form`` made to answer each in
turn, whatever the pass's part of the pairs) and at each pass size in
question (``twice``: two even loads, what a pass held until PR 61;
``margin``: ``moe_ops._share_rows``'s; ``short``: three quarters of an even
load, so that a full prompt needs a second pass).

    python benchmarks/diag_share_prefill.py [--only laguna,kimi]
        [--fill 1.0,0.7] [--no-trace]

One JSON line a point. ``ms``: the median of ``--reps`` timings of
``--chain`` calls chained in one executable, a call's ``u`` fed by the one
before. ``by_instruction_ms``: ONE traced call's device time by the
instruction of the loop's body that spent it, named by the tail of its
``op_name`` in the executable's own text and its result's shape (the
gather of ``u``, the three ``ragged-dot`` calls, the float32 weighting, the
scatter-add and what the compiler makes of it, the gather that returns the
rows). ``ragged_dead``: the three grouped matmuls over a pass half of whose
rows lie past the last group against the same groups in a pass cut to its
live rows: whether the compiler's ``ragged_dot`` skips the tiles past the
last group. Run on the chip; it refuses another backend.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import shutil
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "grid", "configs")

# name: (configuration file, largest prompt bucket of its cells' traffic,
# the keys of: experts a token, experts published, experts held, d, f)
SHARES = {
    "laguna": ("laguna-s-ep2-serve", 8192, "num_experts_per_tok",
               "num_experts", "hidden_size", "moe_intermediate_size"),
    "ling": ("ling-3-flash-ep4-serve", 8192, "num_experts_per_tok",
             "num_experts", "hidden_size", "moe_intermediate_size"),
    "glm": ("glm-5.3-flash-ep8-serve", 8192, "num_experts_per_tok",
            "n_routed_experts", "hidden_size", "moe_intermediate_size"),
    "motif": ("motif-3-beta-ep16-serve", 8192, "experts_top_k",
              "num_experts", "hidden_size", "moe_intermediate_size"),
    "kimi": ("kimi-k2-ep32-serve", 4096, "num_experts_per_tok",
             "n_routed_experts", "hidden_size", "moe_intermediate_size"),
}


def geometry(name):
    """``(n, k, e_held, n_expert, d, f)``: the held experts are the
    configuration's own count, the experts the published one."""
    path, n, k, e, d, f = SHARES[name]
    with open(os.path.join(CONFIGS, path + ".json")) as fh:
        cfg = json.load(fh)
    return n, cfg[k], cfg[e], cfg["published"][e], cfg[d], cfg[f]


def draw(seed, n, k, e_held, n_expert, d, f, fill):
    """An even router's pairs (``k`` distinct experts a token, uniform over
    ``n_expert``), the first ``e_held`` held, the first ``fill`` of the
    rows a prompt's; sorted as ``expert_layer`` sorts them."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    bf = jnp.bfloat16
    u = jax.random.normal(ks[0], (n, d), jnp.float32).astype(bf)
    idx = jax.lax.top_k(jax.random.uniform(ks[1], (n, n_expert)), k)[1]
    w = jax.nn.softmax(jax.random.normal(ks[2], (n, k), jnp.float32), -1)
    wg = (jax.random.normal(ks[3], (e_held, d, f), jnp.float32)
          / np.sqrt(d)).astype(bf)
    wu = (jax.random.normal(ks[4], (e_held, d, f), jnp.float32)
          / np.sqrt(d)).astype(bf)
    wd = (jax.random.normal(ks[5], (e_held, f, d), jnp.float32)
          / np.sqrt(f)).astype(bf)
    valid = jnp.arange(n) < int(round(fill * n))
    flat = jnp.where(idx < e_held, idx, e_held).reshape(n * k)
    flat = jnp.where(jnp.repeat(valid, k), flat, e_held)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((e_held + 1,), jnp.int32).at[flat].add(1)[:e_held]
    return u, w, wg, wu, wd, order, sizes


@contextlib.contextmanager
def forced(combine):
    """``moe_ops._share`` traced inside combines its passes by
    ``combine``, whatever their part of the pairs."""
    from paddle_tpu.ops import moe_ops

    rule = moe_ops.combine_form
    moe_ops.combine_form = lambda *a: combine
    try:
        yield
    finally:
        moe_ops.combine_form = rule


def chained(rows, chain):
    from paddle_tpu.ops import moe_ops

    def run(u, w, wg, wu, wd, order, sizes):
        def body(_, x):
            y = moe_ops._share(x, w, wg, wu, wd, order, sizes, jax.nn.silu,
                               rows)
            return (x + y * 1e-3).astype(x.dtype)
        return jax.lax.fori_loop(0, chain, body, u)
    return jax.jit(run)


def time_ms(fn, args, reps, chain):
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append((time.perf_counter() - t0) / chain)
    return statistics.median(out) * 1e3


_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\S+) ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def by_instruction(rows, args):
    """One traced call: ``{tail of op_name + result: ms}``, the largest
    first, of the executable's own instructions (the loops that hold them
    left out, so nothing counts twice)."""
    from grid import reduce
    from paddle_tpu.ops import moe_ops

    fn = jax.jit(lambda u, w, wg, wu, wd, order, sizes: moe_ops._share(
        u, w, wg, wu, wd, order, sizes, jax.nn.silu, rows))
    compiled = fn.lower(*args).compile()
    named = {}
    for line in compiled.as_text().split("\n"):
        m = _INSTRUCTION.match(line)
        op = _OP_NAME.search(line)
        if m:
            where = op.group(1).split("while/body/")[-1] if op else "-"
            named[m.group(1)] = "%s %s" % (
                where.replace("jit(<lambda>)/", ""), m.group(2).split("{")[0])
    jax.block_until_ready(compiled(*args))
    trace_dir = tempfile.mkdtemp(prefix="share_trace_")
    try:
        with jax.profiler.trace(trace_dir):
            jax.block_until_ready(compiled(*args))
        trace = reduce.load(reduce.find_xplane(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    out = {}
    for ops in trace.ops.values():
        for o in ops:
            if o.opcode in ("while", "call", "conditional"):
                continue
            label = named.get(o.name, o.name)
            out[label] = out.get(label, 0.0) + (o.end - o.start) * 1e3
    return dict(sorted(out.items(), key=lambda kv: -kv[1])[:16])


def ragged_dead(args, rows, reps, chain):
    """The grouped feed-forward over ``rows`` rows of which the groups
    hold the first half, and over that half alone."""
    from paddle_tpu.ops import moe_ops

    u, w, wg, wu, wd, order, sizes = args
    k = w.shape[1]
    half = rows // 2
    ends = jnp.cumsum(sizes)
    part = jnp.clip(ends, 0, half) - jnp.clip(ends - sizes, 0, half)
    out = {}
    for name, m in (("half_dead", rows), ("cut_to_live", half)):
        xs = u[order[:m] // k]

        def run(xs, wg, wu, wd, part):
            def body(_, x):
                y = moe_ops._ragged_ffn(x, wg, wu, wd, part, jax.nn.silu)
                live = (jnp.arange(x.shape[0]) < half)[:, None]
                return (x + jnp.where(live, y, 0) * 1e-3).astype(x.dtype)
            return jax.lax.fori_loop(0, chain, body, xs)
        out[name + "_ms"] = time_ms(jax.jit(run), (xs, wg, wu, wd, part),
                                    reps, chain)
        out[name + "_rows"] = m
    out["live_rows"] = int(jnp.sum(part))
    return out


def main():
    from paddle_tpu.ops import moe_ops

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--fill", default="1.0,0.7")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--chain", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-trace", action="store_true")
    ap.add_argument("--shrink", type=int, default=1,
                    help="divide rows and widths (a rehearsal off the chip)")
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--out", default="")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.allow_cpu:
        print(json.dumps({"ok": False, "why": "no TPU: %s" % dev.platform}))
        return 1
    fills = [float(x) for x in a.fill.split(",")]
    lines = []

    def emit(line):
        lines.append(line)
        print(json.dumps(line), flush=True)

    for name in SHARES:
        if a.only and name not in a.only.split(","):
            continue
        n, k, e_held, n_expert, d, f = geometry(name)
        n, d, f = n // a.shrink, d // a.shrink, f // a.shrink
        even = -(-n * k * e_held // n_expert)
        sizes_of = {
            "twice": min(n * k, moe_ops._tiles(2 * even)),
            "margin": moe_ops._share_rows(n * k, e_held, n_expert),
            "short": moe_ops._tiles(3 * even // 4)}
        base = {"share": name, "n": n, "k": k, "held": e_held,
                "experts": n_expert, "d": d, "f": f, "even": even,
                "chosen": moe_ops.combine_form(sizes_of["margin"], n * k,
                                               e_held)}
        data = {fill: draw(a.seed, n, k, e_held, n_expert, d, f, fill)
                for fill in fills}
        for size, rows in sizes_of.items():
            for combine in ("scatter", "gather"):
                fn = chained(rows, a.chain)
                for fill, args in data.items():
                    total = int(jnp.sum(args[-1]))
                    with forced(combine):   # the first call traces
                        ms = time_ms(fn, args, a.reps, a.chain)
                        line = dict(base, pass_rows=rows, size=size,
                                    combine=combine, fill=fill,
                                    held_pairs=total,
                                    passes=-(-total // rows), ms=ms)
                        if (not a.no_trace and fill == fills[0]
                                and size != "short"):
                            line["by_instruction_ms"] = by_instruction(
                                rows, args)
                    emit(line)
        emit(dict(base, ragged_dead=ragged_dead(
            data[fills[0]], sizes_of["twice"], a.reps, a.chain)))
        del data
    if a.out:
        os.makedirs(os.path.dirname(a.out), exist_ok=True)
        with open(a.out, "w") as fh:
            for line in lines:
                fh.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
