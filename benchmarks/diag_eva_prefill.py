"""What ONE layer's EVA prefill attention costs on the chip, apart from
everything around it: ``models/evabyte._prefill_attention`` at EvaByte-6.5B's
heads (32 of 128, windows of 2,048, chunks of 16) and the cell's three
prompt buckets, in BOTH its forms, the ``eva_prefill_attention`` kernel
(``ops/pallas_kernels/eva_prefill.py``) and the blocked XLA form (a
``lax.map`` over query blocks of 512 rows, the float32 scores through HBM).

    python benchmarks/diag_eva_prefill.py [--rows 8192] [--form kernel]
        [--tiles 512x512x8,1024x512x4] [--model]

One JSON line a bucket: milliseconds a call (the median of ``--reps``
timings of ``--calls`` calls queued back to back), the products of the
(row, key) pairs EVA keeps (``4 D`` operations a head a pair: a row of
window w reads ``kept w`` summaries and its own window's rows up to
itself) and the share of the matrix unit's peak they reach, and the largest
difference of each form from the other and from the float32 statement
(``grid/reference/evabyte._attention``). ``--tiles`` times the kernel at
other ``block_q x block_k x heads`` than its own. ``--model`` times, in
place of the attention alone, ``--layers`` whole layers through
``prefill_last`` in both forms: what a layer costs WITH whatever the
compiler puts between its producers and the call. Run on the chip; it
refuses another backend.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.diag_window_prefill import PEAK_FLOPS, time_ms

HEADS, D, WINDOW, CHUNK = 32, 128, 2048, 16
BUCKETS = (4096, 8192, 16384)


def kept_flops(s, window=WINDOW, chunk=CHUNK, n_head=HEADS, d=D):
    """The two products of the (row, key) pairs EVA keeps."""
    p = np.arange(s, dtype=np.int64)
    keys = (window // chunk) * (p // window) + p % window + 1
    return int(keys.sum()) * n_head * 4 * d


@contextlib.contextmanager
def gate_refuses():
    """``_prefill_attention`` traced inside takes its blocked form."""
    from paddle_tpu.ops.pallas_kernels import eva_prefill

    gate = eva_prefill.eva_prefill_gate
    eva_prefill.eva_prefill_gate = lambda *a, **kw: "diag: blocked"
    try:
        yield
    finally:
        eva_prefill.eva_prefill_gate = gate


def config(n_layer=1, max_seq=BUCKETS[-1]):
    from paddle_tpu.models import evabyte

    return evabyte.EvaByteConfig(320, n_layer, HEADS * D, HEADS, HEADS,
                                 11008, window=WINDOW, chunk=CHUNK,
                                 max_seq=max_seq, dtype="bfloat16")


def forms(cfg, tiles):
    """``{name: jitted (q, k, v, ks, vs) -> o}`` over q, k, v ``[H, D, S]``,
    the rows in the lanes as the compiler lays the model's projections out
    (the transposes to the function's ``[S, H, D]`` are views then), and
    the summaries ``[n, H, D]``: the function as the model calls it (the
    kernel, on a chip whose gate takes the shapes), the same with the gate
    refusing, the kernel at ``tiles``."""
    from paddle_tpu.models import evabyte
    from paddle_tpu.ops.pallas_kernels import eva_prefill

    def rows_first(fn):
        return lambda q, k, v, ks, vs: fn(
            *(x.transpose(2, 0, 1) for x in (q, k, v)), ks, vs
        ).transpose(1, 2, 0)

    def served(*a):
        return evabyte._prefill_attention(cfg, *a)

    def blocked(*a):
        with gate_refuses():
            return served(*a)

    out = {"kernel": served, "blocked": blocked}
    for bq, bk, g in tiles:
        out["kernel_%dx%dx%d" % (bq, bk, g)] = (
            lambda *a, bq=bq, bk=bk, g=g: eva_prefill.eva_prefill_attention(
                *a, cfg.window, cfg.chunk, cfg.sm_scale, block_q=bq,
                block_k=bk, heads=g))
    return {name: jax.jit(rows_first(fn)) for name, fn in out.items()}


def reference(q, k, v, ks, vs):
    """The float32 statement over the same (rounded) numbers, [H, D, S]."""
    from grid.reference import evabyte as ref

    with jax.default_matmul_precision("highest"):
        o = jax.jit(lambda *a: ref._attention(*a, WINDOW, CHUNK))(
            *(x.astype(jnp.float32).transpose(2, 0, 1) for x in (q, k, v)),
            ks.astype(jnp.float32), vs.astype(jnp.float32))
    return np.asarray(o.transpose(1, 2, 0), np.float32)


def model_line(s, layers, reps, calls, seed):
    """``prefill_last`` of ``layers`` whole layers at ``s`` rows with the
    attention in each form."""
    from paddle_tpu.models import evabyte

    cfg = config(layers)
    params = evabyte.init_params(cfg, seed)
    model = evabyte.EvaByteLM(cfg, params=params)
    toks = jax.random.randint(jax.random.PRNGKey(seed), (1, s), 0,
                              cfg.vocab_size, jnp.int32)
    lens = jnp.asarray([s], jnp.int32)
    line = {"rows": s, "model_layers": layers}
    for form, tracing in (("kernel", contextlib.nullcontext),
                          ("blocked", gate_refuses)):
        with tracing():     # the first call traces: inside time_ms
            line["prefill_%s_ms" % form] = time_ms(
                jax.jit(model.prefill_last), (params, toks, lens), reps,
                calls)
    line["a_layer_saves_ms"] = (line["prefill_blocked_ms"]
                                - line["prefill_kernel_ms"]) / layers
    return line


def attention_line(s, want, tiles, reps, calls, seed, with_reference):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(key, (HEADS, D, s), jnp.float32
                                 ).astype(jnp.bfloat16) for key in keys[:3])
    ks, vs = (jax.random.normal(key, (s // CHUNK, HEADS, D), jnp.float32
                                ).astype(jnp.bfloat16) for key in keys[3:])
    flops = kept_flops(s)
    line = {"rows": s, "window": WINDOW, "chunk": CHUNK, "heads": HEADS,
            "d": D, "kept_gflop": flops / 1e9,
            "peak_ms": flops / PEAK_FLOPS * 1e3}
    outs = {}
    for name, fn in forms(config(), tiles).items():
        if name.split("_")[0] not in want:
            continue
        try:
            ms = time_ms(fn, (q, k, v, ks, vs), reps, calls)
            line[name + "_ms"] = ms
            line[name + "_mxu_share"] = 100.0 * line["peak_ms"] / ms
            outs[name] = np.asarray(fn(q, k, v, ks, vs), np.float32)
        except Exception as exc:    # tiles the chip's compiler refuses
            line[name + "_error"] = repr(exc)[:300]
    if "blocked" in outs:
        line["max_abs_diff_from_blocked"] = {
            n: float(np.max(np.abs(o - outs["blocked"])))
            for n, o in outs.items() if n != "blocked"}
    if with_reference and outs:
        want_o = reference(q, k, v, ks, vs)
        line["max_abs"] = float(np.max(np.abs(want_o)))
        line["max_abs_diff_from_float32"] = {
            n: float(np.max(np.abs(o - want_o))) for n, o in outs.items()}
        line["mean_abs_diff_from_float32"] = {
            n: float(np.mean(np.abs(o - want_o))) for n, o in outs.items()}
    return line


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default=",".join(map(str, BUCKETS)))
    ap.add_argument("--form", default="kernel,blocked")
    ap.add_argument("--tiles", default="",
                    help="block_q x block_k x heads beside the kernel's own")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--model", action="store_true",
                    help="whole layers through prefill_last, not the "
                         "attention alone")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--no-reference", action="store_true")
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--out", default="")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.allow_cpu:
        print(json.dumps({"ok": False, "why": "no TPU: %s" % dev.platform}))
        return 1
    tiles = [tuple(int(x) for x in t.split("x"))
             for t in a.tiles.split(",") if t]
    lines = []
    for s in (int(r) for r in a.rows.split(",")):
        line = model_line(s, a.layers, a.reps, a.calls, a.seed) if a.model \
            else attention_line(s, a.form.split(","), tiles, a.reps, a.calls,
                                a.seed, not a.no_reference)
        lines.append(line)
        print(json.dumps(line), flush=True)
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as fh:
            for line in lines:
                fh.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
