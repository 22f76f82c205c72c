"""What ONE window layer's prefill attention costs on the chip, apart from
everything around it: ``attention_ops.windowed_causal_attention`` at the
three served geometries and their cells' prompt buckets, in BOTH its forms,
the ``window_prefill_attention`` kernel (``ops/pallas_kernels/
window_prefill.py``) and the blocked XLA form (a ``lax.map`` over query
blocks of 512 rows, the float32 scores through HBM).

    python benchmarks/diag_window_prefill.py [--only motif3] [--form kernel]
        [--tiles 128x128,256x128]

One JSON line a point: the geometry, milliseconds a call (the median of
``--reps`` timings of ``--calls`` calls queued back to back), the band's
products (``2 (D + Dv)`` operations a query head a (row, key it keeps)
pair) and the share of the matrix unit's peak they reach, and the largest
difference between the two forms. ``--tiles`` times the kernel at other
``block_q x block_k`` than its own choice. ``--model`` times, in place of
the attention alone, the served configuration's leading layers up to its
first window layer through ``prefill_last`` in both forms: what the layer
costs WITH the copies the compiler puts between its producers and the
call. Run on the chip; it refuses another backend.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

PEAK_FLOPS = 197e12         # TPU v5e, bfloat16 (grid/peaks.json)

# name: (query heads, KV heads, D, Dv, window, the cell's buckets past it)
GEOMETRIES = {
    "smallthinker": (28, 4, 128, 128, 4096, (8192,)),
    "laguna": (72, 8, 128, 128, 512, (4096, 8192)),
    "motif3": (80, 16, 192, 128, 128, (2048, 4096, 8192)),
}


def band_flops(s, window, n_head, d, d_v):
    """The products of the (row, key) pairs the band keeps: row ``i`` reads
    ``min(i + 1, window)`` keys."""
    i = np.arange(s, dtype=np.int64)
    return int(np.minimum(i + 1, window).sum()) * n_head * 2 * (d + d_v)


def time_ms(fn, args, reps, calls):
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(calls):
            y = fn(*args)
        jax.block_until_ready(y)
        out.append((time.perf_counter() - t0) / calls)
    return statistics.median(out) * 1e3


@contextlib.contextmanager
def gate_refuses():
    """``windowed_causal_attention`` traced inside takes its blocked form."""
    from paddle_tpu.ops.pallas_kernels import window_prefill

    gate = window_prefill.window_prefill_gate
    window_prefill.window_prefill_gate = lambda *a, **kw: "diag: blocked"
    try:
        yield
    finally:
        window_prefill.window_prefill_gate = gate


def forms(window, sm_scale, tiles):
    """``{name: jitted (q, k, v) -> o}`` over ``[H, D, S]`` arrays, the
    rows in the lanes as the compiler lays a layer's projections out in
    the three models (the transposes to the functions' ``[S, H, D]`` are
    views then): the function as the models call it (the kernel, on a chip
    whose gate takes the shapes), the same with the gate refusing (the
    blocked form), the kernel at ``tiles``."""
    from paddle_tpu.ops import attention_ops
    from paddle_tpu.ops.pallas_kernels import window_prefill

    def rows_first(fn):
        return lambda q, k, v: fn(*(x.transpose(2, 0, 1) for x in (q, k, v))
                                  ).transpose(1, 2, 0)

    def served(q, k, v):
        return attention_ops.windowed_causal_attention(q, k, v, window,
                                                       sm_scale)

    def blocked(q, k, v):
        with gate_refuses():
            return served(q, k, v)

    out = {"kernel": served, "blocked": blocked}
    for bq, bk in tiles:
        out["kernel_%dx%d" % (bq, bk)] = (
            lambda q, k, v, bq=bq, bk=bk:
            window_prefill.window_prefill_attention(
                q, k, v, window, sm_scale, block_q=bq, block_k=bk))
    return {name: jax.jit(rows_first(fn)) for name, fn in out.items()}


# name: (configuration file, its driver, its model's module and class, the
# leading layers held: the first window layer is the last of them)
MODELS = {
    "smallthinker": ("smallthinker-21b-a3b-serve", "serve_moe",
                     "smallthinker", "SmallThinkerLM", 2),
    "laguna": ("laguna-s-ep2-serve", "serve_mixed_gqa", "laguna",
               "LagunaLM", 2),
    "motif3": ("motif-3-beta-ep16-serve", "serve_gdla", "motif3",
               "Motif3LM", 1),
}


def model_lines(geo, buckets, reps, calls, seed):
    """One line a bucket: ``prefill_last`` of the configuration cut to its
    leading layers, with the window layer's attention in each form."""
    import importlib

    name, driver, module, cls, layers = MODELS[geo]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "grid", "configs", name + ".json")) as fh:
        config = json.load(fh)
    config["num_hidden_layers"] = layers
    if "layer_types" in config:     # Motif checks the list's length
        config["layer_types"] = config["layer_types"][:layers]
    cfg = importlib.import_module(
        "grid.drivers." + driver).model_config(config)
    mod = importlib.import_module("paddle_tpu.models." + module)
    params = mod.init_params(cfg, seed)
    model = getattr(mod, cls)(cfg, params=params)
    for s in buckets:
        toks = jax.random.randint(jax.random.PRNGKey(seed), (1, s), 0,
                                  cfg.vocab_size, jnp.int32)
        lens = jnp.asarray([s], jnp.int32)
        line = {"geometry": geo, "rows": s, "model_layers": layers}
        for form, tracing in (("kernel", contextlib.nullcontext),
                              ("blocked", gate_refuses)):
            with tracing():     # the first call traces: inside time_ms
                line["prefill_%s_ms" % form] = time_ms(
                    jax.jit(model.prefill_last), (params, toks, lens),
                    reps, calls)
        line["window_layer_saves_ms"] = (line["prefill_blocked_ms"]
                                         - line["prefill_kernel_ms"])
        yield line


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--form", default="kernel,blocked")
    ap.add_argument("--tiles", default="",
                    help="block_q x block_k beside the kernel's own choice")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--model", action="store_true",
                    help="the configuration's leading layers, not the "
                         "attention alone")
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--out", default="")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.allow_cpu:
        print(json.dumps({"ok": False, "why": "no TPU: %s" % dev.platform}))
        return 1
    tiles = [tuple(int(x) for x in t.split("x"))
             for t in a.tiles.split(",") if t]
    want = a.form.split(",")
    lines = []
    for geo, (hq, hkv, d, d_v, window, buckets) in GEOMETRIES.items():
        if a.only and geo not in a.only.split(","):
            continue
        if a.model:
            for line in model_lines(geo, buckets, a.reps, a.calls, a.seed):
                lines.append(line)
                print(json.dumps(line), flush=True)
            continue
        for s in buckets:
            kq, kk, kv = jax.random.split(jax.random.PRNGKey(a.seed), 3)
            q, k, v = (jax.random.normal(key, shape, jnp.float32
                                         ).astype(jnp.bfloat16)
                       for key, shape in ((kq, (hq, d, s)), (kk, (hkv, d, s)),
                                          (kv, (hkv, d_v, s))))
            flops = band_flops(s, window, hq, d, d_v)
            line = {"geometry": geo, "rows": s, "window": window,
                    "heads": [hq, hkv], "d": [d, d_v], "band_gflop":
                    flops / 1e9, "peak_ms": flops / PEAK_FLOPS * 1e3}
            outs = {}
            for name, fn in forms(window, d ** -0.5, tiles).items():
                if name.split("_")[0] not in want:
                    continue
                try:
                    ms = time_ms(fn, (q, k, v), a.reps, a.calls)
                    line[name + "_ms"] = ms
                    line[name + "_mxu_share"] = 100.0 * line["peak_ms"] / ms
                    outs[name] = np.asarray(fn(q, k, v), np.float32)
                except Exception as exc:  # tiles the chip's compiler refuses
                    line[name + "_error"] = repr(exc)[:300]
            if "blocked" in outs:
                line["max_abs_diff"] = {
                    n: float(np.max(np.abs(o - outs["blocked"])))
                    for n, o in outs.items() if n != "blocked"}
                line["max_abs"] = float(np.max(np.abs(outs["blocked"])))
            lines.append(line)
            print(json.dumps(line), flush=True)
    if a.out:
        os.makedirs(os.path.dirname(a.out), exist_ok=True)
        with open(a.out, "w") as fh:
            for line in lines:
                fh.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
