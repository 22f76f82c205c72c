"""Metrics of the grouped-differential latent decoder's cell: the latent
kernel over pages and over rings, the attention half, the residual path
and the routed experts held here in the device trace, and the counters the
driver sampled after every cycle (``drivers/serve_gdla.Sample``).

An operation is told by what survives in the profile's event text (the
``jax.named_scope`` names reach the HLO and not that text, which does
carry result and operand shapes): the full layers' latent kernel is a
Pallas call named ``mla_latent_decode``, the window layers' the same
kernel under the name ``mla_latent_decode_ring``; the rest of the
attention half has shapes only it has (the query latent, 80 x 192 query
lanes, the 576-lane row's projection, the 16-head up-projection, the 64
lambdas, the 8,192-wide gate and output); the residual path's operations
are told BY NAME from the decode executable's own text, whose metadata
does keep the scopes (:func:`scoped_instructions`; the driver writes the
names into the record); the routed experts are the ``ragged_dot`` kernels
and the ``while`` that carries the held experts' ``[E_held, d, f]``
weights. A reader that finds no such operation, or a record without the
samples (the parent of the PR that added this file has neither the kernels
nor the counters), returns nothing."""

from __future__ import annotations

import re
from typing import List, Optional

from .. import flops_gdla, reduce
from .hybrid import _is_routed     # the same keys name the experts held
from .moe import DECODE_MODULE, _delta, _in, _win

KERNEL = "mla_latent_decode"
RING_KERNEL = "mla_latent_decode_ring"


def _is_record(record) -> bool:
    return ("samples" in record
            and "mhc_expansion_rate" in record.get("model", {})
            and "layer_types" in record["model"])


def _is_ring(o) -> bool:
    return o.module == DECODE_MODULE and RING_KERNEL in o.text


def _is_full(o) -> bool:
    return (o.module == DECODE_MODULE and KERNEL in o.text
            and RING_KERNEL not in o.text)


def _is_decode(o) -> bool:
    return o.module == DECODE_MODULE


def _shapes(patterns):
    found = [re.compile(p) for p in patterns]
    return lambda text: any(p.search(text) for p in found)


def _is_attn(record):
    """What runs under ``attn/`` in the decode executable: both latent
    kernels, and every operation whose text holds a shape only the
    attention half has."""
    m, b = record["model"], int(record["slots"])
    d = int(m["hidden_size"])
    h, hd = int(m["num_attention_heads"]), int(m["head_dim"])
    n_kv = int(m["num_key_value_heads"])
    rank, rope = int(m["kv_lora_rank"]), int(m["qk_rope_head_dim"])
    dv, q_rank = int(m["v_head_dim"]), int(m["q_lora_rank"])
    sig = h - int(m["num_noise_heads"])
    kv_cols = n_kv * (hd - rope + dv)
    own = _shapes([
        r"\[%d,%d\]" % (d, q_rank), r"\[%d,%d\]" % (q_rank, h * hd),
        r"\[%d,%d\]" % (b, h * hd), r"\[%d,%d,%d\]" % (b, h, hd),
        r"\[%d,%d\]" % (d, rank + rope), r"\[%d,%d\]" % (rank, kv_cols),
        r"\[%d,%d,%d\]" % (rank, n_kv, hd - rope + dv),
        r"\[%d,%d\]" % (d, sig), r"\[%d,%d\]" % (d, sig * dv),
        r"\[%d,%d\]" % (sig * dv, d), r"\[%d,%d\]" % (b, sig * dv),
        r"\[%d,%d,%d\]" % (b, h, rank), r"\[%d,%d,\d+,%d\]" % (b, n_kv, rank),
        r"\[%d,%d,%d\]" % (b, sig, rank)])
    routed = _is_routed(record)

    def pred(o):
        return o.module == DECODE_MODULE and not routed(o) and (
            KERNEL in o.text or "attn/" in o.text or own(o.text))

    return pred


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
_APPLIES = re.compile(r"\bto_apply=%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_NO_EVENT = re.compile(
    r" (get-tuple-element|bitcast|parameter|constant|tuple)\(")
_PRODUCT = re.compile(r" (dot|convolution)\(")


def scoped_instructions(hlo_text: str, scope: str) -> List[str]:
    """The instructions of a compiled executable's text that run under the
    ``jax.named_scope`` ``scope``. The profile's event text is an
    instruction WITHOUT its metadata, but an event is named by its
    instruction, and the executable's own text (``Compiled.as_text()``)
    keeps each instruction's ``op_name``, the scopes it was traced under.
    An instruction of its own counts where its ``op_name`` holds
    ``scope``. A fusion is judged by what it fused, fusions inside it
    included: by its matrix products where it has any (their weights'
    stream is its time: the output projection with the streams' mixing
    fused in as its epilogue is the projection's, ``z Phi`` the residual
    path's), else by more than half of its instructions."""
    members, calls, rows = {}, {}, []
    nested = set()        # computations whose instructions are no events
    comp = None
    for line in hlo_text.split("\n"):
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        nested.update(_APPLIES.findall(line))
        op = _OP_NAME.search(line)
        hit = bool(op) and scope in op.group(1)
        called = _CALLS.search(line) if " fusion(" in line else None
        if called:
            nested.add(called.group(1))
            calls.setdefault(comp, []).append(called.group(1))
        elif op:
            members.setdefault(comp, []).append(
                (hit, bool(_PRODUCT.search(line))))
        if not _NO_EVENT.search(line):
            rows.append((m.group(1), comp, hit,
                         called.group(1) if called else None))

    def fused(comp, seen):
        if comp in seen:
            return
        seen.add(comp)
        yield from members.get(comp, ())
        for inner in calls.get(comp, ()):
            yield from fused(inner, seen)

    def mostly(comp) -> bool:
        inside = list(fused(comp, set()))
        votes = [hit for hit, product in inside if product] \
            or [hit for hit, _ in inside]
        return 2 * sum(votes) > len(votes)

    return sorted(name for name, home, hit, called in rows
                  if home not in nested
                  and (mostly(called) if called else hit))


def _is_mhc(record):
    """What runs under ``residual/mhc`` on its own in the decode
    executable: the events named by the instructions the driver wrote
    down from the executable's own text (``record["residual_ops"]``:
    :func:`scoped_instructions`), less what the attention half's or the
    experts' shapes claim. The mixing ``H_res X + H_post^T y`` is not
    among them where the compiler made it the epilogue of the product
    before it: that fusion's time is its weights' stream."""
    attn, routed = _is_attn(record), _is_routed(record)
    named = frozenset(record.get("residual_ops") or ())
    return lambda o: (o.module == DECODE_MODULE and o.name in named
                      and not attn(o) and not routed(o))


def _roofline(record, trace, is_kernel, kind: str, field: str
              ) -> Optional[float]:
    if trace is None or not _is_record(record):
        return None
    kernel_s = reduce.time_where(trace, is_kernel, _win(record))
    samples, inside = _in(record, "tail_open", "tail_close")
    rows = _delta(samples, inside, field)
    if not kernel_s or not rows:
        return None
    need = flops_gdla.attn_decode_need_s(rows, kind, record["model"],
                                         record["peaks"])
    return 100.0 * need / kernel_s


def gdla_full_attn_roofline(record, trace) -> Optional[float]:
    """``flops_gdla.attn_decode_need_s`` over the rows ONE full layer read
    in the traced decode steps (``serving/attn_rows_read.latent_full``),
    times the full layers, over the ``mla_latent_decode`` kernel's device
    time in the decode executable."""
    return _roofline(record, trace, _is_full, flops_gdla.FULL,
                     "rows_full_sum")


def gdla_ring_attn_roofline(record, trace) -> Optional[float]:
    """The same need over ``min(context, window)`` rows a live slot
    (``serving/attn_rows_read.latent_ring``) and the window layers, over
    the ``mla_latent_decode_ring`` calls' device time."""
    return _roofline(record, trace, _is_ring, flops_gdla.RING,
                     "rows_ring_sum")


def _decode_share(record, trace, which) -> Optional[float]:
    if trace is None or not _is_record(record):
        return None
    win = _win(record)
    decode_s = reduce.time_where(trace, _is_decode, win)
    own_s = reduce.time_where(trace, which(record), win)
    if not decode_s or not own_s:
        return None
    return 100.0 * own_s / decode_s


def gdla_attn_time_share(record, trace) -> Optional[float]:
    """Device time of everything under ``attn/`` in the decode executable
    (:func:`_is_attn`) over the decode executable's busy device time."""
    return _decode_share(record, trace, _is_attn)


def mhc_time_share(record, trace) -> Optional[float]:
    """Device time of everything under ``residual/mhc`` in the decode
    executable (:func:`_is_mhc`) over the same."""
    return _decode_share(record, trace, _is_mhc)


def sixteenth_share_expert_stream_roofline(record, trace) -> Optional[float]:
    """Bytes of the weights of the HELD experts the traced decode steps
    touched (``serving/moe_experts_touched``;
    ``flops_gdla.expert_stream_bytes``) over the peak HBM rate, over the
    device time of the routed experts' operations of the decode
    executable."""
    if trace is None or not _is_record(record):
        return None
    samples, inside = _in(record, "tail_open", "tail_close")
    touched = _delta(samples, inside, "touched_sum")
    routed_s = reduce.time_where(trace, _is_routed(record), _win(record))
    if not touched or not routed_s:
        return None
    need = flops_gdla.expert_stream_bytes(touched, record["model"])
    return 100.0 * (need / record["peaks"]["hbm_bytes_per_s"]) / routed_s


def _rows_per_step(record, field: str) -> Optional[float]:
    if not _is_record(record):
        return None
    samples, inside = _in(record, "open", "close")
    n = _delta(samples, inside, "rows_n")
    return _delta(samples, inside, field) / n if n else None


def attn_rows_read_per_step_latent_full(record, trace=None
                                        ) -> Optional[float]:
    """``serving/attn_rows_read.latent_full``: rows one full layer read in
    a decode step, over the live slots, mean over the window's steps."""
    return _rows_per_step(record, "rows_full_sum")


def attn_rows_read_per_step_latent_ring(record, trace=None
                                        ) -> Optional[float]:
    """``serving/attn_rows_read.latent_ring``: the same of one window
    layer (``min(context, window)`` a live slot)."""
    return _rows_per_step(record, "rows_ring_sum")


def gdla_latent_pages_used_share(record, trace=None) -> Optional[float]:
    """Pages of the FULL group in use after each cycle, mean over the
    window, over its pool (the ring group's are a slot's fixed eight)."""
    if not _is_record(record) \
            or "latent_full" not in record.get("pools", {}):
        return None
    samples, inside = _in(record, "open", "close")
    if not inside:
        return None
    used = sum(samples[i].pages_used for i in inside) / len(inside)
    return 100.0 * used / record["pools"]["latent_full"]
