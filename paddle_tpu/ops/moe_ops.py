"""Dropless top-k expert layer for the serving path.

``parallel/moe.py`` is top-1 Switch with a capacity factor, for training:
a token over capacity is dropped. A served token cannot be: every routed
(token, expert) pair is computed here, with no capacity and no drops, by
sorting the pairs by expert and running ONE grouped feed-forward over the
experts that received rows. The same function serves the decode step (16
rows x 6) and the prefill (8,192 rows x 6), and :func:`_grouped_ffn` gives
each pass the form of the grouped product that its rows call for:

* a pass of at most ``STREAM_ROWS_AN_EXPERT`` rows a held expert on a TPU
  (every decode pass of the served cells: 96 to 768 rows, 1 to 21 an
  expert) is bound by the stream of the touched experts' weights, and
  takes ``pallas_kernels/expert_stream.py``: the products in ONE kernel
  that reads each touched expert's matrices once and multiplies the
  expert's own rows;
* every larger pass (the prefills: 40 rows an expert and up, bound by
  arithmetic) and every other backend takes ``jax.lax.ragged_dot`` a
  matrix; on the TPU the compiler lowers it to its own grouped-matmul
  kernel, which reads an expert's weights only where its group has rows
  but multiplies EVERY row of the pass by every touched expert (PERF.md,
  PR 42): right where the rows are many, half the stream's rate where
  they are few.

The layer is told which experts it HOLDS and routes over all of them: a
pair routed to an expert that lives on another chip contributes nothing
here (that chip adds its part), so the one-chip share of a wider
deployment is this function with a shorter ``held``. On one chip ``held``
is every expert. A share computes its own pairs only: the sorted pairs go
through the grouped matmul in passes of a bound derived from the share
(:func:`pass_rows`), so a 12-of-384 share of a 4,096-token prefill gathers
1,280 rows and not the 32,768 of which 31 in 32 belong to absent experts; a
router that sends it more makes the loop run again, and nothing is dropped.
A DECODE pass (at most ``STREAM_ROWS_AN_EXPERT`` rows a held expert) holds
twice an even router's load and adds its weighed rows to their tokens by a
scatter-add. A PREFILL
pass holds the even load and a quarter of it (``PASS_MARGIN``) and gives
its rows back in the form :func:`combine_form` chooses from the part of
all pairs it is:

* ``gather`` where the pass is a quarter of the pairs or more (Laguna's
  half share, Ling's quarter): the permutation is inverted once in front
  of the loop and every pair fetches its row of the pass's result, in the
  result's type; a token's ``k`` rows are then weighed and added in
  float32 in ONE fusion over ``k`` slabs of ``[n, d]``. ``n k`` rows are
  gathered a pass whatever it holds, and there is no float32 ``[rows,
  d]`` and no scatter: the compiler makes a scatter-add of unsorted
  indices a sort, a gather of the float32 updates into sorted order and a
  sorted scatter, 13.3 of the 30.8 ms that a Laguna layer's 81,920-row
  pass took on the chip (PERF.md section 6, PR 61);
* ``scatter`` below that (GLM's eighth, Motif's sixteenth, Kimi's
  thirty-second), the decode pass's form.

The routing rule and the experts' activation are the caller's:
:func:`route_topk` (softmax over the chosen logits) or
:func:`route_sigmoid_topk` (sigmoid scores, a bias that selects and never
weighs, group-limited where the model's router is), and
``expert_layer(..., activation=)`` (ReLU by default: ReGLU experts;
``jax.nn.silu`` gives SwiGLU). An UNGATED expert has two matrices and no
gate (``wg=None``): ``act(u Wu_e) Wd_e``, Nemotron's with :func:`relu2`;
every path then runs two products, not three. An activation with numbers of its own for
every expert (PolyNorm's three weights and bias) comes with ``act_params``
[E_held, P] and is called ``activation(gate, p)`` with ``p`` the P numbers
of the expert the rows belong to; it sees an expert's WHOLE width, so it
may reduce over it.

Precision: the router's logits accumulate in float32 and its softmax or
sigmoid is float32; the experts' outputs are combined in float32.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import attention_ops

__all__ = ["route_topk", "route_sigmoid_topk", "expert_layer", "held_pairs",
           "pass_rows", "matmul_form", "combine_form", "relu2",
           "STREAM_ROWS_AN_EXPERT"]

# Rows of a pass A HELD EXPERT up to which the experts' weights, not the
# arithmetic, bound the grouped product: an expert's matrices are read once
# whatever rows it has, so what bounds a pass is its rows an expert against
# the chip's ridge of 240, not its rows. One row tile of the stream kernel
# (``expert_stream._row_tile``: 32). The served decode passes hold 1.2 to
# 21.3 rows an expert (96 rows over 64 experts to 256 over 12; Nemotron's
# 768 over 64 are 12), the prefill passes 40 (Ling's 5,120 over 128) to 768:
# no served pass lies between (tests/test_moe_share.py pins each).
STREAM_ROWS_AN_EXPERT = 32

# What a prefill's pass holds over an even router's load. The grouped matmul
# all but skips the tiles past its last group (a Laguna layer's gate and up
# read 8.95 ms at 81,920 and at 51,200 rows of which 41,027 are live, the
# down product 4.87 and 4.34), so a margin costs the gather of u, the
# activation and the combine over its dead rows (from twice the load to
# this: Motif 7.22 -> 5.89 ms, GLM 13.27 -> 11.98, Kimi 8.14 -> 6.90) and
# their scratch; a second pass costs a whole combine again (Kimi 6.90 ->
# 10.16 ms, Laguna 20.43 -> 25.58 where a full prompt needs two passes). The
# cells' prompts fill 0.7 of their bucket on average: a pass of 1.25 even
# loads runs twice only where the router is 1.3 times off even on a prompt
# that fills its bucket. (benchmarks/diag_share_prefill.py, my chip runs,
# PR 61.)
PASS_MARGIN = 0.25

# The part of all pairs a prefill's pass must be for the gather to return its
# rows. Milliseconds a layer at each served share's largest bucket, scatter |
# gather, by rows / (n k): Laguna 0.625: 24.50 | 20.43; Ling 0.3125: 14.19 |
# 10.02; GLM 0.156: 11.98 | 11.57, where the gather holds 540 MB of scratch a
# layer for the scatter's 254 (405 at twice the load); Motif 0.078: 5.89 |
# 6.48. The gather costs n k rows a pass (0.5-0.8 ms where the pass's result
# is under about 100 MB, 4.0 ms out of Laguna's 315 MB) and one float32
# fusion over k slabs; the scatter a sort and a float32 [rows, d] written,
# gathered into sorted order and scattered. Kimi (0.039: 6.90 | 5.29) is the
# exception a ratio cannot see: a scatter-add into f32[4096, 7168] costs 3.4
# ms whatever the pass holds (PERF.md section 7 (0-n)). (The same runs.)
GATHER_SHARE = 0.25


def route_topk(h, wr, top_k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``h`` [N, d] through the router ``wr`` [d, E]: the ``top_k`` largest
    logits a row and the softmax over THOSE (equal to the softmax over all
    E kept at the chosen ones and renormalised). Returns ``(idx [N, k]
    int32, w [N, k] float32)``."""
    with jax.named_scope("moe/router"):
        logits = jnp.dot(h, wr, preferred_element_type=jnp.float32)
        top, idx = jax.lax.top_k(logits, top_k)
        return idx.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def route_sigmoid_topk(h, wr, bias, top_k: int, scale: float = 1.0,
                       n_group: int = 1, topk_group: int = 1,
                       with_groups: bool = False
                       ) -> Tuple[jnp.ndarray, ...]:
    """The DeepSeek-V3 family's router: ``s = sigmoid(h wr)`` [N, E]; the
    ``top_k`` largest of ``s + bias`` are CHOSEN, and weighed by ``s``
    alone (the bias selects, never weighs), normalised over the chosen
    ones and multiplied by ``scale``. With ``n_group`` > 1 the choice is
    group-limited: the E experts are ``n_group`` equal runs, a group's
    score is the sum of its two largest ``s + bias``, only the
    ``topk_group`` best groups stay, and the ``top_k`` are chosen among
    their experts. ``n_group`` = ``topk_group`` = 1 is the plain top-k,
    the same operations as before there were groups. Returns ``(idx [N,
    k] int32, w [N, k] float32)`` and, ``with_groups`` (a group-limited
    router's counter), ``kept`` [N, n_group] bool: the groups that
    stayed."""
    with jax.named_scope("moe/router"):
        s = jax.nn.sigmoid(jnp.dot(h, wr, preferred_element_type=jnp.float32))
        biased = s + bias.astype(jnp.float32)
        if n_group > 1:
            n, e = biased.shape
            by_group = biased.reshape(n, n_group, e // n_group)
            score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
            _, keep = jax.lax.top_k(score, topk_group)
            kept = jnp.zeros((n, n_group), bool).at[
                jnp.arange(n)[:, None], keep].set(True)
            biased = jnp.where(jnp.repeat(kept, e // n_group, axis=1),
                               biased, -jnp.inf)
        _, idx = jax.lax.top_k(biased, top_k)
        chosen = jnp.take_along_axis(s, idx, axis=-1)
        w = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
        if with_groups:
            if n_group == 1:    # one group, and it stays
                kept = jnp.ones((biased.shape[0], 1), bool)
            return idx.astype(jnp.int32), w * scale, kept
        return idx.astype(jnp.int32), w * scale


def held_pairs(idx, held: Sequence[int], n_expert: int, row_valid=None):
    """The share's load: how many of the pairs ``idx`` [N, k] of the rows
    marked ``row_valid`` [N] are routed to an expert in ``held`` (global
    ids of ``n_expert``). An int32 scalar."""
    here = np.zeros((n_expert,), bool)
    here[list(held)] = True
    on_share = jnp.asarray(here)[idx]
    if row_valid is not None:
        on_share = on_share & row_valid[:, None]
    return jnp.sum(on_share).astype(jnp.int32)


def _tiles(rows: int) -> int:
    return -(-max(rows, 1) // 256) * 256


def _stream_bound(rows: int, e_held: int) -> bool:
    """Whether the experts' weight stream bounds a pass of ``rows`` rows
    over ``e_held`` held experts: a decode pass."""
    return rows <= STREAM_ROWS_AN_EXPERT * e_held


def _share_rows(n_pairs: int, e_held: int, n_expert: int) -> int:
    """Rows of one pass of a share's grouped matmul, in whole tiles of 256
    and never more than there are pairs. Twice what an even router sends
    ``e_held`` of ``n_expert`` experts where that is a decode pass (at most
    ``STREAM_ROWS_AN_EXPERT`` rows an expert); a larger one (a prefill's)
    holds the even load and ``PASS_MARGIN`` of it, and stays a prefill's
    pass: over that bound by a tile."""
    even = -(-n_pairs * e_held // n_expert)
    twice = min(n_pairs, _tiles(2 * even))
    if _stream_bound(twice, e_held):
        return twice
    return min(n_pairs, max(_tiles(even + int(even * PASS_MARGIN)),
                            _tiles(STREAM_ROWS_AN_EXPERT * e_held + 1)))


def pass_rows(n_pairs: int, e_held: int, n_expert: int) -> int:
    """Rows of one pass of the grouped product over ``n_pairs`` (token,
    expert) pairs: all of them where every expert is held, a share's bound
    else."""
    return (n_pairs if e_held == n_expert
            else _share_rows(n_pairs, e_held, n_expert))


def _on_tpu() -> bool:
    # asked through the module, so that what steers attention_ops' kernels
    # onto a described chip (tests/test_chip_compile.py) steers this too
    return attention_ops._on_tpu()


def matmul_form(rows: int, e_held: int) -> str:
    """Which grouped product a pass of ``rows`` rows over ``e_held`` held
    experts takes: ``"stream"`` (the fused kernel) or ``"grouped"``
    (``ragged_dot`` a matrix). A function of the pass's static counts and
    the backend alone."""
    return ("stream" if _stream_bound(rows, e_held) and _on_tpu()
            else "grouped")


def combine_form(rows: int, n_pairs: int, e_held: int) -> str:
    """How a pass of ``rows`` rows of a share of ``e_held`` experts gives
    its results back to the tokens of ``n_pairs`` pairs: ``"gather"``
    (every pair fetches its row by the inverse permutation and a token's
    ``k`` are summed: ``n_pairs`` rows gathered a pass, no scatter) where
    the pass is a prefill's and at least ``GATHER_SHARE`` of the pairs,
    else ``"scatter"`` (the pass's rows added to their tokens). A function
    of static counts alone."""
    return ("gather" if not _stream_bound(rows, e_held)
            and rows >= GATHER_SHARE * n_pairs else "scatter")


def relu2(x):
    """``relu(x)^2``: Nemotron's ungated experts' activation."""
    return jnp.square(jax.nn.relu(x))


def _ragged_ffn(xs, wg, wu, wd, sizes, activation, act_params=None,
                transposed_up: bool = False):
    """The grouped feed-forward as the compiler's grouped matmuls, one a
    matrix: three of a gated expert (gate and up are rounded to ``xs``'s
    type before the activation), two of an ungated one (``wg`` None). With
    ``act_params`` [E, P] each row's activation is given the P numbers of
    the expert whose group the row lies in, as P columns ``[M, 1]``.
    ``transposed_up``: ``wg``/``wu`` are stored ``[E, f, d]``; turned here
    (a change of layout the compiler folds into the product's operand)."""
    if transposed_up:
        wu = jnp.swapaxes(wu, 1, 2)
        wg = None if wg is None else jnp.swapaxes(wg, 1, 2)
    if wg is None:
        return jax.lax.ragged_dot(
            activation(jax.lax.ragged_dot(xs, wu, sizes)), wd, sizes)
    gate = jax.lax.ragged_dot(xs, wg, sizes)
    up = jax.lax.ragged_dot(xs, wu, sizes)
    if act_params is None:
        return jax.lax.ragged_dot(activation(gate) * up, wd, sizes)
    e = sizes.shape[0]
    of_row = jnp.minimum(jnp.searchsorted(
        jnp.cumsum(sizes), jnp.arange(xs.shape[0]), side="right"), e - 1)
    p = act_params.astype(jnp.float32)[of_row]
    act = activation(gate, [p[:, j:j + 1] for j in range(p.shape[1])])
    return jax.lax.ragged_dot((act * up).astype(xs.dtype), wd, sizes)


def _grouped_ffn(xs, wg, wu, wd, sizes, activation, act_params=None,
                 transposed_up: bool = False):
    """``(act(xs Wg_e) * (xs Wu_e)) Wd_e`` (``act(xs Wu_e) Wd_e`` where
    ``wg`` is None) for the rows of each group of ``sizes`` (rows sorted by
    expert; the rows past the last group are unspecified), in ``xs``'s
    type, in the form :func:`matmul_form` gives the pass; a geometry the
    kernel's gate refuses keeps ``ragged_dot`` (``moe/pass_form.stream``
    and ``.grouped`` count, once a traced pass, what was TAKEN: a silent
    fall to ``ragged_dot`` shows there). ``act_params`` [E, P]: the activation's own numbers
    for each expert."""
    m, d = xs.shape
    e, f, _ = wd.shape
    if matmul_form(m, e) == "stream":
        from .pallas_kernels import expert_stream   # Pallas only where used

        if expert_stream.expert_stream_gate(m, e, d, f, xs.dtype,
                                            gated=wg is not None) is None:
            attention_ops._count("stream", "moe/pass_form",
                                 "moe_ops._grouped_ffn")
            return expert_stream.expert_stream_ffn(
                xs, wg, wu, wd, sizes, activation, act_params=act_params,
                transposed_up=transposed_up)
    attention_ops._count("grouped", "moe/pass_form", "moe_ops._grouped_ffn")
    return _ragged_ffn(xs, wg, wu, wd, sizes, activation, act_params,
                       transposed_up)


def expert_layer(u, idx, w, wg, wu, wd, n_expert: Optional[int] = None,
                 held: Optional[Sequence[int]] = None, row_valid=None,
                 activation=jax.nn.relu, act_params=None,
                 transposed_up: bool = False
                 ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """``sum_k w[n, k] * (act(u Wg_e) * (u Wu_e)) Wd_e`` with ``e = idx[n,
    k]``, for the experts in ``held``; ``activation`` is ``act``: a
    callable of the gate's rows ``[R, f]`` (ReLU: ReGLU experts;
    ``jax.nn.silu``: SwiGLU), which sees an expert's whole width ``f`` in
    every path. ``wg`` None: UNGATED experts of two matrices, ``act(u
    Wu_e) Wd_e`` (:func:`relu2`: Nemotron's). ``transposed_up``: ``wg``
    and ``wu`` are stored ``[E_held, f, d]``, each expert's matrix
    transposed: how a width ``f`` of no whole lane tiles is stored
    (``pallas_kernels/expert_stream.py`` says why). Where the activation has numbers of its own for every
    expert, ``act_params`` [E_held, P] holds them in the order of the
    weights and the callable is ``act(gate, p)`` with ``p`` a list of P
    values that broadcast against ``[R, 1]``: the numbers of the expert
    the rows belong to (columns in the ``ragged_dot`` path, scalars in the
    fused kernel). Without ``act_params`` every path is as it was.

    ``u`` [N, d]; ``idx``/``w`` [N, k] from :func:`route_topk` or
    :func:`route_sigmoid_topk`; ``wg``/
    ``wu`` [E_held, d, f] and ``wd`` [E_held, f, d] are the held experts'
    weights in the order of ``held`` (global expert ids; default: all
    ``n_expert`` = ``wg.shape[0]`` of them). ``row_valid`` [N] bool marks
    the rows whose result is used (live decode slots, prompt positions
    below the length): the others are sorted past the last group and never
    computed. Returns ``(y [N, d] float32, stats)``; ``stats`` holds
    ``experts_touched`` (held experts with at least one row) and
    ``max_expert_rows`` (the largest group), int32 scalars.
    """
    n, _ = u.shape
    k = idx.shape[1]
    e_held = wd.shape[0]
    n_expert = e_held if n_expert is None else int(n_expert)
    if held is None:
        if n_expert != e_held:
            raise ValueError("%d experts held of %d: say which (held=)"
                             % (e_held, n_expert))
        local = idx
    else:
        held = [int(e) for e in held]
        if len(held) != e_held:
            raise ValueError("held names %d experts, the weights hold %d"
                             % (len(held), e_held))
        table = np.full((n_expert,), e_held, np.int32)
        table[held] = np.arange(e_held, dtype=np.int32)
        local = jnp.asarray(table)[idx]
    with jax.named_scope("moe/experts"):
        flat = local.reshape(n * k)
        if row_valid is not None:
            flat = jnp.where(jnp.repeat(row_valid, k), flat, e_held)
        # pairs sorted by expert; the absent ones (another chip's experts,
        # unused rows) carry the sentinel e_held and sort past every group
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.zeros((e_held + 1,), jnp.int32).at[flat].add(1)[:e_held]
        if e_held < n_expert:
            return _share(u, w, wg, wu, wd, order, sizes, activation,
                          pass_rows(n * k, e_held, n_expert), act_params,
                          transposed_up), _group_stats(sizes)
        out = _grouped_ffn(u[order // k], wg, wu, wd, sizes, activation,
                           act_params, transposed_up)
        # rows past the last group are whatever the grouped matmul left
        out = jnp.where((flat[order] < e_held)[:, None], out, 0)
        back = jnp.zeros((n * k,), jnp.int32).at[order].set(
            jnp.arange(n * k, dtype=jnp.int32))
        y = jnp.sum(out[back].reshape(n, k, -1).astype(jnp.float32)
                    * w.astype(jnp.float32)[:, :, None], axis=1)
        stats = _group_stats(sizes)
    return y, stats


def _group_stats(sizes) -> Dict[str, jnp.ndarray]:
    return {"experts_touched": jnp.sum(sizes > 0).astype(jnp.int32),
            "max_expert_rows": jnp.max(sizes).astype(jnp.int32)}


def _share(u, w, wg, wu, wd, order, sizes, activation, rows: int,
           act_params=None, transposed_up: bool = False):
    """A share's part of the layer: the sorted pairs of the HELD experts
    (the first ``sum(sizes)`` of ``order``), ``rows`` of them a pass, each
    pass one grouped matmul over its own slice of every group, its rows
    weighed and added to their tokens in float32 in the form
    :func:`combine_form` gives the pass (``moe/share_combine.gather`` and
    ``.scatter`` count which, once a traced layer). The loop runs until the
    last held pair is done."""
    n, d = u.shape
    k = w.shape[1]
    ends = jnp.cumsum(sizes)
    total = ends[-1]
    wf = w.astype(jnp.float32).reshape(n * k)
    combine = combine_form(rows, n * k, sizes.shape[0])
    attention_ops._count(combine, "moe/share_combine", "moe_ops._share")
    if combine == "gather":
        # where each pair lies among the sorted ones (the held pairs' below
        # ``total``, in the order the passes take them), a token's k-th
        # pair in row k: a token's sum is then over the LEADING axis, whole
        # [n, d] slabs added, and no [n, k, d] tiling pads k to a sublane
        back = jnp.zeros((n * k,), jnp.int32).at[order].set(
            jnp.arange(n * k, dtype=jnp.int32)).reshape(n, k).T
        wt = wf.reshape(n, k).T[:, :, None]

    def one_pass(i, y):
        lo = i * rows
        at = lo + jnp.arange(rows, dtype=jnp.int32)
        pair = order[jnp.minimum(at, n * k - 1)]
        live = at < total
        part = (jnp.clip(ends, lo, lo + rows)
                - jnp.clip(ends - sizes, lo, lo + rows))
        out = _grouped_ffn(u[pair // k], wg, wu, wd, part, activation,
                           act_params, transposed_up)
        # rows past the last group are whatever the grouped matmul left
        if combine == "gather":
            rel = back - lo
            mine = (rel >= 0) & (rel < rows) & (back < total)
            got = out[jnp.clip(rel, 0, rows - 1)]
            for j in range(k):      # one fusion: y and the k slabs read once
                y = y + jnp.where(mine[j][:, None], got[j], 0).astype(
                    jnp.float32) * wt[j]
            return y
        out = jnp.where(live[:, None],
                        out.astype(jnp.float32) * wf[pair][:, None], 0)
        return y.at[jnp.where(live, pair // k, n)].add(out, mode="drop")

    return jax.lax.fori_loop(0, -(-total // rows), one_pass,
                             jnp.zeros((n, d), jnp.float32))
