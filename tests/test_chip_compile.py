"""The chip-compiler rehearsal kept as tests (on-chip-measurement guide §2).

The TPU's compiler is installed here and compiles for a chip that is
described, not attached. Every Pallas kernel of the two main paths is
compiled with ``interpret=False`` at the shapes ``chip_smoke.py`` runs on
the chip, and must either come back holding a ``tpu_custom_call`` or be
excluded by its static gate — the interpreter the other kernel tests use
has no tiling and passes shapes the chip refuses (a ``[1, 10]`` row DMA, a
``[R, 12, 64]`` page DMA). A compile that passes is not a chip run: nothing
here says a kernel computes the right thing or how fast.
"""

import functools
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops import attention_ops
from paddle_tpu.ops.pallas_kernels import flash_attention as fa
from paddle_tpu.ops.pallas_kernels import paged_attention as pa
from paddle_tpu.ops.pallas_kernels import sparse_adam as sa
from paddle_tpu.ops.pallas_kernels import fused_softmax_xent


@pytest.fixture(scope="module")
def chip():
    """A described v5e chip's sharding. The persistent compile cache is off
    around the module: an executable compiled for a described chip is
    written to it but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu on this machine
        pytest.skip("cannot describe a v5e topology here: %r" % (e,))
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def compiled_text(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _expert_products(text, dims):
    """What an executable makes of its expert layers: the lines of the
    fused stream kernel's calls, each of which must name the experts'
    ``[E, d, f]`` operand (what the grid's readers tell an expert operation
    by, beside the name), and how many grouped matmuls the compiler made of
    ``ragged_dot``."""
    e, d, f = dims
    stream = [ln for ln in text.split("\n")
              if "tpu_custom_call" in ln and "%ragged_dot_stream" in ln]
    for ln in stream:
        assert "[%d,%d,%d]" % (e, d, f) in ln and "[%d,%d,%d]" % (e, f, d) in ln
    return stream, len(re.findall(
        r"= \S+ custom-call\([^\n]*ragged-dot", text))


@pytest.mark.parametrize("seq", [2048, 8192])
def test_flash_attention_fwd_bwd(chip, seq):
    """bf16 causal at the tiles the tune table hands out for this length."""
    bs = attention_ops._tuned_block_sizes(seq, seq)

    def loss(q, k, v):
        o = fa.flash_attention(q, k, v, causal=True, sm_scale=0.125,
                               block_sizes=bs)
        return o.astype(jnp.float32).sum()

    shape = ((1, 8, seq, 64), jnp.bfloat16)
    text = compiled_text(chip, jax.grad(loss, argnums=(0, 1, 2)),
                         shape, shape, shape)
    assert text.count("tpu_custom_call") >= 3  # fwd, dq, dkv


@pytest.mark.parametrize("case", ["encoder", "decoder_self", "cross"])
def test_single_tile_attention_at_transformer_base(chip, monkeypatch, case):
    """Through ``sdpa`` at the training cell's shape, dropout 0.1 and
    segment ids, forward and backward: two kernels and no ``[B, H, S, S]``
    tensor anywhere in the executable."""
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)

    def loss(q, k, v, seg_q, seg_kv, key):
        o = attention_ops.sdpa(
            q, k, v, None, seg_q, seg_kv if case == "cross" else seg_q,
            case == "decoder_self", 0.125, 0.1, key)
        return (o.astype(jnp.float32) ** 2).sum()

    act, seg = ((96, 8, 256, 64), jnp.bfloat16), ((96, 256), jnp.int32)
    text = compiled_text(chip, jax.grad(loss, argnums=(0, 1, 2)),
                         act, act, act, seg, seg, ((2,), jnp.uint32))
    assert text.count("tpu_custom_call") == 2
    assert "single_tile_attention_fwd" in text
    assert "single_tile_attention_bwd" in text
    assert "[96,8,256,256]" not in text


def test_single_tile_attention_runs_a_shard_on_four_chips(chip, monkeypatch):
    """384 rows split over a ``data`` axis of four described chips: each
    chip's kernel holds its 96 rows, and no collective gathers q, k or v."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    # the fixture has described the topology once: this process may again
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices), ("data",))
    rows, repl = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())

    def loss(q, k, v, seg, key):
        o = attention_ops.sdpa(q, k, v, None, seg, seg, True, 0.125, 0.1,
                               key, mesh=mesh)
        return (o.astype(jnp.float32) ** 2).sum()

    act = jax.ShapeDtypeStruct((384, 8, 256, 64), jnp.bfloat16, sharding=rows)
    seg = jax.ShapeDtypeStruct((384, 256), jnp.int32, sharding=rows)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        act, act, act, seg, key).compile().as_text()
    calls = [ln for ln in text.split("\n") if "tpu_custom_call" in ln]
    assert len(calls) == 2
    assert all("[96,256,512]" in ln and "[384," not in ln for ln in calls)
    assert "all-gather" not in text
    assert "[96,8,256,256]" not in text


def _ffn_with_its_dropouts(x, w1, w2):
    """An FFN with its inner dropout and the residual dropout behind it,
    through the registered ``dropout`` op (a constant key, as the training
    cell's seeded program gives it)."""
    from paddle_tpu.testing.op_test import run_op

    def drop(v, seed):
        return run_op("dropout", {"X": v}, ["Out"], attrs={
            "dropout_prob": 0.1, "seed": seed,
            "dropout_implementation": "upscale_in_train"})["Out"]

    h = drop(jax.nn.relu(jnp.einsum("bsd,df->bsf", x, w1)), 3)
    y = jnp.einsum("bsf,fd->bsd", h, w2)
    return ((x + drop(y, 4)).astype(jnp.float32) ** 2).mean()


@pytest.mark.parametrize("chips", [1, 4])
def test_dropout_draws_no_threefry_chain_at_transformer_base(chip, chips):
    """Forward and backward at ``[96, 256, 512]`` / 2048 in bf16 (a chip's
    rows; 384 over a ``data`` axis of four): no fused computation holds a
    threefry chain, the mixer's two multipliers stand where a mask is used
    (the two forward fusions, the backward ``where``s and the weight-gradient
    products that read a dropped tensor), and on four chips each hashes its
    own rows' global positions: no collective carries a mask."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks import diag_train_split

    w1, w2 = (512, 2048), (2048, 512)
    if chips == 1:
        shard = [chip] * 3
        outs = None
    else:
        # the fixture has described the topology once: this process may again
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        mesh = Mesh(np.array(topo.devices), ("data",))
        rows, repl = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
        shard = outs = (rows, repl, repl)
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sh) for s, sh in
            zip(((96 * chips, 256, 512), w1, w2), shard)]
    text = jax.jit(jax.grad(_ffn_with_its_dropouts, argnums=(0, 1, 2)),
                   out_shardings=outs).lower(*args).compile().as_text()
    holds = diag_train_split.fusion_contents(text)
    assert not diag_train_split.drawing_fusions(text)
    hashing = [held for held in holds.values() if "hash" in held]
    assert len(hashing) >= 4
    assert any("product" in held for held in hashing)
    assert "[%d,256," % (96 * chips) not in text or chips == 1
    collectives = [ln for ln in text.split("\n") if re.search(
        r" (all-gather|all-reduce|all-to-all|collective-permute)"
        r"(-start)?\(", ln)]
    assert len(collectives) == (chips == 4)    # the weight gradients' sum
    assert all("pred[" not in ln and "u32[" not in ln and "all-reduce" in ln
               for ln in collectives)


def test_softmax_xent_fwd_bwd(chip):
    def loss(logits, labels):
        return fused_softmax_xent(logits, labels).sum()

    text = compiled_text(chip, jax.grad(loss),
                         ((16384, 30000), jnp.bfloat16),
                         ((16384, 1), jnp.int32))
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("width", [1, 10, 128])
@pytest.mark.parametrize("opt", ["adam", "sgd"])
def test_sparse_rows(chip, opt, width):
    """DeepFM's tables (width 10 and 1) and a lane-wide one, V=1e6, one
    batch of 1024 x 26 ids."""
    vocab, n_ids = 1_000_000, 26624
    why = sa.sparse_rows_gate(vocab, width, jnp.float32)
    if width % 128:
        assert why is not None and "128 lanes" in why
        assert not sa.sparse_rows_supported(vocab, width, jnp.float32)
        # ...while the interpreter, which has no tiling, still takes it
        assert sa.sparse_rows_supported(vocab, width, jnp.float32,
                                        interpret=True)
        return
    assert why is None
    table = ((vocab, width), jnp.float32)
    ids, rows = ((n_ids,), jnp.int32), ((n_ids, width), jnp.float32)
    if opt == "adam":
        text = compiled_text(
            chip, lambda p, m, v, i, r: sa.sparse_adam_rows(p, m, v, i, r,
                                                            0.01),
            table, table, table, ids, rows)
    else:
        text = compiled_text(
            chip, lambda p, i, r: sa.sparse_sgd_rows(p, i, r, 0.5),
            table, ids, rows)
    assert "tpu_custom_call" in text


PAGED_SHAPES = {
    # slots, heads, d_head, page_size, pages/slot, dtype
    "gpt2_small_f32": (8, 12, 64, 16, 64, jnp.float32),
    "gpt2_small_bf16": (8, 12, 64, 16, 64, jnp.bfloat16),
    "heads4_d32": (8, 4, 32, 16, 16, jnp.float32),
    "tiny_test_model": (4, 2, 16, 8, 8, jnp.float32),
}


def _instructions(text):
    """``(name, result type, opcode, operand names)`` of every instruction
    in an HLO module's text. Operands are printed by name only, so a
    caller that wants their shapes looks the names up."""
    def balanced(s):  # length of the parenthesised group s starts with
        depth = 0
        for i, ch in enumerate(s):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                return i + 1
        return len(s)

    for line in text.split("\n"):
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*)", line)
        if not m:
            continue
        name, rest = m.groups()
        cut = balanced(rest) if rest.startswith("(") else rest.index(" ")
        rtype, rest = rest[:cut], rest[cut + 1:]
        opcode, paren, rest = rest.partition("(")
        if not paren:
            continue
        operands = re.findall(r"%([\w.\-]+)", rest[:balanced("(" + rest) - 1])
        yield name, rtype, opcode, operands


def _has_dim(type_str, n):
    return any(str(n) in dims.split(",")
               for dims in re.findall(r"\[([\d,]*)\]", type_str))


def _copies_around(instructions, kernel, rows):
    """``[(call, feeding, after)]`` for every custom call named ``kernel``:
    the ``copy`` instructions of ``rows``-row results that feed it (through
    bitcasts) and those that read its result."""
    by = {name: (rtype, op, operands)
          for name, rtype, op, operands in instructions}

    def source(name):
        while name in by and by[name][1] == "bitcast":
            name = by[name][2][0]
        return name

    def big_copy(name):
        return name in by and by[name][1] == "copy" \
            and _has_dim(by[name][0], rows)

    found = []
    for name, (rtype, op, operands) in by.items():
        if op == "custom-call" and name.startswith(kernel):
            readers = [n for n, (_, _, ops) in by.items() if name in ops]
            found.append((name,
                          [source(o) for o in operands if big_copy(source(o))],
                          [n for n in readers if big_copy(n)]))
    return found


def _choices_inside_loops(text):
    """``(homes, reached)``: the computations that hold a ``conditional``
    (each has to be a ``while``'s body), and the text of every computation
    its branches reach."""
    comps = {name: "\n".join(lines)
             for name, lines in _computations(text)[0].items()}
    bodies = set(re.findall(r"body=%([\w.\-]+)", text))
    homes = [name for name, body in comps.items() if " conditional(" in body]
    assert homes and set(homes) <= bodies, (homes, sorted(bodies)[:8])
    reached, todo = {}, []
    for home in homes:
        for line in comps[home].split("\n"):
            if " conditional(" in line:
                todo += re.findall(r"%([\w.\-]+)", line.split(
                    "branch_computations={")[1].split("}")[0])
    while todo:
        name = todo.pop()
        if name in reached or name not in comps:
            continue
        reached[name] = comps[name]
        todo += re.findall(
            r"(?:calls|to_apply|body|condition)=%([\w.\-]+)", comps[name])
    return {home: comps[home] for home in homes}, reached


@pytest.mark.parametrize("name", sorted(PAGED_SHAPES))
def test_paged_decode_attention(chip, name):
    """The engine's entry: the whole ``[n_layer, rows, H*D]`` pool and a
    layer in the middle of it. ``block_pages`` comes from the tune table,
    as in the engine."""
    b, h, d, ps, pps, dtype = PAGED_SHAPES[name]
    why = pa.paged_attention_gate(dtype, h, d, ps)
    if name == "tiny_test_model":
        # H*D = 32 does not fill a 128-lane row: `auto` keeps the gather path
        assert why is not None and "multiple of 128" in why
        assert pa.paged_attention_gate(dtype, h, d, ps, interpret=True) is None
        return
    assert why is None
    rows = b * pps * ps
    pool = ((3, rows, h * d), dtype)
    text = compiled_text(
        chip,
        functools.partial(pa.paged_decode_attention, page_size=ps, layer=1,
                          sm_scale=0.125),
        ((b, h, d), dtype), pool, pool, ((b, pps), jnp.int32),
        ((b,), jnp.int32))
    # the kernel runs under its own name: what a profile's XLA Ops line and
    # the grid's device_ops show in place of closed_call
    kernel, = [ln for ln in text.split("\n") if "tpu_custom_call" in ln]
    assert kernel.strip().startswith("%paged_attention")
    # and it is handed the pool itself: nothing slices or copies a layer
    assert [op for _, rtype, op, _ in _instructions(text)
            if _has_dim(rtype, rows) and op != "parameter"] == []


def test_paged_decode_attention_single_layer(chip):
    """One layer as ``[rows, H*D]`` (what the tuner and ``chip_smoke.py``
    time) becomes a pool of one without a copy."""
    b, h, d, ps, pps, dtype = PAGED_SHAPES["gpt2_small_bf16"]
    rows = b * pps * ps
    text = compiled_text(
        chip, functools.partial(pa.paged_decode_attention, page_size=ps),
        ((b, h, d), dtype), ((rows, h * d), dtype), ((rows, h * d), dtype),
        ((b, pps), jnp.int32), ((b,), jnp.int32))
    assert "tpu_custom_call" in text
    assert {op for _, rtype, op, _ in _instructions(text)
            if _has_dim(rtype, rows)} <= {"parameter", "bitcast"}


# the paged kernel's calls in the serve cells: slots, query heads, KV heads,
# head width, layers, pages, pages a slot, and the call's result
CELL_KERNELS = {
    "gpt2_small": (32, 12, 12, 64, 12, 2048, 64, "bf16[32,1,768]"),
    "smallthinker_global": (16, 28, 4, 128, 3, 6144, 1024, "bf16[16,8,512]"),
    "smallthinker_window": (16, 28, 4, 128, 9, 4096, 256, "bf16[16,8,512]"),
    "laguna_global": (16, 48, 8, 128, 2, 9216, 1024, "bf16[16,8,1024]"),
    "laguna_window": (16, 72, 8, 128, 3, 512, 32, "bf16[16,16,1024]"),
    # 48 layers x 4 steps in one pool, the layer a TRACED scalar (the step
    # of a device loop); one query head a KV head of a whole lane tile: the
    # grouped fold, the query head padded to 8 rows
    "ouro_loop": (12, 16, 16, 128, 192, 288, 64, "bf16[12,8,2048]"),
}


@pytest.mark.parametrize("cell", sorted(CELL_KERNELS))
def test_paged_kernel_at_the_cells_shapes_and_shipped_waves(chip, cell):
    """bf16 pools, the ``block_pages`` the shipped v5e table holds for the
    call's bucket (a measured entry, not the wildcard; the looped model's
    under a layer that is traced): ONE custom call named
    ``paged_attention`` with the result the trace readers tell the calls
    by, two K and two V wave buffers in the pool's type within the wave
    budget, and the pool handed over untouched. At the looped model's
    shape, whose fold the head's width chose, nothing is copied or
    transposed around the call that the head-membership fold did not
    copy: the traced layer's scalar and the result's split into heads."""
    from paddle_tpu import tune

    b, hq, h, d, n_layer, pages, pps, result = CELL_KERNELS[cell]
    ps, hd = 16, h * d
    bucket = tune.bucket_ctx(pps * ps, hd)
    cfg, src = tune.lookup("paged_attention", bucket, device="tpu-v5e")
    shipped = tune.table.read_entries(tune.table.shipped_path())
    measured = tune.table.entry_key("paged_attention", bucket,
                                    "tpu-v5e") in shipped
    assert src == "shipped" and measured, (bucket, src)
    bp = pa._block_pages(cfg["block_pages"], ps, pps, pps * ps, hd, 2)
    assert bp == cfg["block_pages"], "the shipped wave is clamped"
    kw = dict(page_size=ps, sm_scale=d ** -0.5, block_pages=bp)
    pool = ((n_layer, pages * ps, hd), jnp.bfloat16)
    shapes = (((b, hq, d), jnp.bfloat16), pool, pool, ((b, pps), jnp.int32),
              ((b,), jnp.int32))
    if cell == "ouro_loop":
        shapes += (((), jnp.int32),)

        def fn(q, k, v, pt, ctx, layer):
            return pa.paged_decode_attention(q, k, v, pt, ctx, layer=layer,
                                             **kw)
    else:
        fn = functools.partial(pa.paged_decode_attention, layer=1, **kw)
    text = compiled_text(chip, fn, *shapes)
    kernel, = [ln for ln in text.split("\n") if "tpu_custom_call" in ln]
    assert kernel.strip().startswith("%paged_attention")
    assert result in kernel.split(" custom-call(")[0]
    assert [op for _, rtype, op, _ in _instructions(text)
            if _has_dim(rtype, pages * ps) and op != "parameter"] == []
    if cell == "ouro_loop":
        assert pa.paged_attention_fold(hq // h, d) == "grouped"
        moved = sorted(rtype.split("{")[0]
                       for _, rtype, op, _ in _instructions(text)
                       if op in ("copy", "transpose"))
        assert moved == ["bf16[2,8,16,128]", "s32[]"], moved
    # what the kernel keeps in fast memory, from its own scratch operands
    jaxpr = jax.make_jaxpr(fn)(*[jax.ShapeDtypeStruct(*x) for x in shapes])
    call, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    n_scratch = call.params["grid_mapping"].num_scratch_operands
    scratch = [v.aval for v in call.params["jaxpr"].invars[-n_scratch:]]
    buffers = [a for a in scratch if a.shape == (2, bp * ps, hd)]
    assert len(buffers) == 2 and len(scratch) == 3, scratch
    assert all(a.dtype == jnp.bfloat16 for a in buffers)
    assert sum(a.size * 2 for a in buffers) <= pa._VMEM_WAVE_BUDGET


# -- the engine's executables at gpt2-small-serve's geometry -------------------

SERVE = dict(slots=32, page_size=16, num_pages=2048, max_seq=1024,
             n_layer=12, n_head=12, d_model=768, vocab=50257, bucket=256)


def _pick(logits, sampling, position, live=None):
    """A step's next tokens: the engine's sampler where the executable was
    given ``(temp, top_k, seed)``, the plain argmax where it was not."""
    from paddle_tpu.serving.engine import _sample_tokens

    if sampling:
        return _sample_tokens(logits, *sampling, position, live)
    return jnp.argmax(logits, -1).astype(jnp.int32)


def _serve_case(name, chip, n_layer=None):
    """``(fn, abstract args)`` of one of ``ServingEngine``'s executables,
    composed as ``engine._get_*_exe`` composes it: the model's forward with
    the cache's own ``write_*``/``decode_*`` calls, the cache first in the
    result. Greedy ``argmax`` stands where the engine calls its sampler (the
    vocabulary sort alone compiles for 22 s and touches no pool), but in
    ``chunk_sampler`` and ``prefill_sampler``: the same two with ``temp``,
    ``top_k`` and ``seed`` among their arguments and the engine's own
    ``_sample_tokens``."""
    from paddle_tpu.models import decoder_lm
    from paddle_tpu.serving.kv_cache import PagedKVCache

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    g = SERVE
    cfg = decoder_lm.DecoderConfig(
        vocab_size=g["vocab"], n_layer=n_layer or g["n_layer"],
        d_model=g["d_model"],
        n_head=g["n_head"], max_seq=g["max_seq"], dtype="bfloat16")
    model = decoder_lm.DecoderLM(cfg, params={})
    ops = PagedKVCache(cfg.n_layer, cfg.n_head, cfg.d_head, g["slots"],
                       g["max_seq"], g["page_size"], g["num_pages"],
                       dtype=cfg.dtype)

    def abstract(fn):
        return jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype),
                                      jax.eval_shape(fn))

    params = abstract(lambda: decoder_lm.init_params(cfg, 0))
    cache = abstract(ops.init_state)
    b = g["slots"]
    ints, flags = sds((b,), jnp.int32), sds((b,), jnp.bool_)

    def chunk(params, cache, lengths, tokens, active, *sampling):
        def body(carry, _):
            cache, ln, tk, ac = carry
            logits, cache = model.decode(params, cache, ops, tk, ln, ac)
            nxt = jnp.where(ac, _pick(logits, sampling, ln, ac), tk)
            return (cache, ln + ac, nxt, ac), nxt

        return jax.lax.scan(body, (cache, lengths, tokens, active), None,
                            length=1)

    def prefill(params, cache, dest, prompt, length, *sampling):
        logits, kvs = model.prefill(params, prompt[None], length[None])
        for i, (k, v) in enumerate(kvs):
            cache = ops.write_prompt(cache, i, k[0], v[0], dest, length)
        return cache, _pick(logits[0, length - 1][None],
                            [x[None] for x in sampling],
                            (length - 1)[None])[0]

    def prefill_armed(params, cache, state, dest, prompt, ints, temp):
        # an admission whole, as the engine launches it: the slot's page
        # table, the prompt's rows, the token, the slot's per-slot state
        from paddle_tpu.serving.engine import _arm_slot

        slot, length, maxnew, topk, seed, _ = ints
        cache, tok = prefill(params, ops.set_page_table(cache, slot, dest),
                             dest, prompt, length)
        return cache, _arm_slot(state, slot, length, tok, maxnew, temp,
                                topk, seed, None), tok

    def resume(params, cache, toks, start, length, slot):
        mask = jnp.arange(b, dtype=jnp.int32) == slot

        def body(cache, i):
            pos = start + i
            logits, cache = model.decode(
                params, cache, ops, jnp.where(mask, toks[i], 0),
                jnp.full((b,), pos, jnp.int32), mask & (pos < length))
            return cache, jnp.argmax(logits, -1)

        return jax.lax.scan(body, cache, jnp.arange(g["page_size"]))

    scalar = sds((), jnp.int32)
    prefill_args = (params, cache, sds((ops.pages_per_slot,), jnp.int32),
                    sds((g["bucket"],), jnp.int32), scalar)
    return {
        "chunk": (chunk, (params, cache, ints, ints, flags)),
        "chunk_sampler": (chunk, (params, cache, ints, ints, flags,
                                  sds((b,), jnp.float32), ints, ints)),
        "prefill_sampler": (prefill, prefill_args + (
            sds((), jnp.float32), scalar, scalar)),
        "prefill": (prefill, prefill_args),
        "prefill_armed": (prefill_armed, (
            params, cache, (ints, ints, flags, ints, ints,
                            sds((b,), jnp.float32), ints, ints),
            *prefill_args[2:4], sds((6,), jnp.int32), sds((), jnp.float32))),
        "resume": (resume, (params, cache, sds((g["page_size"],), jnp.int32),
                            scalar, scalar, scalar)),
    }[name], ops.num_rows


OURO_SERVE = dict(slots=12, page_size=16, num_pages=288, max_seq=1024,
                  n_layer=4, steps=4, bucket=512)


def _ouro_case(name, chip):
    """``_serve_case``'s twin for the looped model at Ouro-2.6B's published
    widths over the cell's pool, four of its 48 layers (16 cache layers:
    the loop and its carry are what is looked at, and they are the same):
    the decode chunk, whose steps are a device loop that CARRIES the pool
    under a traced cache step, and the 512-row prefill, whose scan hands
    the engine K and V a step."""
    from paddle_tpu.models import ouro
    from paddle_tpu.serving.kv_cache import PagedKVCache

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    g = OURO_SERVE
    cfg = ouro.OuroConfig(49152, g["n_layer"], 2048, 16, 16, 128, 5632,
                          ut_steps=g["steps"], max_seq=g["max_seq"],
                          dtype="bfloat16")
    model = ouro.OuroLM(cfg, params={})
    ops = PagedKVCache(cfg.n_layer, 16, 128, g["slots"], g["max_seq"],
                       g["page_size"], g["num_pages"], dtype="bfloat16",
                       cache_steps=cfg.cache_steps)
    params = jax.tree_util.tree_map(
        sds, jax.eval_shape(lambda: ouro.init_params(cfg, 0)))
    cache = jax.tree_util.tree_map(sds, jax.eval_shape(ops.init_state))
    b = g["slots"]
    ints = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=chip)
    flags = jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=chip)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)

    def chunk(params, cache, lengths, tokens, active):
        def body(carry, _):
            cache, ln, tk, ac = carry
            logits, cache, stats = model.decode(params, cache, ops, tk, ln,
                                                ac)
            nxt = jnp.where(ac, jnp.argmax(logits, -1).astype(jnp.int32), tk)
            return (cache, ln + ac, nxt, ac), (nxt, stats)

        return jax.lax.scan(body, (cache, lengths, tokens, active), None,
                            length=1)

    def prefill(params, cache, dest, prompt, length):
        logits, kvs = model.prefill_last(params, prompt[None], length[None])
        for i, kv in enumerate(kvs):
            for t in range(g["steps"]):
                cache = ops.write_prompt(cache, i, *(x[t, 0] for x in kv),
                                         dest, length, step=t)
        return cache, jnp.argmax(logits[0])

    return {
        "ouro_chunk": (chunk, (params, cache, ints, ints, flags)),
        "ouro_prefill": (prefill, (
            params, cache, jax.ShapeDtypeStruct((ops.pages_per_slot,),
                                                jnp.int32, sharding=chip),
            jax.ShapeDtypeStruct((g["bucket"],), jnp.int32, sharding=chip),
            scalar)),
    }[name], ops.num_rows


EVA_SERVE = dict(slots=12, page_size=16, num_pages=2048, max_seq=18432,
                 n_layer=2, bucket=4096)


def _eva_case(name, chip):
    """``_serve_case``'s twin for the model whose cache group COMPACTS, at
    EvaByte-6.5B's published widths over the cell's pool, two of its
    layers: the decode chunk (a row in, the open chunk's rows out, its
    summary in, the paged kernel over the slot's view, the page-table
    rotation after the last layer) and the 4,096-row prefill, which hands
    the cache the open window's rows and a summary a chunk."""
    from paddle_tpu.models import evabyte
    from paddle_tpu.serving.kv_cache import KV, CacheGroup, PagedKVCache

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    g = EVA_SERVE
    cfg = evabyte.EvaByteConfig(320, g["n_layer"], 4096, 32, 32, 11008,
                                max_seq=g["max_seq"], dtype="bfloat16")
    model = evabyte.EvaByteLM(cfg, params={})
    ops = PagedKVCache(cfg.n_layer, 32, 128, g["slots"], g["max_seq"],
                       g["page_size"], g["num_pages"], dtype="bfloat16",
                       groups=[CacheGroup("eva", tuple(range(cfg.n_layer)),
                                          cfg.window, g["num_pages"], KV,
                                          cfg.chunk)])
    params = jax.tree_util.tree_map(
        sds, jax.eval_shape(lambda: evabyte.init_params(cfg, 0)))
    cache = jax.tree_util.tree_map(sds, jax.eval_shape(ops.init_state))
    b = g["slots"]
    ints = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=chip)
    flags = jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=chip)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)

    def chunk(params, cache, lengths, tokens, active):
        def body(carry, _):
            cache, ln, tk, ac = carry
            logits, cache, stats = model.decode(params, cache, ops, tk, ln,
                                                ac)
            nxt = jnp.where(ac, jnp.argmax(logits, -1).astype(jnp.int32), tk)
            return (cache, ln + ac, nxt, ac), (nxt, stats)

        return jax.lax.scan(body, (cache, lengths, tokens, active), None,
                            length=1)

    def prefill(params, cache, dest, prompt, length):
        logits, kvs = model.prefill_last(params, prompt[None], length[None])
        for i, kv in enumerate(kvs):
            cache = ops.write_prompt(cache, i, *(t[0] for t in kv), dest,
                                     length)
        return cache, jnp.argmax(logits[0])

    return {
        "eva_chunk": (chunk, (params, cache, ints, ints, flags)),
        "eva_prefill": (prefill, (
            params, cache, jax.ShapeDtypeStruct((ops.page_table_len,),
                                                jnp.int32, sharding=chip),
            jax.ShapeDtypeStruct((g["bucket"],), jnp.int32, sharding=chip),
            scalar)),
    }[name], ops.num_rows


@pytest.mark.parametrize("exe", ["chunk", "prefill", "prefill_armed",
                                 "resume", "ouro_chunk", "ouro_prefill",
                                 "eva_chunk", "eva_prefill"])
def test_serving_executables_leave_the_pool_in_place(chip, monkeypatch, exe):
    """The decode chunk, a prefill bucket (alone, and as
    the admission the engine launches: with the slot's page table and its
    per-slot state written in the same program) and the resume
    scan write the 1.2 GB pool where it lies and hand it to the kernel
    whole: no ``copy``, ``slice``, ``dynamic-slice`` or ``transpose`` with
    the pool's row count in its result or an operand, both pools aliased
    from input to output, and temporaries far under one layer of the pool.
    (A ``[n_layer, rows, H, D]`` pool failed all three: the chip's compiler
    stored it rows-minor, converted it whole around the row scatters, and
    sliced and copied a layer for every kernel call: 2.5 GB of temporaries
    a decode step.) Lowered over abstract shapes: nothing is allocated."""
    # `auto` compiles the kernel where the default backend is the TPU; here
    # the backend is the CPU and only the compile's target is the chip
    monkeypatch.setattr(attention_ops, "paged_kernel_mode",
                        lambda: "compiled")
    looped, compacting = exe.startswith("ouro"), exe.startswith("eva")
    if looped or compacting:
        monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    (fn, args), rows = (_ouro_case if looped else _eva_case if compacting
                        else _serve_case)(exe, chip)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    text = compiled.as_text()
    if "prefill" not in exe:  # a prefill attends over its own K and V
        assert text.count("tpu_custom_call") == (
            OURO_SERVE if looped else EVA_SERVE if compacting
            else SERVE)["n_layer"]
    if looped:      # the steps stayed ONE loop: nothing unrolled them
        assert len(re.findall(r" while\(", text)) == 1
    instructions = list(_instructions(text))
    types = {name: rtype for name, rtype, _, _ in instructions}
    moved = [(op, rtype) for _, rtype, op, operands in instructions
             if op in ("copy", "copy-start", "slice", "dynamic-slice",
                       "transpose")
             and any(_has_dim(t, rows) for t in
                     [rtype] + [types.get(o, "") for o in operands])]
    if exe == "eva_chunk":
        # ``open_chunk``: the 16 rows of a slot's open chunk out of the
        # pool, a slice a slot a pool a layer, is what the model asked for
        chunks = [m for m in moved if m[0] == "dynamic-slice"
                  and m[1].startswith("bf16[1,16,4096]")]
        assert len(chunks) == 2 * EVA_SERVE["n_layer"]
        moved = [m for m in moved if m not in chunks]
    assert moved == []
    # cache is argument 1 of every executable: its leaves follow the
    # params' in the flattened parameter list, "k" "pt" "v" in key order
    n_params = len(jax.tree_util.tree_leaves(args[0]))
    if exe == "ouro_prefill":   # a prefill reads no gate: ``w_e``, ``b_e``
        n_params -= 2           # are pruned from the executable's arguments
    aliased = {int(p) for p in re.findall(
        r"\((\d+), \{\}, (?:may|must)-alias\)", text.split("\n", 1)[0])}
    assert {n_params, n_params + 2} <= aliased
    if exe == "prefill_armed":  # the page table is written where it lies
        assert n_params + 1 in aliased
    limit = 64 * 2 ** 20
    if exe == "ouro_prefill":   # K and V of 4 steps x 4 layers x 512 rows
        g = OURO_SERVE
        limit = g["n_layer"] * g["steps"] * rows * 2048 * 2     # one pool
    if exe == "eva_prefill":
        # the attention is ONE kernel call a layer under the scope the
        # benchmark's readers tell it by, fed by the projections' and the
        # rotation's own results: no ``copy`` or ``transpose`` turns q, k
        # or v for it (row-major ``[S, H D]`` operands cost three)
        made = {name: (op, operands)
                for name, _, op, operands in instructions}
        calls = [ln for ln in text.split("\n") if "tpu_custom_call" in ln]
        assert len(calls) == EVA_SERVE["n_layer"]
        for ln in calls:
            name = ln.split(" = ")[0].strip().lstrip("%")
            assert name.startswith("eva_prefill_attention")
            assert "attn/eva_prefill" in ln
            for operand in made[name][1][:3]:
                while made[operand][0] in ("bitcast", "copy-done",
                                           "copy-start"):  # views, prefetch
                    operand = made[operand][1][0]
                assert not re.match(r"(copy|transpose)", operand), ln
                assert made[operand][0] not in ("copy", "transpose"), ln
        # 4,096 rows' float32 residual (64 MiB), the MLP's two halves (86
        # each), q, k, v and what is kept: 310 MiB read, where the blocked
        # form's float32 scores of a block made it 359
        limit = 336 * 2 ** 20
    assert compiled.memory_analysis().temp_size_in_bytes < limit


def _computations(text):
    """``{name: lines}`` of an HLO module's computations, and the entry's
    name."""
    comps, entry, cur = {}, None, None
    for line in text.split("\n"):
        m = re.match(r"(ENTRY )?%(\S+) \(.*\{\s*$", line)
        if m and not line.startswith(" "):
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            comps[cur].append(line)
    return comps, entry


def _runs_every_step(comps, entry):
    """The computations reached from the entry without passing through a
    ``conditional``'s branch: what runs whichever branch is taken."""
    seen, todo = set(), [entry]
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            if re.search(r" conditional\(", line):
                continue
            todo += re.findall(r"%([\w.\-]+)", " ".join(re.findall(
                r"(?:calls|to_apply|body|condition)=(\{[^}]*\}|\S+)", line)))
    return seen


@pytest.mark.parametrize("exe", ["chunk", "prefill"])
@pytest.mark.parametrize("config", ["gpt2_small", "smallthinker"])
def test_sampler_sorts_only_inside_a_branch(chip, monkeypatch, config, exe):
    """With the engine's own sampler in the decode chunk and in a prefill
    bucket of both served models (their widths and vocabularies, a toy
    depth), every sort over the vocabulary and the ``[B, V]`` division by
    the temperature are compiled into a ``conditional``'s branches, none
    into what the step runs whichever branch it takes: a step whose live
    slots are all greedy runs neither."""
    monkeypatch.setattr(attention_ops, "paged_kernel_mode",
                        lambda: "compiled")
    if config == "gpt2_small":
        (fn, args), _ = _serve_case(exe + "_sampler", chip, n_layer=2)
        b, v = SERVE["slots"], SERVE["vocab"]
    else:
        monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
        (fn, args), _ = _moe_case(exe + "_sampler", chip, n_layer=1,
                                  bucket=1024)
        b, v = MOE_SERVE["slots"], MOE_SERVE["vocab"]
    if exe == "prefill":
        b = 1
    text = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile().as_text()
    comps, entry = _computations(text)
    always = _runs_every_step(comps, entry)
    assert entry in always and len(always) > 1

    def holding(opcode):  # computations with a [b, v] result of `opcode`
        return {name for name, lines in comps.items() for ln in lines
                if " %s(" % opcode in ln
                and "[%d,%d]" % (b, v) in ln.split(" %s(" % opcode)[0]}

    sorts = holding("sort")
    assert sorts, "no sort of the vocabulary: is the sampler in the step?"
    assert not sorts & always, sorts & always
    assert not holding("divide") & always
    # and the branches hang off one conditional of three
    cond, = [ln for lines in comps.values() for ln in lines
             if re.search(r" conditional\(", ln)]
    assert len(re.findall(r"%", cond.split("branch_computations=")[1]
                          .split("}")[0])) == 3


# -- the sparse decoder's executables at smallthinker-21b-a3b-serve's widths ----

MOE_SERVE = dict(slots=16, page_size=16, max_seq=16384, global_pages=6144,
                 window_pages=4096, vocab=151936, bucket=8192)


def _moe_case(name, chip, n_layer=4, bucket=None):
    """``(fn, abstract args, cache ops)`` of the decode chunk or a prefill
    bucket over SmallThinker's block at its published widths (one period of
    its layer pattern by default: the widths are what the chip's compiler
    judges), composed as the engine composes them, over a cache of two
    groups. ``chunk_sampler`` and ``prefill_sampler`` call the engine's own
    sampler in place of the argmax, as in ``_serve_case``."""
    from paddle_tpu.models import smallthinker as st
    from paddle_tpu.serving.kv_cache import CacheGroup, PagedKVCache

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    g = MOE_SERVE
    pattern = ([0, 1, 1, 1] * n_layer)[:n_layer]
    cfg = st.SmallThinkerConfig(
        vocab_size=g["vocab"], n_layer=n_layer, d_model=2560, n_head=28,
        n_kv_head=4, d_head=128, n_expert=64, top_k=6, d_expert=768,
        window=4096, rope_layout=pattern, window_layout=pattern,
        max_seq=g["max_seq"], dtype="bfloat16")
    model = st.SmallThinkerLM(cfg, params={})
    groups = [CacheGroup(n, l, w, g[n + "_pages"])
              for n, l, w in cfg.cache_groups]
    ops = PagedKVCache(n_layer, 4, 128, g["slots"], g["max_seq"],
                       g["page_size"], g["global_pages"], dtype=cfg.dtype,
                       groups=groups, q_per_kv=7)

    def abstract(fn):
        return jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype),
                                      jax.eval_shape(fn))

    params = abstract(lambda: st.init_params(cfg, 0))
    cache = abstract(ops.init_state)
    b = g["slots"]
    ints, flags = sds((b,), jnp.int32), sds((b,), jnp.bool_)

    def chunk(params, cache, lengths, tokens, active, *sampling):
        def body(carry, _):
            cache, ln, tk, ac = carry
            logits, cache, stats = model.decode(params, cache, ops, tk, ln,
                                                ac)
            nxt = jnp.where(ac, _pick(logits, sampling, ln, ac), tk)
            return (cache, ln + ac, nxt, ac), (nxt, stats)

        return jax.lax.scan(body, (cache, lengths, tokens, active), None,
                            length=1)

    def prefill(params, cache, dest, prompt, length, *sampling):
        logits, kvs = model.prefill_last(params, prompt[None], length[None])
        for i, (k, v) in enumerate(kvs):
            cache = ops.write_prompt(cache, i, k[0], v[0], dest, length)
        return cache, _pick(logits, [x[None] for x in sampling],
                            (length - 1)[None])[0]

    scalar = sds((), jnp.int32)
    prefill_args = (params, cache, sds((ops.page_table_len,), jnp.int32),
                    sds((bucket or g["bucket"],), jnp.int32), scalar)
    return {
        "chunk": (chunk, (params, cache, ints, ints, flags)),
        "chunk_sampler": (chunk, (params, cache, ints, ints, flags,
                                  sds((b,), jnp.float32), ints, ints)),
        "prefill": (prefill, prefill_args),
        "prefill_sampler": (prefill, prefill_args + (
            sds((), jnp.float32), scalar, scalar)),
    }[name], ops


def test_grouped_query_paged_kernel_at_the_served_geometry(chip):
    """Row width 512 (4 KV heads of 128), 7 query heads a KV head, page 16,
    bf16: both cache groups' pools, the layer in the middle."""
    why = pa.paged_attention_gate(jnp.bfloat16, 4, 128, 16, q_per_kv=7)
    assert why is None
    for n_layer, pages, pps in ((3, 6144, 1024), (9, 4096, 256)):
        pool = ((n_layer, pages * 16, 512), jnp.bfloat16)
        text = compiled_text(
            chip,
            functools.partial(pa.paged_decode_attention, page_size=16,
                              layer=1, sm_scale=128 ** -0.5),
            ((16, 28, 128), jnp.bfloat16), pool, pool,
            ((16, pps), jnp.int32), ((16,), jnp.int32))
        kernel, = [ln for ln in text.split("\n") if "tpu_custom_call" in ln]
        assert kernel.strip().startswith("%paged_attention")
        assert [op for _, rtype, op, _ in _instructions(text)
                if _has_dim(rtype, pages * 16) and op != "parameter"] == []


@pytest.mark.parametrize("cell,layer,q_block", [
    ("gpt2-small-serve", 5, "[32,1,768]"),
    ("smallthinker global layer", 0, "[16,8,512]"),
    ("smallthinker window layer", 1, "[16,8,512]")])
def test_cache_attention_over_live_lengths_at_the_cells_shapes(
        chip, monkeypatch, cell, layer, q_block):
    """``decode_attention`` as the models call it, ``active`` beside the
    lengths, at the serve cells' geometries: the kernel whose rowless grid
    step returns at once compiles for the chip with the q block a device
    trace names it by, the lengths it is handed come out of a ``select`` on
    the live mask (then the window's ``minimum``), and the pool is still
    handed over whole."""
    monkeypatch.setattr(attention_ops, "paged_kernel_mode",
                        lambda: "compiled")
    if cell == "gpt2-small-serve":
        (_, args), rows = _serve_case("chunk", chip)
        from paddle_tpu.serving.kv_cache import PagedKVCache
        g = SERVE
        ops = PagedKVCache(g["n_layer"], g["n_head"], 64, g["slots"],
                           g["max_seq"], g["page_size"], g["num_pages"],
                           dtype="bfloat16")
        heads = g["n_head"]
    else:
        (_, args), ops = _moe_case("chunk", chip)
        rows = ops.groups[ops._where[layer][0]].num_pages * ops.page_size
        heads = 28
    cache, lengths, active = args[1], args[2], args[4]
    q = jax.ShapeDtypeStruct((ops.slots, heads, ops.d_head), jnp.bfloat16,
                             sharding=chip)
    text = jax.jit(
        lambda cache, q, n, live: ops.decode_attention(
            cache, layer, q, n, live, sm_scale=0.125)
    ).lower(cache, q, lengths, active).compile().as_text()
    kernel, = [ln for ln in text.split("\n") if "tpu_custom_call" in ln]
    assert kernel.strip().startswith("%paged_attention")
    assert "bf16" + q_block in kernel
    ops_on_lengths = {op for _, rtype, op, _ in _instructions(text)
                      if rtype.startswith("s32[%d]" % ops.slots)}
    assert "select" in ops_on_lengths
    assert ("minimum" in ops_on_lengths) == cell.endswith("window layer")
    assert [op for _, rtype, op, _ in _instructions(text)
            if _has_dim(rtype, rows) and op != "parameter"] == []


@pytest.mark.parametrize("exe", ["chunk", "prefill"])
def test_sparse_decoder_executables(chip, monkeypatch, exe):
    """The decode chunk runs the grouped-query kernel once a layer and the
    fused expert-stream kernel once a layer (96 rows a pass: no grouped
    matmul of the compiler's is left in it), an 8,192-token prefill keeps
    the compiler's grouped matmul three times a layer (49,152 rows a pass),
    and neither copies, slices or transposes a pool of either group; every
    pool is aliased from input to output."""
    # the default backend here is the CPU; only the compile's target is the
    # chip, so the two choices made by asking the backend are made here
    monkeypatch.setattr(attention_ops, "paged_kernel_mode",
                        lambda: "compiled")
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    (fn, args), ops = _moe_case(exe, chip)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    text = compiled.as_text()
    stream, grouped = _expert_products(text, (64, 2560, 768))
    if exe == "chunk":
        assert text.count("%paged_attention") >= 4
        assert (len(stream), grouped) == (4, 0)
    else:
        assert stream == [] and grouped >= 3 * 4
    instructions = list(_instructions(text))
    types = {name: rtype for name, rtype, _, _ in instructions}
    for gi, grp in enumerate(ops.groups):
        rows = grp.num_pages * ops.page_size
        moved = [(op, rtype) for _, rtype, op, operands in instructions
                 if op in ("copy", "copy-start", "slice", "dynamic-slice",
                           "transpose")
                 and any(_has_dim(t, rows) for t in
                         [rtype] + [types.get(o, "") for o in operands])]
        assert moved == [], (grp.name, moved)
    n_params = len(jax.tree_util.tree_leaves(args[0]))
    aliased = {int(p) for p in re.findall(
        r"\((\d+), \{\}, (?:may|must)-alias\)", text.split("\n", 1)[0])}
    # "k" "k.window" "pt" "pt.window" "v" "v.window" in key order
    assert {n_params, n_params + 1, n_params + 4, n_params + 5} <= aliased


@pytest.mark.parametrize("cell,m,e,d,f", [
    ("smallthinker-mixed-sat", 96, 64, 2560, 768),
    ("ling3-flash-reason-sat", 256, 128, 2560, 768),
    ("kimi-k2-longctx-sat", 256, 12, 7168, 2048),
    ("laguna-s-code-sat", 160, 128, 3072, 1024)])
def test_expert_stream_kernel_at_the_served_decode_geometries(
        chip, cell, m, e, d, f):
    """The fused expert kernel at each sparse cell's decode pass (rows a
    pass, experts held, hidden size, expert width), bf16: the chip's
    compiler takes it, the weights go in AS STORED (no operand of their
    size is copied or transposed on the way), and the VMEM the compiler
    reports using fits the limit the kernel asks for, which is the plan's."""
    from paddle_tpu.ops.pallas_kernels import expert_stream as es

    assert es.expert_stream_gate(m, e, d, f, jnp.bfloat16) is None
    plan = es.expert_stream_plan(m, e, d, f, jnp.bfloat16)
    assert (plan["nkd"], plan["nkf"]) == (
        (4, 4) if cell.startswith("kimi") else (1, 1))
    text = compiled_text(
        chip, functools.partial(es.expert_stream_ffn,
                                activation=jax.nn.silu),
        ((m, d), jnp.bfloat16), ((e, d, f), jnp.bfloat16),
        ((e, d, f), jnp.bfloat16), ((e, f, d), jnp.bfloat16),
        ((e,), jnp.int32))
    (kernel,), grouped = _expert_products(text, (e, d, f))
    assert grouped == 0
    asked, = re.findall(
        r'"scoped_memory_configs":\[\{"memory_space":"1","offset":"0",'
        r'"size":"(\d+)"\}\]', kernel)
    used, = re.findall(
        r'"used_scoped_memory_configs":\[\{"memory_space":"1","offset":"0",'
        r'"size":"(\d+)"\}\]', kernel)
    assert int(asked) == plan["vmem"] and 0 < int(used) <= int(asked)
    assert [(op, rtype) for _, rtype, op, _ in _instructions(text)
            if op != "parameter" and (_has_dim(rtype, d) or _has_dim(rtype, f))
            and _has_dim(rtype, e)] == []


# -- the latent (MLA) decoder ---------------------------------------------------


@pytest.mark.parametrize("slots,heads,pages,layers,ring", [
    (32, 64, 1024, 7, False),   # kimi-k2-longctx-sat
    (64, 32, 1024, 1, False),   # ling3-flash-reason-sat: one layer in seven
    (64, 80, 1024, 2, False),   # motif3-docreason-sat's two full layers
    (64, 80, 8, 7, True),       # ... and its seven rings of 128 rows
], ids=["64_heads", "32_heads", "80_heads", "80_heads_ring"])
def test_latent_decode_kernel_at_the_served_geometry(chip, slots, heads,
                                                     pages, layers, ring):
    """32, 64 and 80 heads over one 640-lane row (512 latent + 64 rotary +
    padding), page 16, bf16, a cell's whole pool, its last layer, at the
    wave the kernel ships with; and the window layers' call over a ring of
    eight pages a slot: the kernel compiles under the call's name, as ONE
    call, and nothing outside it touches the pool."""
    from paddle_tpu.ops.pallas_kernels import mla_attention as mla

    assert mla.mla_decode_gate(jnp.bfloat16, 640, 512, 16) is None
    name = mla.RING_KERNEL_NAME if ring else mla.KERNEL_NAME
    rows = (slots * pages if ring else 18432) * 16
    text = compiled_text(
        chip,
        functools.partial(mla.mla_paged_decode, page_size=16, rank=512,
                          layer=layers - 1, sm_scale=0.1309, name=name),
        ((slots, heads, 640), jnp.bfloat16),
        ((layers, rows, 640), jnp.bfloat16),
        ((slots, pages), jnp.int32), ((slots,), jnp.int32))
    kernel, = [ln for ln in text.split("\n") if "tpu_custom_call" in ln]
    assert kernel.strip().startswith(("%" + name + ".", "ROOT %" + name + "."))
    assert "bf16[%d,%d,512]" % (slots, heads) in kernel
    assert [op for _, rtype, op, _ in _instructions(text)
            if _has_dim(rtype, rows) and op != "parameter"] == []


def _mla_case(chip, n_layer=2):
    """The decode step of the latent decoder at the published widths, as
    one chip of 32 holds it (12 of 384 experts, 20,480 rows of the
    vocabulary), over the cell's pool; ``n_layer`` 2 is the dense layer and
    one expert layer."""
    from paddle_tpu.models import kimi_k2 as kk
    from paddle_tpu.serving.kv_cache import LatentPagedCache

    yarn = {"beta_fast": 1, "beta_slow": 1, "factor": 32, "mscale": 1,
            "mscale_all_dim": 1, "type": "yarn",
            "original_max_position_embeddings": 4096}
    cfg = kk.KimiK2Config(
        vocab_size=20480, n_layer=n_layer, d_model=7168, n_head=64,
        q_rank=1536, kv_rank=512, d_nope=128, d_rope=64, d_v=128,
        d_dense=18432, n_dense=1, n_expert=384, top_k=8, d_expert=2048,
        routed_scale=2.827, rope_scaling=yarn, max_seq=16384,
        dtype="bfloat16", experts_held=tuple(range(12)))
    model = kk.KimiK2LM(cfg, params={})

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    params = jax.tree_util.tree_map(
        sds, jax.eval_shape(lambda: kk.init_params(cfg, 0)))
    ops = LatentPagedCache(n_layer, 512, 64, 32, 16384, 16, 18432,
                           dtype="bfloat16")
    cache = jax.tree_util.tree_map(sds, jax.eval_shape(ops.init_state))
    ints = jax.ShapeDtypeStruct((32,), jnp.int32, sharding=chip)
    flags = jax.ShapeDtypeStruct((32,), jnp.bool_, sharding=chip)

    def chunk(params, cache, lengths, tokens, active):
        logits, cache, stats = model.decode(params, cache, ops, tokens,
                                            lengths, active)
        return cache, jnp.argmax(logits, -1), stats

    return chunk, (params, cache, ints, ints, flags), ops


def test_latent_decoder_decode_step(chip, monkeypatch):
    """The decode step runs the latent kernel once a layer and the fused
    expert-stream kernel once an expert layer (inside the share's loop; no
    grouped matmul of the compiler's is left), and does not copy, slice or
    transpose the latent pool, which is aliased from input to output."""
    monkeypatch.setattr(attention_ops, "paged_kernel_mode",
                        lambda: "compiled")
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    fn, args, ops = _mla_case(chip)
    text = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile().as_text()
    assert text.count("%mla_latent_decode") >= 2
    stream, grouped = _expert_products(text, (12, 7168, 2048))
    assert (len(stream), grouped) == (1, 0)
    instructions = list(_instructions(text))
    types = {name: rtype for name, rtype, _, _ in instructions}
    rows = ops.groups[0].num_pages * ops.page_size
    moved = [(op, rtype) for _, rtype, op, operands in instructions
             if op in ("copy", "copy-start", "slice", "dynamic-slice",
                       "transpose")
             and any(_has_dim(t, rows) for t in
                     [rtype] + [types.get(o, "") for o in operands])]
    assert moved == [], moved
    n_params = len(jax.tree_util.tree_leaves(args[0]))
    aliased = {int(p) for p in re.findall(
        r"\((\d+), \{\}, (?:may|must)-alias\)", text.split("\n", 1)[0])}
    assert n_params in aliased           # "c" before "pt" in key order


# -- the decoder with query heads by layer type (laguna-s-ep2-serve) -------------

MIXED_SERVE = dict(slots=16, page_size=16, max_seq=16384, global_pages=9216,
                   window_pages=512, vocab=50176)


@pytest.mark.parametrize("g,n_layer,pages,pps,rows", [
    (6, 2, 9216, 1024, 8), (9, 3, 512, 32, 16)])
def test_mixed_query_groups_paged_kernel_at_the_served_geometry(
        chip, g, n_layer, pages, pps, rows):
    """Row width 1,024 (8 KV heads of 128), page 16, bf16, each cache
    group's own pool and query heads a KV head: 6 over the full layers'
    pool, 9 over the rings'. The kernel's result is ``[slots, G padded to
    whole sublanes, row width]``, the shape by which a trace tells the two
    groups' calls apart (``grid/readers/mixed_gqa.py``)."""
    assert pa.paged_attention_gate(jnp.bfloat16, 8, 128, 16,
                                   q_per_kv=g) is None
    pool = ((n_layer, pages * 16, 1024), jnp.bfloat16)
    text = compiled_text(
        chip,
        functools.partial(pa.paged_decode_attention, page_size=16, layer=1,
                          sm_scale=128 ** -0.5),
        ((16, 8 * g, 128), jnp.bfloat16), pool, pool,
        ((16, pps), jnp.int32), ((16,), jnp.int32))
    kernel, = [ln for ln in text.split("\n") if "tpu_custom_call" in ln]
    assert kernel.strip().startswith("%paged_attention")
    assert "bf16[16,%d,1024]" % rows in kernel.split(" custom-call(")[0]


def _mixed_model(chip, n_layer=5):
    """``(cfg, model, params as shapes, sds)`` of the decoder with query
    heads by layer type at its published widths, as one chip of two holds
    it (128 of 256 experts, 50,176 rows of the vocabulary): layers full
    (dense), sliding, sliding, sliding, full, or the first ``n_layer``."""
    from paddle_tpu.models import laguna as lg

    g = MIXED_SERVE
    rope = {lg.FULL: {"rope_theta": 500000, "rope_type": "yarn",
                      "factor": 128, "beta_slow": 1, "beta_fast": 32,
                      "original_max_position_embeddings": 8192,
                      "attention_factor": 1.4852030263919618,
                      "partial_rotary_factor": 0.5},
            lg.SLIDING: {"rope_type": "default", "rope_theta": 10000,
                         "partial_rotary_factor": 1}}
    cfg = lg.LagunaConfig(
        vocab_size=g["vocab"], n_layer=n_layer, d_model=3072,
        n_head=[48, 72, 72, 72, 48][:n_layer], n_kv_head=8, d_head=128,
        layer_types=([lg.FULL] + [lg.SLIDING] * 3 + [lg.FULL])[:n_layer],
        window=512,
        rope=rope, d_dense=12288, dense_layers=[0], n_expert=256, top_k=10,
        d_expert=1024, d_shared=1024, routed_scale=2.5, max_seq=g["max_seq"],
        dtype="bfloat16", experts_held=tuple(range(128)))
    model = lg.LagunaLM(cfg, params={})

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    params = jax.tree_util.tree_map(
        sds, jax.eval_shape(lambda: lg.init_params(cfg, 0)))
    return cfg, model, params, sds


def _mixed_case(chip):
    """The decode step of :func:`_mixed_model` over the cell's two pools."""
    from paddle_tpu.serving.kv_cache import CacheGroup, PagedKVCache

    g = MIXED_SERVE
    cfg, model, params, sds = _mixed_model(chip)
    groups = [CacheGroup(n, l, w, g[n + "_pages"])
              for n, l, w in cfg.cache_groups]
    ops = PagedKVCache(5, 8, 128, g["slots"], g["max_seq"], g["page_size"],
                       g["global_pages"], dtype=cfg.dtype, groups=groups,
                       q_per_kv={"global": 6, "window": 9})
    cache = jax.tree_util.tree_map(sds, jax.eval_shape(ops.init_state))
    ints = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=chip)
    flags = jax.ShapeDtypeStruct((16,), jnp.bool_, sharding=chip)

    def chunk(params, cache, lengths, tokens, active):
        logits, cache, stats = model.decode(params, cache, ops, tokens,
                                            lengths, active)
        return cache, jnp.argmax(logits, -1), stats

    return chunk, (params, cache, ints, ints, flags), ops


def test_mixed_query_groups_decoder_decode_step(chip, monkeypatch):
    """The decode step runs the paged kernel once a layer, at the full
    layers' query shape twice and at the window layers' three times, and
    the fused expert-stream kernel once an expert layer (inside the
    share's loop; no grouped matmul of the compiler's is left); it does
    not copy, slice or transpose a pool of either
    group, and every pool is aliased from input to output."""
    monkeypatch.setattr(attention_ops, "paged_kernel_mode",
                        lambda: "compiled")
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    fn, args, ops = _mixed_case(chip)
    assert ops.kernel_mode() == ("compiled", None)
    text = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile().as_text()
    kernels = [ln.split(" custom-call(")[0] for ln in text.split("\n")
               if "tpu_custom_call" in ln and "%paged_attention" in ln]
    assert sum("bf16[16,8,1024]" in k for k in kernels) == 2
    assert sum("bf16[16,16,1024]" in k for k in kernels) == 3
    stream, grouped = _expert_products(text, (128, 3072, 1024))
    assert (len(stream), grouped) == (4, 0)
    instructions = list(_instructions(text))
    types = {name: rtype for name, rtype, _, _ in instructions}
    for grp in ops.groups:
        rows = grp.num_pages * ops.page_size
        moved = [(op, rtype) for _, rtype, op, operands in instructions
                 if op in ("copy", "copy-start", "slice", "dynamic-slice",
                           "transpose")
                 and any(_has_dim(t, rows) for t in
                         [rtype] + [types.get(o, "") for o in operands])]
        assert moved == [], (grp.name, moved)
    n_params = len(jax.tree_util.tree_leaves(args[0]))
    aliased = {int(p) for p in re.findall(
        r"\((\d+), \{\}, (?:may|must)-alias\)", text.split("\n", 1)[0])}
    # "k" "k.window" "pt" "pt.window" "v" "v.window" in key order
    assert {n_params, n_params + 1, n_params + 4, n_params + 5} <= aliased


# -- the hybrid decoder: KDA layers beside an MLA layer (ling-3-flash-ep4-serve)

HYBRID_SERVE = dict(slots=64, page_size=16, max_seq=16384,
                    latent_pages=40960, vocab=39296)


def test_state_step_kernel_at_the_served_geometry(chip):
    """64 slots of 32 heads of a 128 x 128 float32 state, six layers in
    one buffer, a layer in the middle: the kernel compiles under its own
    name, the whole buffer is aliased from input to output, and nothing
    outside the kernel touches it."""
    from paddle_tpu.ops.pallas_kernels import kda

    assert kda.kda_state_step_gate(32, 128, 128) is None
    shape = (6, 64, 32, 128, 128)
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in (
        (shape, jnp.float32), ((64, 32, 128), jnp.bfloat16),
        ((64, 32, 128), jnp.bfloat16), ((64, 32, 128), jnp.bfloat16),
        ((64, 32, 128), jnp.float32), ((64, 32), jnp.float32),
        ((64,), jnp.bool_))]
    text = jax.jit(
        lambda s, q, k, v, a, b, live: kda.kda_state_step(s, 3, q, k, v, a,
                                                          b, live),
        donate_argnums=(0,)).lower(*args).compile().as_text()
    kernel, = [ln for ln in text.split("\n") if "tpu_custom_call" in ln]
    assert kernel.strip().startswith("%kda_state_step")
    assert "f32[6,64,32,128,128]" in kernel.split(" custom-call(")[0]
    assert [op for _, rtype, op, _ in _instructions(text)
            if _has_dim(rtype, 6) and "128,128" in rtype
            and op not in ("parameter", "custom-call", "get-tuple-element",
                           "tuple")] == []
    assert re.search(r"\(0, \{\}, (?:may|must)-alias\)",
                     text.split("\n", 1)[0])


def _hybrid_case(chip):
    """The decode step of the hybrid decoder at its published widths, as
    one chip of four holds it (128 of 512 experts, 39,296 rows of the
    vocabulary): a dense KDA layer, a sparse KDA layer and the MLA layer
    over the cell's latent pool and 64 slots' states."""
    from paddle_tpu.models import ling3_flash as lf
    from paddle_tpu.serving.kv_cache import CacheGroup, LatentPagedCache

    g = HYBRID_SERVE
    cfg = lf.Ling3FlashConfig(
        vocab_size=g["vocab"], n_layer=3, d_model=2560, n_head=32,
        d_state=128, layer_types=["kda", "kda", "mla"], kv_rank=512,
        d_nope=128, d_rope=64, d_v=128, d_dense=6144, dense_layers=(0,),
        n_expert=512, top_k=8, d_expert=768, n_group=8, topk_group=4,
        routed_scale=2.5, max_seq=g["max_seq"], dtype="bfloat16",
        experts_held=tuple(range(128)))
    model = lf.Ling3FlashLM(cfg, params={})

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    params = jax.tree_util.tree_map(
        sds, jax.eval_shape(lambda: lf.init_params(cfg, 0)))
    groups = [CacheGroup(name, layers, window,
                         g["latent_pages"] if kind == "latent" else 0, kind)
              for name, layers, window, kind in cfg.cache_groups]
    ops = LatentPagedCache(3, 512, 64, g["slots"], g["max_seq"],
                           g["page_size"], g["latent_pages"],
                           dtype="bfloat16", groups=groups,
                           slot_state=cfg.slot_state)
    cache = jax.tree_util.tree_map(sds, jax.eval_shape(ops.init_state))
    ints = jax.ShapeDtypeStruct((g["slots"],), jnp.int32, sharding=chip)
    flags = jax.ShapeDtypeStruct((g["slots"],), jnp.bool_, sharding=chip)

    def chunk(params, cache, lengths, tokens, active):
        logits, cache, stats = model.decode(params, cache, ops, tokens,
                                            lengths, active)
        return cache, jnp.argmax(logits, -1), stats

    return chunk, (params, cache, ints, ints, flags), ops


def test_hybrid_decoder_decode_step(chip, monkeypatch):
    """The decode step runs the state kernel once a KDA layer and the
    latent kernel once, and the fused expert-stream kernel once an expert
    layer (no grouped matmul of the compiler's is left); it does not copy, slice or transpose the states or the
    latent pool, and both (and the tails) are aliased from input to
    output."""
    monkeypatch.setattr(attention_ops, "paged_kernel_mode",
                        lambda: "compiled")
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    fn, args, ops = _hybrid_case(chip)
    assert ops.kernel_mode() == ("compiled", None)
    assert ops.state_kernel_mode() == ("compiled", None)
    text = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile().as_text()
    kernels = [ln.strip().split(" = ")[0] for ln in text.split("\n")
               if "tpu_custom_call" in ln]
    assert sum(k.startswith("%kda_state_step") for k in kernels) == 2
    assert sum(k.startswith("%mla_latent_decode") for k in kernels) == 1
    stream, grouped = _expert_products(text, (128, 2560, 768))
    assert len(stream) >= 1 and grouped == 0
    instructions = list(_instructions(text))
    types = {name: rtype for name, rtype, _, _ in instructions}
    rows = ops.groups[0].num_pages * ops.page_size
    moved = [(op, rtype) for _, rtype, op, operands in instructions
             if op in ("copy", "copy-start", "slice", "dynamic-slice",
                       "transpose")
             and any(_has_dim(t, rows) or "f32[2,64,32,128,128]" in t
                     for t in [rtype] + [types.get(o, "") for o in operands])]
    assert moved == [], moved
    n_params = len(jax.tree_util.tree_leaves(args[0]))
    aliased = {int(p) for p in re.findall(
        r"\((\d+), \{\}, (?:may|must)-alias\)", text.split("\n", 1)[0])}
    # "c" "pt" "s.state" "tail.state" in key order
    assert {n_params, n_params + 2, n_params + 3} <= aliased


def _scan_loops(text, heads):
    """The ``while`` instructions of an HLO module whose carried tuple
    names the chunk scan's float32 state ``[heads, 128, 128]`` (what
    ``grid/readers/hybrid.py`` ``_is_scan`` asks of a traced event), each
    with the names of the computations it runs."""
    state = "f32[%d,128,128]" % heads
    return [(rtype, re.findall(r"(?:condition|body)=%([\w.\-]+)", line))
            for line in text.split("\n")
            for _, rtype, op, _ in _instructions(line)
            if op == "while" and state in rtype]


def _calls_the_scan_kernel(text, computations):
    comps, _ = _computations(text)
    return any("tpu_custom_call" in ln and ln.strip().startswith(
        "%kda_chunk_scan") for name in computations for ln in comps[name])


@pytest.mark.parametrize("heads", [32, 64])
def test_chunk_scan_kernel_at_the_served_geometries(chip, heads):
    """A 4,096-row bucket of 32 and of 64 heads of 128 x 128, chunks of
    64: the kernel compiles under its own name, ONE text for the loop's
    turns, inside a ``while`` that carries the float32 state (the chunk
    scan's roofline is that loop's time); q, k, v, the log-decay and the
    outputs (what has 128 lanes a head) are neither copied nor turned on
    their way in and out."""
    from paddle_tpu.ops.pallas_kernels import kda

    assert kda.kda_chunk_scan_gate(heads, 128, 128) is None
    rows = 4096
    text = compiled_text(
        chip, kda.kda_chunk_scan_kernel,
        *[((rows, heads, 128), jnp.bfloat16)] * 3,
        ((rows, heads, 128), jnp.float32), ((rows, heads), jnp.float32))
    kernel, = [ln for ln in text.split("\n") if "tpu_custom_call" in ln]
    assert kernel.strip().startswith("%kda_chunk_scan")
    loop, = _scan_loops(text, heads)
    assert _calls_the_scan_kernel(text, loop[1])
    moved = [(op, rtype) for _, rtype, op, _ in _instructions(text)
             if op in ("copy", "transpose", "fusion")
             and _has_dim(rtype, heads) and (_has_dim(rtype, rows)
                                             or _has_dim(rtype, rows // 64))
             and _has_dim(rtype, 128) and "128,128]" not in rtype]
    assert moved == [], moved
    assert "reduce-window" not in text


def test_hybrid_decoder_prefill_holds_the_scan_kernel_in_its_loop(
        chip, monkeypatch):
    """The hybrid decoder's prefill executable (a dense KDA layer, a
    sparse KDA layer and the MLA layer at the published widths, a 2,048-row
    bucket): a ``while`` a KDA layer carries ``f32[32,128,128]`` and runs
    the ``kda_chunk_scan`` kernel; no running sum of floats (a softmax's,
    a decay's) became a ``reduce-window``."""
    from paddle_tpu.models import ling3_flash as lf

    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    _, (params, _, _, _, _), _ = _hybrid_case(chip)
    cfg = lf.Ling3FlashConfig(
        vocab_size=HYBRID_SERVE["vocab"], n_layer=3, d_model=2560,
        n_head=32, d_state=128, layer_types=["kda", "kda", "mla"],
        kv_rank=512, d_nope=128, d_rope=64, d_v=128, d_dense=6144,
        dense_layers=(0,), n_expert=512, top_k=8, d_expert=768, n_group=8,
        topk_group=4, routed_scale=2.5, max_seq=HYBRID_SERVE["max_seq"],
        dtype="bfloat16", experts_held=tuple(range(128)))
    tokens = jax.ShapeDtypeStruct((1, 2048), jnp.int32, sharding=chip)
    lengths = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=chip)
    text = jax.jit(lambda p, t, n: lf.prefill_forward(p, cfg, t, n)).lower(
        params, tokens, lengths).compile().as_text()
    loops = _scan_loops(text, 32)
    assert len(loops) == 2
    assert all(_calls_the_scan_kernel(text, comps) for _, comps in loops)
    # the routing's integer running counts are reduce-windows; no float is
    assert [rtype for _, rtype, op, _ in _instructions(text)
            if op == "reduce-window" and rtype[:3] != "s32"] == []


# -- the grouped-differential latent decoder (motif-3-beta-ep16-serve) -----------


def test_expert_stream_kernel_with_an_experts_own_numbers(chip):
    """The fused expert kernel at the cell's decode pass (256 rows, 24 of
    384 held, 4096 x 1280, bf16) with PolyNorm's four numbers an expert as
    an SMEM operand: the chip's compiler takes it, a matrix goes in two
    row blocks (10.5 MB each), and no operand of the weights' size is
    copied on the way."""
    from paddle_tpu.models import motif3 as mf
    from paddle_tpu.ops.pallas_kernels import expert_stream as es

    m, e, d, f = 256, 24, 4096, 1280
    assert es.expert_stream_gate(m, e, d, f, jnp.bfloat16) is None
    plan = es.expert_stream_plan(m, e, d, f, jnp.bfloat16)
    assert (plan["nkd"], plan["nkf"]) == (2, 2)

    def ffn(xs, wg, wu, wd, sizes, pn):
        return es.expert_stream_ffn(xs, wg, wu, wd, sizes,
                                    mf._activation(0.5, 0.5), act_params=pn)

    text = compiled_text(
        chip, ffn, ((m, d), jnp.bfloat16), ((e, d, f), jnp.bfloat16),
        ((e, d, f), jnp.bfloat16), ((e, f, d), jnp.bfloat16),
        ((e,), jnp.int32), ((e, 4), jnp.float32))
    (kernel,), grouped = _expert_products(text, (e, d, f))
    assert grouped == 0 and "f32[%d]" % (4 * e) in kernel
    assert [(op, rtype) for _, rtype, op, _ in _instructions(text)
            if op != "parameter" and (_has_dim(rtype, d) or _has_dim(rtype, f))
            and _has_dim(rtype, e)] == []


def _gdla_model(chip, n_layer=3):
    """``(cfg, model, params as shapes, sds)`` of the grouped-differential
    latent decoder at its published widths, as one chip of sixteen holds
    it (24 of 384 experts, 27,520 rows of the vocabulary): a dense window
    layer, a sparse window layer and a sparse full layer, or the first
    ``n_layer``."""
    from paddle_tpu.models import motif3 as mf

    yarn = {"original_max_position_embeddings": 4096, "factor": 64,
            "mscale": 1, "rope_type": "yarn", "rope_theta": 10000,
            "beta_fast": 32, "beta_slow": 1, "apply_yarn_scaling": False}
    cfg = mf.Motif3Config(
        vocab_size=27520, n_layer=n_layer, d_model=4096, n_head=80,
        n_kv_head=16, q_rank=1024, kv_rank=512, d_nope=128, d_rope=64,
        d_v=128, layer_types=["window", "window", "full"][:n_layer],
        window=128, d_dense=12288,
        dense_layers=(0,), n_expert=384, top_k=8, d_expert=1280,
        routed_scale=2.0, rope_scaling=yarn, max_seq=16384,
        dtype="bfloat16", experts_held=tuple(range(24)))
    model = mf.Motif3LM(cfg, params={})

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    params = jax.tree_util.tree_map(
        sds, jax.eval_shape(lambda: mf.init_params(cfg, 0)))
    return cfg, model, params, sds


def _gdla_case(chip):
    """The decode step of :func:`_gdla_model` over the cell's page pool
    and 64 slots' rings."""
    from paddle_tpu.serving.kv_cache import CacheGroup, LatentPagedCache

    cfg, model, params, sds = _gdla_model(chip)
    pages = {"latent_full": 36864, "latent_ring": 64 * 8}
    groups = [CacheGroup(name, layers, window, pages[name], kind)
              for name, layers, window, kind in cfg.cache_groups]
    ops = LatentPagedCache(3, 512, 64, 64, 16384, 16, 36864,
                           dtype="bfloat16", groups=groups)
    cache = jax.tree_util.tree_map(sds, jax.eval_shape(ops.init_state))
    ints = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=chip)
    flags = jax.ShapeDtypeStruct((64,), jnp.bool_, sharding=chip)

    def chunk(params, cache, lengths, tokens, active):
        logits, cache, stats = model.decode(params, cache, ops, tokens,
                                            lengths, active)
        return cache, jnp.argmax(logits, -1), stats

    return chunk, (params, cache, ints, ints, flags), ops


def test_gdla_decoder_decode_step(chip, monkeypatch):
    """The decode step runs the latent kernel at 80 heads once a layer,
    under its ring name over the window layers' rings and its own over the
    full layer's pages, and the fused expert-stream kernel once an expert
    layer (no grouped matmul of the compiler's is left); it does not copy,
    slice or transpose either pool, and both are aliased from input to
    output."""
    monkeypatch.setattr(attention_ops, "paged_kernel_mode",
                        lambda: "compiled")
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    fn, args, ops = _gdla_case(chip)
    assert ops.kernel_mode() == ("compiled", None)
    text = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile().as_text()
    kernels = [ln.strip().split(" = ")[0] for ln in text.split("\n")
               if "tpu_custom_call" in ln]
    assert sum(k.startswith("%mla_latent_decode_ring") for k in kernels) == 2
    assert sum(k.startswith("%mla_latent_decode") for k in kernels) == 3
    assert all("bf16[64,80,512]" in ln for ln in text.split("\n")
               if "tpu_custom_call" in ln and "%mla_latent_decode" in ln)
    stream, grouped = _expert_products(text, (24, 4096, 1280))
    assert len(stream) >= 1 and grouped == 0
    instructions = list(_instructions(text))
    types = {name: rtype for name, rtype, _, _ in instructions}
    pools = [g.num_pages * ops.page_size for g in ops.groups]
    moved = [(op, rtype) for _, rtype, op, operands in instructions
             if op in ("copy", "copy-start", "slice", "dynamic-slice",
                       "transpose")
             and any(_has_dim(t, rows) and _has_dim(t, 640)
                     for rows in pools
                     for t in [rtype] + [types.get(o, "") for o in operands])]
    assert moved == [], moved
    n_params = len(jax.tree_util.tree_leaves(args[0]))
    aliased = {int(p) for p in re.findall(
        r"\((\d+), \{\}, (?:may|must)-alias\)", text.split("\n", 1)[0])}
    # "c" "c.latent_ring" "pt" "pt.latent_ring" in key order
    assert {n_params, n_params + 1} <= aliased


# -- the sparse latent hybrid (GLM-5.3-Flash as one chip of EP8) ---------------


def test_sparse_latent_kernel_at_the_served_geometry(chip):
    """64 heads over a 512-lane row with no rotary lane, bf16, the cell's
    whole pool, a table of 512 eight-row tiles a slot and the row mask:
    the kernel compiles under the sparse call's name, as ONE call, and
    nothing outside it touches the pool. A four-row copy (a block alone)
    is what the chip's compiler refuses; the gate says so first."""
    from paddle_tpu.ops.pallas_kernels import mla_attention as mla

    assert mla.mla_decode_gate(jnp.bfloat16, 512, 512, mla.SPARSE_TILE,
                               sparse=True) is None
    assert mla.mla_decode_gate(jnp.bfloat16, 512, 512, 4,
                               sparse=True) is not None
    rows = 40960 * 16
    text = compiled_text(
        chip,
        lambda q, pool, table, length, valid: mla.mla_paged_decode(
            q, pool, table, length, page_size=mla.SPARSE_TILE, rank=512,
            layer=0, sm_scale=0.0625, name=mla.SPARSE_KERNEL_NAME,
            row_valid=valid),
        ((64, 64, 512), jnp.bfloat16), ((1, rows, 512), jnp.bfloat16),
        ((64, 512), jnp.int32), ((64,), jnp.int32), ((64, 4096), jnp.bool_))
    kernel, = [ln for ln in text.split("\n") if "tpu_custom_call" in ln]
    assert kernel.strip().startswith(("%dsa_sparse_decode.",
                                      "ROOT %dsa_sparse_decode."))
    assert "bf16[64,64,512]" in kernel
    assert [op for _, rtype, op, _ in _instructions(text)
            if _has_dim(rtype, rows) and op != "parameter"] == []


def _glm_case(chip):
    """The sparse latent hybrid at its published widths, as one chip of
    eight holds it (36 of 288 experts, 19,360 rows of the vocabulary): a
    dense KDA layer and the sparse DSA layer, parameters as shapes."""
    from paddle_tpu.models import glm5_flash as gf

    cfg = gf.Glm5FlashConfig(
        vocab_size=19360, n_layer=2, d_model=4096, n_head=64, d_state=128,
        layer_types=[gf.KDA, gf.DSA], q_rank=1536, kv_rank=512, d_nope=256,
        d_v=256, index_heads=32, index_dim=128, index_topk=2048,
        index_kpool=4, d_dense=12288, dense_layers=(0,), n_expert=288,
        top_k=8, d_expert=2048, routed_scale=2.5, max_seq=16384,
        dtype="bfloat16", experts_held=tuple(range(36)))
    model = gf.Glm5FlashLM(cfg, params={})

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    params = jax.tree_util.tree_map(
        sds, jax.eval_shape(lambda: gf.init_params(cfg, 0)))
    return cfg, model, params, sds


def test_sparse_hybrid_decoder_decode_step(chip, monkeypatch):
    """The decode step of the sparse latent hybrid at its published
    widths, a dense KDA layer and the sparse DSA layer over the
    cell's pool, index and states: the state kernel once, the sparse read
    once under its own name (and no dense latent call), the fused expert
    kernel; the latent pool is neither copied nor sliced, and pool, index
    and states are aliased from input to output."""
    from paddle_tpu.serving.kv_cache import CacheGroup, LatentPagedCache

    monkeypatch.setattr(attention_ops, "paged_kernel_mode",
                        lambda: "compiled")
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    cfg, model, params, sds = _glm_case(chip)
    groups = [CacheGroup(name, layers, window,
                         40960 if kind == "latent" else 0, kind)
              for name, layers, window, kind in cfg.cache_groups]
    ops = LatentPagedCache(2, 512, 0, 64, 16384, 16, 40960, dtype="bfloat16",
                           groups=groups, slot_state=cfg.slot_state,
                           index=cfg.index_row)
    assert ops.sparse_kernel_mode() == ("compiled", None)
    assert ops.state_kernel_mode() == ("compiled", None)
    cache = jax.tree_util.tree_map(sds, jax.eval_shape(ops.init_state))
    ints = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=chip)
    flags = jax.ShapeDtypeStruct((64,), jnp.bool_, sharding=chip)

    def chunk(params, cache, lengths, tokens, active):
        logits, cache, stats = model.decode(params, cache, ops, tokens,
                                            lengths, active)
        return cache, jnp.argmax(logits, -1), stats

    text = jax.jit(chunk, donate_argnums=(1,)).lower(
        params, cache, ints, ints, flags).compile().as_text()
    kernels = [ln.strip().split(" = ")[0] for ln in text.split("\n")
               if "tpu_custom_call" in ln]
    assert sum(k.startswith("%kda_state_step") for k in kernels) == 1
    assert sum(k.startswith("%dsa_sparse_decode") for k in kernels) == 1
    assert not any(k.startswith("%mla_latent_decode") for k in kernels)
    stream, grouped = _expert_products(text, (36, 4096, 2048))
    assert len(stream) >= 1 and grouped == 0
    instructions = list(_instructions(text))
    types = {name: rtype for name, rtype, _, _ in instructions}
    rows = 40960 * 16
    moved = [(op, rtype) for _, rtype, op, operands in instructions
             if op in ("copy", "copy-start", "slice", "dynamic-slice",
                       "transpose")
             and any(_has_dim(t, rows) and _has_dim(t, 512)
                     for t in [rtype] + [types.get(o, "") for o in operands])]
    assert moved == [], moved
    n_params = len(jax.tree_util.tree_leaves(params))
    aliased = {int(p) for p in re.findall(
        r"\((\d+), \{\}, (?:may|must)-alias\)", text.split("\n", 1)[0])}
    # "c" "ik" "it" "pt" "s.state" "tail.state" in key order
    assert {n_params, n_params + 1, n_params + 4} <= aliased


@pytest.mark.parametrize("bucket", [4096, 8192])
def test_sparse_hybrid_decoder_prefill_attends_in_one_kernel(chip,
                                                             monkeypatch,
                                                             bucket):
    """The cell's two buckets' prefills of a KDA and a DSA layer: the DSA
    layer's attention under its rows' own masks is ONE
    ``dsa_prefill_attention`` call, no score of 64 heads against the
    bucket's keys is a float32 result of any instruction, and no softmax
    became a ``reduce-window`` (the selection's and the experts' running
    counts, int32, are the only ones). The call takes the prompt's length
    as a scalar-prefetch operand with no ``[S, H D]`` copy more around it,
    and the mask's loop holds the ``conditional`` that skips the blocks
    past the prompt and those that keep every closed block."""
    from paddle_tpu.ops.pallas_kernels import dsa_prefill

    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    _, model, params, _ = _glm_case(chip)
    assert dsa_prefill.dsa_prefill_gate(64, 256, 256, bucket, 4) is None
    toks = jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=chip)
    lens = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=chip)
    text = jax.jit(model.prefill_last).lower(params, toks,
                                             lens).compile().as_text()
    kernels = [ln.strip().split(" = ")[0] for ln in text.split("\n")
               if "tpu_custom_call" in ln]
    assert sum(k.startswith("%dsa_prefill_attention") for k in kernels) == 1
    instructions = list(_instructions(text))
    assert [rtype for _, rtype, op, _ in instructions
            if op == "reduce-window" and not rtype.startswith("s32")] == []
    scores = re.compile(r"f32\[(\d+,)*64,\d+,%d\]" % bucket)
    assert [rtype for _, rtype, _, _ in instructions
            if scores.search(rtype)] == []
    # the length's scalar brought no copy of [S, H D] around the call: its
    # result is read as it lies, and of its operands only k and v come
    # through one (the compiler's turn of its own products, as before)
    (_, feeding, after), = _copies_around(instructions,
                                          "dsa_prefill_attention", bucket)
    assert len(feeding) <= 2 and after == [], (feeding, after)
    # the mask's loop chooses a block under a conditional: a block past
    # the prompt, one that keeps every closed block, and one that selects
    homes, reached = _choices_inside_loops(text)
    assert len(homes) == 1
    assert any("dsa_select" in body for body in reached.values())


def _prefill_last_of(build, n_layer):
    """``(chip, bucket) -> (fn, abstract args)`` of ``prefill_last`` over
    the first ``n_layer`` layers of ``build``'s model."""
    def case(chip, bucket):
        _, model, params, _ = build(chip, n_layer)
        return model.prefill_last, (
            params, jax.ShapeDtypeStruct((1, bucket), jnp.int32,
                                         sharding=chip),
            jax.ShapeDtypeStruct((1,), jnp.int32, sharding=chip))
    return case


# config: (its prefill over the leading layers up to the FIRST window layer
# (a compile of the expert layers' 8,192 rows is 10-25 s a layer), query
# heads, KV heads, D, Dv, window); SmallThinker's as the engine composes
# it, over its two pools
WINDOW_PREFILLS = {
    "smallthinker": (lambda chip, bucket: _moe_case(
        "prefill", chip, n_layer=2, bucket=bucket)[0], 28, 4, 128, 128, 4096),
    "laguna": (_prefill_last_of(_mixed_model, 2), 72, 8, 128, 128, 512),
    "motif3": (_prefill_last_of(_gdla_model, 1), 80, 16, 192, 128, 128),
}


@pytest.mark.parametrize("config,bucket", [
    ("smallthinker", 8192), ("laguna", 4096), ("laguna", 8192),
    ("motif3", 2048), ("motif3", 4096), ("motif3", 8192)])
def test_window_layers_prefill_attends_in_one_kernel(chip, monkeypatch,
                                                     config, bucket):
    """The cells' own buckets past the window, at the published widths: the
    gate takes the shapes, the window layer's attention is ONE
    ``window_prefill_attention`` call, and no float32 instruction result
    is a block of 512 query rows against ``window + 512`` keys or more
    (the blocked form's scores, which went through HBM)."""
    from paddle_tpu.ops.pallas_kernels import window_prefill

    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    build, hq, hkv, d, d_v, window = WINDOW_PREFILLS[config]
    assert window_prefill.window_prefill_gate(
        hq, hkv, d, d_v, bucket, window) is None
    fn, args = build(chip, bucket)
    text = jax.jit(fn).lower(*args).compile().as_text()
    kernels = [ln.strip().split(" = ")[0] for ln in text.split("\n")
               if "tpu_custom_call" in ln]
    assert sum(k.startswith("%window_prefill_attention")
               for k in kernels) == 1
    scores = [rtype for _, rtype, _, _ in _instructions(text)
              if rtype.startswith("f32[") and any(
                  rows == 512 and keys >= window + 512
                  for rows, keys in re.findall(r"(\d+),(\d+)\]", rtype))]
    assert scores == []


def test_half_share_prefill_returns_its_rows_without_a_scatter(chip,
                                                               monkeypatch):
    """The half share's 8,192-row prefill (the dense layer and the first
    expert layer at the published widths): the share's loop holds the
    three grouped matmuls of a 51,200-row pass and NO scatter, no sort (what
    the compiler makes a scatter-add's unsorted indices into), no ``copy``
    and no ``transpose`` (the parent's loop had neither, and 81,920-row
    matmuls); the rows come back by a gather of ``[81920, 3072]`` in the
    experts' type whose ``[10, 8192, 3072]`` view is a bitcast; and the
    executable's scratch is under the 2,336,591,872 bytes the same two
    layers took with the scatter-add (commit 1936beb, compiled here)."""
    from paddle_tpu.monitor import metrics
    from paddle_tpu.ops import moe_ops

    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    traced = metrics.counter("moe/share_combine.gather").value
    fn, args = _prefill_last_of(_mixed_model, 2)(chip, 8192)
    compiled = jax.jit(fn).lower(*args).compile()
    assert moe_ops.pass_rows(81920, 128, 256) == 51200
    assert metrics.counter("moe/share_combine.gather").value == traced + 1
    text = compiled.as_text()
    comps, _ = _computations(text)
    loops = [comps[body] for body in re.findall(
        r" while\([^\n]*body=%([\w.\-]+)", text)
        if any("moe/experts" in ln for ln in comps[body])]
    assert len(loops) == 1
    made = list(_instructions("\n".join(loops[0])))
    assert not {"sort", "copy", "transpose"} & {op for _, _, op, _ in made}
    assert not any("scatter" in ln for ln in loops[0])
    assert sum(name.startswith("ragged-dot-none") and
               rtype.startswith("bf16[51200,") for name, rtype, _, _ in made
               ) == 3
    assert any(rtype.startswith("bf16[81920,3072]") and op == "fusion"
               for _, rtype, op, _ in made)
    assert not any(rtype.startswith("f32[81920,") for _, rtype, _, _ in made)
    assert compiled.memory_analysis().temp_size_in_bytes < 2336591872


# -- Falcon-H1: a Mamba-2 state beside a GQA page pool in every layer -----------

def test_ssd_state_step_kernel_at_the_served_geometry(chip):
    """64 slots of 32 heads of a 256 x 128 float32 state (2 groups of 16
    heads, a group a grid step), five layers in one buffer, a layer in the
    middle: the kernel compiles under its own name, the whole buffer is
    aliased from input to output, and nothing outside the kernel touches
    it."""
    from paddle_tpu.ops.pallas_kernels import ssd

    assert ssd.ssd_state_step_gate(32, 256, 128, 2) is None
    shape = (5, 64, 32, 256, 128)
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in (
        (shape, jnp.float32), ((64, 32, 128), jnp.float32),
        ((64, 2, 256), jnp.float32), ((64, 2, 256), jnp.float32),
        ((64, 32), jnp.float32), ((64,), jnp.bool_))]
    text = jax.jit(
        lambda s, x, b, c, a, live: ssd.ssd_state_step(s, 2, x, b, c, a,
                                                       live),
        donate_argnums=(0,)).lower(*args).compile().as_text()
    kernel, = [ln for ln in text.split("\n") if "tpu_custom_call" in ln]
    assert kernel.strip().startswith("%ssd_state_step")
    assert "f32[5,64,32,256,128]" in kernel.split(" custom-call(")[0]
    assert [op for _, rtype, op, _ in _instructions(text)
            if _has_dim(rtype, 5) and "256,128" in rtype
            and op not in ("parameter", "custom-call", "get-tuple-element",
                           "tuple")] == []
    assert re.search(r"\(0, \{\}, (?:may|must)-alias\)",
                     text.split("\n", 1)[0])


@pytest.mark.parametrize("rows", [1024, 4096])
def test_ssd_chunk_scan_kernel_at_the_served_geometry(chip, monkeypatch,
                                                      rows):
    """A bucket's rows of 32 heads of 128 channels, 2 groups of 256 state
    lanes, chunks of 128: on a TPU ``ssd_chunk_scan`` takes the kernel
    form, ONE call whose grid holds the chunks."""
    from paddle_tpu.ops.pallas_kernels import ssd

    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    assert ssd.ssd_chunk_scan_gate(32, 2, 256, 128) is None
    f32 = jnp.float32
    text = compiled_text(
        chip, lambda x, b, c, a: ssd.ssd_chunk_scan(x, b, c, a),
        ((rows, 32, 128), f32), ((rows, 2, 256), f32), ((rows, 2, 256), f32),
        ((rows, 32), f32))
    kernel, = [ln for ln in text.split("\n") if "tpu_custom_call" in ln]
    assert kernel.strip().startswith("%ssd_chunk_scan")


FALCON_SERVE = dict(slots=64, page_size=16, max_seq=8192, pages=12288)


def _falcon_case(chip, n_layer=2):
    """Falcon-H1-34B at its published widths, ``n_layer`` whole layers and
    the whole vocabulary, over the cell's K/V pool and 64 slots' states:
    ``(model, params, ops, cache)`` as shapes on the described chip."""
    import json

    from grid.drivers import serve_ssm
    from paddle_tpu.models import falcon_h1 as fh
    from paddle_tpu.serving.kv_cache import CacheGroup, PagedKVCache

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "grid", "configs",
                           "falcon-h1-34b-serve.json")) as f:
        cfg = serve_ssm.model_config(dict(json.load(f),
                                          num_hidden_layers=n_layer))
    model = fh.FalconH1LM(cfg, params={})

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    params = jax.tree_util.tree_map(
        sds, jax.eval_shape(lambda: fh.init_params(cfg, 0)))
    g = FALCON_SERVE
    groups = [CacheGroup(name, layers, window,
                         g["pages"] if kind == "kv" else 0, kind)
              for name, layers, window, kind in cfg.cache_groups]
    ops = PagedKVCache(n_layer, 4, 128, g["slots"], g["max_seq"],
                       g["page_size"], g["pages"], dtype="bfloat16",
                       groups=groups, q_per_kv=5, slot_state=cfg.slot_state,
                       recurrence=cfg.state_recurrence)
    cache = jax.tree_util.tree_map(sds, jax.eval_shape(ops.init_state))
    return model, params, ops, cache


def test_parallel_hybrid_decoder_decode_step(chip, monkeypatch):
    """A decode step of two whole layers: EACH layer calls the state-step
    kernel over the group's whole float32 state buffer AND the paged
    kernel at 5 query heads a KV head (8 rows a KV head: its result is
    ``[64, 8, 512]``); neither the K and V pools nor the states are
    copied, sliced or turned, and every one is aliased from input to
    output."""
    monkeypatch.setattr(attention_ops, "paged_kernel_mode",
                        lambda: "compiled")
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    model, params, ops, cache = _falcon_case(chip)
    assert ops.kernel_mode() == ("compiled", None)
    assert ops.state_kernel_mode() == ("compiled", None)
    ints = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=chip)
    flags = jax.ShapeDtypeStruct((64,), jnp.bool_, sharding=chip)

    def chunk(params, cache, lengths, tokens, active):
        logits, cache, stats = model.decode(params, cache, ops, tokens,
                                            lengths, active)
        return cache, jnp.argmax(logits, -1), stats

    text = jax.jit(chunk, donate_argnums=(1,)).lower(
        params, cache, ints, ints, flags).compile().as_text()
    kernels = [ln.strip().split(" custom-call(")[0]
               for ln in text.split("\n") if "tpu_custom_call" in ln]
    steps = [k for k in kernels if k.startswith("%ssd_state_step")]
    paged = [k for k in kernels if k.startswith("%paged_attention")]
    assert len(steps) == 2 and len(paged) == 2
    assert all("f32[2,64,32,256,128]" in k for k in steps)
    assert all("bf16[64,8,512]" in k for k in paged)
    instructions = list(_instructions(text))
    types = {name: rtype for name, rtype, _, _ in instructions}
    rows = FALCON_SERVE["pages"] * 16
    moved = [(op, rtype) for _, rtype, op, operands in instructions
             if op in ("copy", "copy-start", "slice", "dynamic-slice",
                       "transpose")
             and any(_has_dim(t, rows) or "32,256,128" in t
                     for t in [rtype] + [types.get(o, "") for o in operands])]
    assert moved == [], moved
    n_params = len(jax.tree_util.tree_leaves(params))
    aliased = {int(p) for p in re.findall(
        r"\((\d+), \{\}, (?:may|must)-alias\)", text.split("\n", 1)[0])}
    # "k" "pt" "s.ssm" "tail.ssm" "v" in key order
    assert {n_params, n_params + 2, n_params + 3, n_params + 4} <= aliased


def test_parallel_hybrid_decoder_prefill_holds_the_scan_kernel(chip,
                                                               monkeypatch):
    """The 1,024-row bucket's prefill of two whole layers: each layer's
    chunk scan is ONE ``ssd_chunk_scan`` kernel call (no loop of the
    compiler's carries the state), and causal attention composes its
    scores below the flash kernel's length."""
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    model, params, _, _ = _falcon_case(chip)
    toks = jax.ShapeDtypeStruct((1, 1024), jnp.int32, sharding=chip)
    lens = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=chip)
    text = jax.jit(model.prefill_last).lower(params, toks,
                                             lens).compile().as_text()
    kernels = [ln.strip().split(" = ")[0] for ln in text.split("\n")
               if "tpu_custom_call" in ln]
    assert sum(k.startswith("%ssd_chunk_scan") for k in kernels) == 2
    assert not [rtype for _, rtype, op, _ in _instructions(text)
                if op == "while" and "f32[32,256,128]" in rtype]


def _dsv32_case(chip, n_layer=2):
    """The row-choosing latent decoder at its published widths, as one
    chip of sixteen holds it (16 of 256 experts, 16,160 rows of the
    vocabulary): the dense layer and a sparse one, parameters as shapes."""
    from paddle_tpu.models import deepseek_v32 as ds

    scaling = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
               "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
               "type": "yarn"}
    cfg = ds.DeepSeekV32Config(
        vocab_size=16160, n_layer=n_layer, d_model=7168, n_head=128,
        q_rank=1536, kv_rank=512, d_nope=128, d_rope=64, d_v=128,
        index_heads=64, index_dim=128, index_topk=2048, d_dense=18432,
        n_dense=1, n_expert=256, top_k=8, d_expert=2048, n_group=8,
        topk_group=4, routed_scale=2.5, rope_theta=1e4, rope_scaling=scaling,
        max_seq=16384, dtype="bfloat16", experts_held=tuple(range(16)))
    model = ds.DeepSeekV32LM(cfg, params={})

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    params = jax.tree_util.tree_map(
        sds, jax.eval_shape(lambda: ds.init_params(cfg, 0)))
    return cfg, model, params, sds


def test_row_choosing_decoder_decode_step(chip, monkeypatch):
    """The decode step of the row-choosing latent decoder at its published
    widths, the dense layer and a sparse one over the cell's two pools:
    the index scores and the sparse read once a layer each under their
    own names (and no dense latent call), the fused expert kernel, no ``top_k`` sort of the scores (the
    choice is a bisection: the sorts left are the expert layer's and the
    probe's); neither pool is copied or sliced, both are aliased from
    input to output, and the step's scratch is a fraction of a GB."""
    from paddle_tpu.serving.kv_cache import CacheGroup, LatentPagedCache

    monkeypatch.setattr(attention_ops, "paged_kernel_mode",
                        lambda: "compiled")
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    cfg, model, params, sds = _dsv32_case(chip)
    groups = [CacheGroup(name, layers, window, 20480, kind)
              for name, layers, window, kind in cfg.cache_groups]
    ops = LatentPagedCache(2, 512, 64, 32, 16384, 16, 20480,
                           dtype="bfloat16", groups=groups,
                           index=cfg.index_row)
    assert ops.index_kernel_mode() == ("compiled", None)
    cache = jax.tree_util.tree_map(sds, jax.eval_shape(ops.init_state))
    assert {k: v.shape for k, v in cache.items()} == {
        "c": (2, 327680, 640), "ik": (2, 20480, 16, 128), "pt": (32, 1024)}
    ints = jax.ShapeDtypeStruct((32,), jnp.int32, sharding=chip)
    flags = jax.ShapeDtypeStruct((32,), jnp.bool_, sharding=chip)

    def chunk(params, cache, lengths, tokens, active):
        logits, cache, stats = model.decode(params, cache, ops, tokens,
                                            lengths, active)
        return cache, jnp.argmax(logits, -1), stats

    exe = jax.jit(chunk, donate_argnums=(1,)).lower(
        params, cache, ints, ints, flags).compile()
    text = exe.as_text()
    assert exe.memory_analysis().temp_size_in_bytes < 0.5e9
    kernels = [ln.strip().split(" = ")[0] for ln in text.split("\n")
               if "tpu_custom_call" in ln]
    assert sum(k.startswith("%dsa_sparse_decode") for k in kernels) == 2
    assert sum(k.startswith("%dsa_index_scores") for k in kernels) == 2
    assert not any(k.startswith("%mla_latent_decode") for k in kernels)
    stream, grouped = _expert_products(text, (16, 7168, 2048))
    assert len(stream) >= 1 and grouped == 0
    instructions = list(_instructions(text))
    types = {name: rtype for name, rtype, _, _ in instructions}
    # no sort over a slot's 16,384 scores, and no float reduce-window
    assert [rtype for _, rtype, op, _ in instructions
            if op == "sort" and _has_dim(rtype, 16384)] == []
    assert [rtype for _, rtype, op, _ in instructions
            if op == "reduce-window" and not rtype.startswith("s32")] == []
    for rows, lanes in ((327680, 640), (20480, 128)):
        moved = [(op, rtype) for _, rtype, op, operands in instructions
                 if op in ("copy", "copy-start", "slice", "dynamic-slice",
                           "transpose")
                 and any(_has_dim(t, rows) and _has_dim(t, lanes)
                         for t in [rtype] + [types.get(o, "")
                                             for o in operands])]
        assert moved == [], moved
    n_params = len(jax.tree_util.tree_leaves(params))
    aliased = {int(p) for p in re.findall(
        r"\((\d+), \{\}, (?:may|must)-alias\)", text.split("\n", 1)[0])}
    assert {n_params, n_params + 1} <= aliased      # "c" "ik" in key order


@pytest.fixture(scope="module")
def row_choosing_prefill(chip):
    """``(text, scratch bytes)`` of the row-choosing decoder's prefill of
    its one bucket, the dense layer and a sparse one, compiled ONCE for the
    tests that read it."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(attention_ops, "_on_tpu", lambda: True)
        _, model, params, _ = _dsv32_case(chip)
        toks = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=chip)
        lens = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=chip)
        exe = jax.jit(model.prefill_last).lower(params, toks, lens).compile()
    return exe.as_text(), exe.memory_analysis().temp_size_in_bytes


def test_row_choosing_decoder_prefill_attends_in_one_kernel(
        row_choosing_prefill):
    """The cell's one bucket's prefill of the dense layer and a sparse
    one: the gate takes 128 heads of 192 lanes padded to 256, each layer's
    attention under its rows' own masks is ONE ``dsa_prefill_attention``
    call and its index scores ONE ``dsa_index_scores_prefill`` call (both
    with the prompt's length as a scalar-prefetch operand, and no ``[S, H
    D]`` copy more around either for it), no score of 128 heads (or
    product of 64 index heads) against the bucket's keys is a float32
    result of any instruction, no softmax became a ``reduce-window``, and
    two layers' scratch is under the 3.5 GB reckoned for the bucket."""
    from paddle_tpu.ops.pallas_kernels import dsa_prefill

    assert dsa_prefill.dsa_prefill_gate(128, 256, 128, 8192, 1) is None
    assert "whole 128-lane" in dsa_prefill.dsa_prefill_gate(128, 192, 128,
                                                            8192, 1)
    text, scratch = row_choosing_prefill
    assert scratch < 3.5e9
    kernels = [ln.strip().split(" = ")[0] for ln in text.split("\n")
               if "tpu_custom_call" in ln]
    assert sum(k.startswith("%dsa_prefill_attention") for k in kernels) == 2
    assert sum(k.startswith("%dsa_index_scores_prefill")
               for k in kernels) == 2
    instructions = list(_instructions(text))
    assert [rtype for _, rtype, op, _ in instructions
            if op == "reduce-window" and not rtype.startswith("s32")] == []
    # neither the 128 heads' scores nor the 64 index heads' products
    scores = re.compile(r"f32\[(\d+,)*(128|64),\d+,8192\]")
    assert [rtype for _, rtype, _, _ in instructions
            if scores.search(rtype)] == []
    # the attention's result is read as it lies and of its operands only q
    # and k come through a copy (the compiler's turn of its own products,
    # as before the scalar); the scores' call has none on either side
    for call, feeding, after in _copies_around(
            instructions, "dsa_prefill_attention", 8192):
        assert len(feeding) <= 2 and after == [], (call, feeding, after)
    for call, feeding, after in _copies_around(
            instructions, "dsa_index_scores_prefill", 8192):
        assert feeding == [] and after == [], (call, feeding, after)


def test_row_choosing_decoder_prefill_chooses_under_a_conditional(
        row_choosing_prefill):
    """The mask's loop over query blocks holds a ``conditional`` a layer,
    inside the loop's body: its branches are a block past the prompt
    (zeros), a block under ``index_topk`` (the causal triangle) and the
    selection, whose bisections (``dsa_select``'s two loops) lie in a
    branch and nowhere else in the loop; and nothing a branch reaches
    sorts."""
    text, _ = row_choosing_prefill
    homes, reached = _choices_inside_loops(text)
    assert len(homes) == 2                      # one a layer
    assert not any("dsa_select" in body.replace(
        "branch_2_fun/attn/dsa_select", "") for body in homes.values())
    assert sum("dsa_select/while" in body for body in reached.values()) >= 2
    assert not any(" sort(" in body for body in reached.values())


# -- the hybrid of one-part layers (Nemotron-3-Nano as one chip of EP2) ---------

NEMOTRON_SERVE = dict(slots=128, page_size=16, max_seq=16384, pages=81920)


def test_ungated_expert_stream_kernel_at_the_published_width(chip):
    """768 rows over 64 held experts of ``[2688, 1856]`` and ``[1856,
    2688]`` bfloat16, no gate: the kernel's gate takes the width that is
    14.5 lane tiles, and the compiled call names the experts' TWO operands
    (what the grid's readers tell an expert operation by) and no third."""
    from paddle_tpu.ops import moe_ops
    from paddle_tpu.ops.pallas_kernels import expert_stream as es

    m, e, d, f = 768, 64, 2688, 1856
    assert es.expert_stream_gate(m, e, d, f, jnp.bfloat16,
                                 gated=False) is None
    bf16 = jnp.bfloat16
    text = compiled_text(
        chip, lambda xs, wu, wd, sizes: es.expert_stream_ffn(
            xs, None, wu, wd, sizes, moe_ops.relu2, transposed_up=True),
        ((m, d), bf16), ((e, f, d), bf16), ((e, f, d), bf16),
        ((e,), jnp.int32))
    kernel, = [ln for ln in text.split("\n") if "tpu_custom_call" in ln]
    assert "%ragged_dot_stream" in kernel.split(" = ")[0]
    # W_up transposed and W_down, both [E, f, d] with the hidden size in
    # the lanes, read as the parameters lie: no copy of either in front
    assert kernel.split(" custom-call(")[1].split(")")[0].count("%w") == 2
    assert not [rtype for _, rtype, op, _ in _instructions(text)
                if op in ("copy", "transpose") and "64,1856,2688" in rtype]


def test_ssd_kernels_at_the_64_lane_geometry(chip, monkeypatch):
    """64 heads of ``[128, 64]`` in 8 groups: the pool keeps 128 slots x 4
    layers as ``[4, 128, 32, 128, 128]`` float32 (2 MiB a slot a layer),
    the step kernel takes a whole slot a grid step with the buffer aliased
    in and out and untouched outside the kernel, and the chunk scan of a
    2,048-row bucket is ONE kernel call."""
    from paddle_tpu.ops.pallas_kernels import ssd

    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    assert ssd.ssd_state_step_gate(64, 128, 64, 8) is None
    assert ssd.ssd_chunk_scan_gate(64, 8, 128, 64) is None
    assert ssd._step_blocks(64, 8, 128, 64) == (2, 4, 8)
    f32 = jnp.float32
    shape = (4, 128) + ssd.state_shape(64, 128, 64, 8)
    assert shape == (4, 128, 32, 128, 128)
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in (
        (shape, f32), ((128, 64, 64), f32), ((128, 8, 128), f32),
        ((128, 8, 128), f32), ((128, 64), f32), ((128,), jnp.bool_))]
    text = jax.jit(
        lambda s, x, b, c, a, live: ssd.ssd_state_step(s, 2, x, b, c, a,
                                                       live),
        donate_argnums=(0,)).lower(*args).compile().as_text()
    kernel, = [ln for ln in text.split("\n") if "tpu_custom_call" in ln]
    assert kernel.strip().startswith("%ssd_state_step")
    assert "f32[4,128,32,128,128]" in kernel.split(" custom-call(")[0]
    assert [op for _, rtype, op, _ in _instructions(text)
            if _has_dim(rtype, 4) and "32,128,128" in rtype
            and op not in ("parameter", "custom-call", "get-tuple-element",
                           "tuple")] == []
    assert re.search(r"\(0, \{\}, (?:may|must)-alias\)",
                     text.split("\n", 1)[0])
    text = compiled_text(
        chip, lambda x, b, c, a: ssd.ssd_chunk_scan(x, b, c, a),
        ((2048, 64, 64), f32), ((2048, 8, 128), f32), ((2048, 8, 128), f32),
        ((2048, 64), f32))
    kernel, = [ln for ln in text.split("\n") if "tpu_custom_call" in ln]
    assert kernel.strip().startswith("%ssd_chunk_scan")


def _nemotron_case(chip, pattern="ME*"):
    """Nemotron-3-Nano at its published widths as one chip of the EP2 pair
    holds it (64 of 128 experts, 65,536 rows of the vocabulary), a layer of
    each kind, over the cell's pool and 128 slots' states: ``(model,
    params, ops, cache)`` as shapes on the described chip."""
    import json

    from grid.drivers import serve_nemotron
    from paddle_tpu.models import nemotron3 as nm
    from paddle_tpu.serving.kv_cache import CacheGroup, PagedKVCache

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "grid", "configs",
                           "nemotron-3-nano-ep2-serve.json")) as f:
        cfg = serve_nemotron.model_config(dict(
            json.load(f), hybrid_override_pattern=pattern,
            num_hidden_layers=len(pattern)))
    model = nm.Nemotron3LM(cfg, params={})

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    params = jax.tree_util.tree_map(
        sds, jax.eval_shape(lambda: nm.init_params(cfg, 0)))
    g = NEMOTRON_SERVE
    groups = [CacheGroup(name, layers, window,
                         g["pages"] if kind == "kv" else 0, kind)
              for name, layers, window, kind in cfg.cache_groups]
    ops = PagedKVCache(cfg.n_layer, 2, 128, g["slots"], g["max_seq"],
                       g["page_size"], g["pages"], dtype="bfloat16",
                       groups=groups, q_per_kv=16,
                       slot_state=cfg.slot_state,
                       recurrence=cfg.state_recurrence)
    cache = jax.tree_util.tree_map(sds, jax.eval_shape(ops.init_state))
    return model, params, ops, cache


def test_one_part_layers_decode_step(chip, monkeypatch):
    """A decode step of an ``M``, an ``E`` and a ``*`` layer at 128 slots:
    ONE state-step kernel over the packed float32 buffer, ONE fused
    ungated expert kernel over the 768-row pass (no ``ragged-dot`` of the
    compiler's left), ONE paged kernel at 16 query heads a KV head (its
    result ``[128, 16, 256]``); the pool and the states are neither
    copied nor turned, and each is aliased from input to output."""
    monkeypatch.setattr(attention_ops, "paged_kernel_mode",
                        lambda: "compiled")
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    model, params, ops, cache = _nemotron_case(chip)
    assert ops.kernel_mode() == ("compiled", None)
    assert ops.state_kernel_mode() == ("compiled", None)
    assert sorted(cache) == ["k", "pt", "s.ssm", "tail.ssm", "v"]
    assert cache["s.ssm"].shape == (1, 128, 32, 128, 128)
    ints = jax.ShapeDtypeStruct((128,), jnp.int32, sharding=chip)
    flags = jax.ShapeDtypeStruct((128,), jnp.bool_, sharding=chip)

    def chunk(params, cache, lengths, tokens, active):
        logits, cache, stats = model.decode(params, cache, ops, tokens,
                                            lengths, active)
        return cache, jnp.argmax(logits, -1), stats

    text = jax.jit(chunk, donate_argnums=(1,)).lower(
        params, cache, ints, ints, flags).compile().as_text()
    kernels = [ln.strip().split(" custom-call(")[0]
               for ln in text.split("\n") if "tpu_custom_call" in ln]
    steps = [k for k in kernels if k.startswith("%ssd_state_step")]
    paged = [k for k in kernels if k.startswith("%paged_attention")]
    stream = [k for k in kernels if k.startswith("%ragged_dot_stream")]
    assert len(steps) == len(paged) == len(stream) == 1
    assert "f32[1,128,32,128,128]" in steps[0]
    assert "bf16[128,16,256]" in paged[0]
    assert "bf16[768,2688]" in stream[0]
    assert not re.findall(r"= \S+ custom-call\([^\n]*ragged-dot", text)
    instructions = list(_instructions(text))
    types = {name: rtype for name, rtype, _, _ in instructions}
    rows = NEMOTRON_SERVE["pages"] * 16
    moved = [(op, rtype) for _, rtype, op, operands in instructions
             if op in ("copy", "copy-start", "slice", "dynamic-slice",
                       "transpose")
             and any(_has_dim(t, rows) or "32,128,128" in t
                     for t in [rtype] + [types.get(o, "") for o in operands])]
    assert moved == [], moved
    n_params = len(jax.tree_util.tree_leaves(params))
    aliased = {int(p) for p in re.findall(
        r"\((\d+), \{\}, (?:may|must)-alias\)", text.split("\n", 1)[0])}
    # "k" "pt" "s.ssm" "tail.ssm" "v" in key order
    assert {n_params, n_params + 2, n_params + 3, n_params + 4} <= aliased


def test_one_part_layers_prefill(chip, monkeypatch):
    """The 2,048-row bucket's prefill of a layer of each kind: the chunk
    scan is ONE ``ssd_chunk_scan`` call, the half share's pass of 7,680
    rows takes the compiler's grouped product twice a pass (two matrices an
    expert) and no stream kernel, causal attention the flash kernel."""
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    model, params, _, _ = _nemotron_case(chip)
    toks = jax.ShapeDtypeStruct((1, 2048), jnp.int32, sharding=chip)
    lens = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=chip)
    text = jax.jit(model.prefill_last).lower(params, toks,
                                             lens).compile().as_text()
    kernels = [ln.strip().split(" = ")[0] for ln in text.split("\n")
               if "tpu_custom_call" in ln]
    assert sum(k.startswith("%ssd_chunk_scan") for k in kernels) == 1
    assert not any(k.startswith("%ragged_dot_stream") for k in kernels)
    assert len(re.findall(r"= \S+ custom-call\([^\n]*ragged-dot", text)) == 2
    assert not [rtype for _, rtype, op, _ in _instructions(text)
                if op == "while" and "f32[64,128,64]" in rtype]
