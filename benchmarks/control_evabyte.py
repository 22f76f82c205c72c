"""The controls of ``evabyte-file-sat``'s comparison: the cell's own run
through ``grid.run`` with ONE thing wrong in the PROGRAM. ``correct`` has to
come out false, by at least one of the reference's two limits; a control
that passes says the comparison does not see that part of the model.

    python benchmarks/control_evabyte.py no_summaries --workload \
        evabyte-file-sat --seed 7 --seconds 40 --trace 0

``no_summaries``: closed windows dropped with nothing in their place: a
query attends to its own window's exact rows alone (the decode step's
kernel is handed the slot's page table from the open window's first page
on and the exact rows' count; the prefill attends a window at a time).
``uniform_pool``: the pooling weights uniform (``phi`` read as 0: a
chunk's summary is its rows' mean, plus ``mu``). ``no_mu``: ``mu`` left
out of the pooled key. ``bf16``: the summaries pooled and the residual
added in bfloat16 where float32 is stated (``fp32_skip_add``,
``mixedp_attn``): the nearest precision below the stated one. Each patches
the program from here: the weights and the reference stay the stated
configuration's in all four. PERF.md,
Findings, PR 58, has each reading. Everything after the control's name is
``grid.run``'s own command line.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def no_summaries() -> None:
    import jax.numpy as jnp
    from paddle_tpu.models import evabyte
    from paddle_tpu.serving.kv_cache import PagedKVCache

    attend, whole = PagedKVCache.decode_attention, evabyte._prefill_attention

    def exact_rows(self, gi, ctx_len):
        w = self.groups[gi].window
        return jnp.where(ctx_len > 0, (ctx_len - 1) % w + 1, 0)

    def decode_attention(self, state, layer, q, ctx_len, active, **kw):
        gi, _ = self._where[layer]
        key = self._key(gi, "pt")
        pt = state[key]
        skip = (self._summaries(gi) // self.page_size) * (
            jnp.maximum(ctx_len - 1, 0) // self.groups[gi].window)
        at = jnp.minimum(jnp.arange(pt.shape[1])[None, :] + skip[:, None],
                         pt.shape[1] - 1)
        return attend(self, {**state, key: jnp.take_along_axis(
            pt, at, axis=1)}, layer, q, ctx_len, active, **kw)

    def a_window_at_a_time(cfg, q, k, v, ks, vs):
        w = min(q.shape[0], cfg.window)
        return jnp.concatenate([
            whole(cfg, q[i:i + w], k[i:i + w], v[i:i + w], ks[:0], vs[:0])
            for i in range(0, q.shape[0], w)])

    PagedKVCache._group_len = exact_rows
    PagedKVCache.decode_attention = decode_attention
    evabyte._prefill_attention = a_window_at_a_time


def _summarize_with(**zeroed) -> None:
    import jax.numpy as jnp
    from paddle_tpu.models import evabyte

    summarize = evabyte.summarize
    evabyte.summarize = lambda cfg, lp, k, v: summarize(
        cfg, dict(lp, **{name: jnp.zeros_like(lp[name]) for name in zeroed}),
        k, v)


def uniform_pool() -> None:
    _summarize_with(phi=True)


def no_mu() -> None:
    _summarize_with(mu=True)


def bf16() -> None:
    """The PROGRAM's residual and pooling at bfloat16's precision: each
    sublayer's sum back into the residual, and the pooling's operands,
    weights and sums (``reduce_precision``: the chip's compiler elides a
    pair of converts). The weights stay the stated configuration's."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import evabyte
    from paddle_tpu.models.blocks import at_precision

    def low(x):
        return at_precision(x, jnp.bfloat16)

    def summarize(cfg, lp, k, v):
        kf, vf = low(k.astype(jnp.float32)), low(v.astype(jnp.float32))
        logit = jnp.sum(kf * lp["phi"], axis=-1) * cfg.sm_scale
        w = low(jax.nn.softmax(logit, axis=-2))[..., None]
        ks = low(jnp.sum(w * kf, axis=-3)) + lp["mu"]
        vs = jnp.sum(w * vf, axis=-3)
        return low(ks).astype(cfg.dtype), low(vs).astype(cfg.dtype)

    attn_out, mlp = evabyte._attn_out, evabyte._mlp
    evabyte._attn_out = lambda *a: low(attn_out(*a))
    evabyte._mlp = lambda *a: low(mlp(*a))
    evabyte.summarize = summarize


CONTROLS = {"no_summaries": no_summaries, "uniform_pool": uniform_pool,
            "no_mu": no_mu, "bf16": bf16}


def main(argv) -> int:
    if not argv or argv[0] not in CONTROLS:
        print("usage: control_evabyte.py {%s} <grid.run's arguments>"
              % "|".join(sorted(CONTROLS)), file=sys.stderr)
        return 2
    CONTROLS[argv[0]]()
    from grid import run

    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
