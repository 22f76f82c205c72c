"""Pipeline parallelism over a mesh axis (SURVEY §2 component: the
reference's pipeline trainer — paddle/fluid/framework/device_worker
section-program pipeline; reimagined TPU-first).

Design (the collective-pipelining recipe from the public scaling
literature): stages are laid out along a ``pipe`` mesh axis; a GPipe
schedule runs M microbatches through S stages in M+S-1 ticks, rotating
activations between neighbouring stages with ``lax.ppermute`` over ICI.
The tick loop is unrolled at trace time so the feed/collect permutes have
static source/destination pairs. The whole schedule — including the
bubble — is one compiled XLA computation, and the *backward* pipeline
schedule falls out of JAX AD transposing the permutes, so there is no
hand-written 1F1B scheduler.

Memory layout (the point of pipeline parallelism):
  - stage params: stacked [S, ...], sharded over ``pipe`` — each device
    holds only its own stage's weights;
  - microbatches [M, mb, ...]: sharded over ``pipe`` on the M axis — each
    device stores M/S microbatches, feeding stage 0 one microbatch per
    tick via a single-pair ppermute (an mb-sized ICI hop);
  - outputs: collected back to the same [M/S per device] layout; at no
    tick does any device hold more than its input slab + one in-flight
    microbatch activation.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..monitor.device import record_collective as _record_collective

__all__ = ["gpipe", "pipeline_step", "stack_stage_params"]


def stack_stage_params(per_stage_params):
    """[pytree per stage] → single pytree with leading stage axis [S, ...]."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)


def gpipe(stage_fn: Callable, mesh: Mesh, axis: str = "pipe"):
    """Build a pipelined forward: ``fn(stacked_params, microbatches)``.

    - ``stage_fn(params, x) -> y`` — one stage; activations must keep one
      shape across stages (standard for transformer blocks).
    - ``stacked_params``: leading [S] axis (see stack_stage_params).
    - ``microbatches``: [M, mb, ...] with M divisible by the pipe size —
      sharded over the pipe axis (a replicated array is resharded by GSPMD
      on entry).

    Returns outputs [M, mb, ...], sharded over the pipe axis on the M dim.
    Differentiable.

    Compile-time note: the tick loop is unrolled at trace time (static
    ppermute pairs are what let the feed/collect hops be single-pair ICI
    sends), so the traced graph holds M+S-1 copies of ``stage_fn`` forward
    — and its AD transpose again in the backward. Compile time and HLO
    size scale linearly with microbatch count; past a few dozen
    microbatches prefer fewer, larger microbatches (the bubble fraction
    (S-1)/(M+S-1) has diminishing returns in M anyway). A warning fires at
    trace time beyond ~64 ticks.
    """
    s = mesh.shape[axis]

    def shard_body(params, x_loc):
        # params: this device's stage slice, leading dim 1 — drop it.
        # x_loc: [M/S, mb, ...] — this device's slab of microbatches.
        params = jax.tree.map(lambda p: p[0], params)
        idx = jax.lax.axis_index(axis)
        mloc = x_loc.shape[0]
        m = mloc * s
        ticks = m + s - 1
        if ticks > 64:
            import warnings

            warnings.warn(
                "gpipe: %d microbatches over %d stages unrolls %d copies of "
                "stage_fn into the traced graph (plus transposes in the "
                "backward) — expect slow compiles; prefer fewer, larger "
                "microbatches" % (m, s, ticks), stacklevel=3)
        out = jnp.zeros_like(x_loc)
        recv = jnp.zeros_like(x_loc[0])
        fwd_perm = [(i, i + 1) for i in range(s - 1)]

        # Unrolled schedule: tick t processes microbatch t-stage on each
        # stage. Static t makes the feed/collect ppermute pairs static.
        for t in range(ticks):
            if t < m:
                owner, loc = divmod(t, mloc)
                feed = x_loc[loc]
                if owner != 0:
                    # owner ships microbatch t to stage 0 (mb-sized ICI hop)
                    _record_collective("ppermute", axis, feed)
                    feed = jax.lax.ppermute(feed, axis, [(owner, 0)])
            else:
                feed = jnp.zeros_like(recv)
            inp = jnp.where(idx == 0, feed, recv)
            y = stage_fn(params, inp)
            mb_idx = t - idx
            active = (mb_idx >= 0) & (mb_idx < m)
            y = jnp.where(active, y, jnp.zeros_like(y))
            done = t - (s - 1)  # microbatch finishing at the last stage
            if done >= 0:
                owner_out, loc_out = divmod(done, mloc)
                w = y
                if owner_out != s - 1:
                    _record_collective("ppermute", axis, w)
                    w = jax.lax.ppermute(w, axis, [(s - 1, owner_out)])
                out = out.at[loc_out].set(
                    jnp.where(idx == owner_out, w, out[loc_out]))
            if t < ticks - 1:
                # the unrolled tick loop traces each hop separately, so the
                # collectives/ppermute counters sum to the true per-step total
                _record_collective("ppermute", axis, y)
                recv = jax.lax.ppermute(y, axis, fwd_perm)
        return out

    def fn(stacked_params, microbatches):
        m = microbatches.shape[0]
        mpad = -(-m // s) * s
        if mpad != m:  # ragged M: zero microbatches ride the bubble, sliced off
            pad = [(0, mpad - m)] + [(0, 0)] * (microbatches.ndim - 1)
            microbatches = jnp.pad(microbatches, pad)
        in_specs = (
            jax.tree.map(lambda _: P(axis), stacked_params),
            P(axis),  # microbatch slabs live with their owner stage
        )
        out = jax.shard_map(
            shard_body, mesh=mesh, in_specs=in_specs, out_specs=P(axis),
            check_vma=False,
        )(stacked_params, microbatches)
        return out[:m] if mpad != m else out

    return fn


def pipeline_step(stage_fn: Callable, loss_fn: Callable, mesh: Mesh,
                  axis: str = "pipe"):
    """Training-step builder: returns ``step(stacked_params, microbatches,
    labels_mb) -> (loss, grads)`` with the full fwd+bwd pipeline compiled as
    one XLA program."""
    fwd = gpipe(stage_fn, mesh, axis)

    def step(stacked_params, microbatches, labels_mb):
        def total_loss(p):
            outs = fwd(p, microbatches)
            return loss_fn(outs, labels_mb)

        return jax.value_and_grad(total_loss)(stacked_params)

    return step
