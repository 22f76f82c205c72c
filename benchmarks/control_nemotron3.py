"""The controls of ``nemotron3-nano-agent-sat``'s comparison: the cell's own
run through ``grid.run`` with ONE thing wrong in the PROGRAM (a kind of
part left out, the experts gated by their own up projection's sign only, a
group mixed up or a precision below the one the configuration states), or
the REFEREE lowered. ``correct`` has to come out false, by one of the
reference's four limits; a control that passes says the comparison does
not see that part of the model.

    python benchmarks/control_nemotron3.py state_bf16 --workload \
        nemotron3-nano-agent-sat --seed 7 --seconds 40 --trace 0

``state_bf16``: the recurrent state rounded to bfloat16's precision after
the prefill's scan and after every decode step (``reduce_precision``: the
chip's compiler elides a pair of converts); it has to fail by the state's
limit. ``ref_fp8`` leaves the program as it is and lowers the REFEREE:
every matrix the float32 reference multiplies by rounded to float8 e4m3,
the nearest precision below the stated bfloat16 (the reading a limit has
to lie under). ``no_mamba``, ``no_moe``, ``no_attn``: that kind of part
adds nothing to the residual. ``relu_not_squared``: the experts'
activation is relu, not relu^2. ``wrong_group``: every SSM head reads
another group's B and C. PERF.md, Findings, PR 65, has each reading.
Everything after the control's name is ``grid.run``'s own command line.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _bf16(x):
    import jax

    return jax.lax.reduce_precision(x, 8, 7)


def state_bf16() -> None:
    from paddle_tpu.ops.pallas_kernels import ssd
    from paddle_tpu.serving.kv_cache import PagedKVCache

    scan, step = ssd.ssd_chunk_scan, PagedKVCache.state_step

    def ssd_chunk_scan(*args, **kw):
        y, s = scan(*args, **kw)
        return y, _bf16(s)

    def state_step(self, state, layer, *inputs_active):
        o, state = step(self, state, layer, *inputs_active)
        gi, li = self._where_state[layer]
        key = self._key(gi, "s")
        return o, {**state,
                   key: state[key].at[li].set(_bf16(state[key][li]))}

    ssd.ssd_chunk_scan = ssd_chunk_scan
    PagedKVCache.state_step = state_step


def _silenced(kind: str) -> None:
    """Layers of ``kind`` add nothing: their weights' last projection is
    called on zeros."""
    import jax.numpy as jnp
    from paddle_tpu.models import nemotron3

    if kind == "M":
        out = nemotron3._ssm_out
        nemotron3._ssm_out = lambda *a: jnp.zeros_like(out(*a))
    elif kind == "E":
        moe = nemotron3._moe

        def silent(cfg, lp, u, row_valid):
            y, stats = moe(cfg, lp, u, row_valid)
            return jnp.zeros_like(y), stats

        nemotron3._moe = silent
    else:
        qkv = nemotron3._qkv

        def blind(cfg, lp, u):
            q, k, v = qkv(cfg, lp, u)
            return q, k, jnp.zeros_like(v)

        nemotron3._qkv = blind


def relu_not_squared() -> None:
    import jax
    from paddle_tpu.ops import moe_ops

    moe_ops.relu2 = jax.nn.relu


def wrong_group() -> None:
    import jax.numpy as jnp
    from paddle_tpu.models import nemotron3

    inputs = nemotron3._ssd_inputs

    def rolled(cfg, lp, conv, dt):
        x, xdt, b, c, a = inputs(cfg, lp, conv, dt)
        return x, xdt, jnp.roll(b, 1, axis=-2), jnp.roll(c, 1, axis=-2), a

    nemotron3._ssd_inputs = rolled


def ref_fp8() -> None:
    import jax.numpy as jnp
    from grid.reference import nemotron3 as reference

    reference._f32 = lambda w: w.astype(jnp.float8_e4m3fn).astype(
        jnp.float32)


CONTROLS = {
    "ref_fp8": ref_fp8, "state_bf16": state_bf16,
    "wrong_group": wrong_group, "relu_not_squared": relu_not_squared,
    "no_mamba": lambda: _silenced("M"), "no_moe": lambda: _silenced("E"),
    "no_attn": lambda: _silenced("*")}


def main(argv) -> int:
    if not argv or argv[0] not in CONTROLS:
        print("usage: control_nemotron3.py {%s} <grid.run's arguments>"
              % "|".join(sorted(CONTROLS)), file=sys.stderr)
        return 2
    CONTROLS[argv[0]]()
    from grid import run

    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
