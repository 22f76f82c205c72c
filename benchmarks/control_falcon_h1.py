"""The controls of ``falcon-h1-chat-sat``'s comparison: the cell's own run
through ``grid.run`` with ONE thing wrong in the PROGRAM, a part of the
block left out, a multiplier that is not the published one, a group mixed
up or a precision below the one the configuration states. ``correct`` has
to come out false, by one of the reference's two limits; a control that
passes says the comparison does not see that part of the model.

    python benchmarks/control_falcon_h1.py state_bf16 --workload \
        falcon-h1-chat-sat --seed 7 --seconds 40 --trace 0

``state_bf16``: the recurrent state rounded to bfloat16's precision after
the prefill's scan and after every decode step (``reduce_precision``: the
chip's compiler elides a pair of converts). ``no_attn``, ``no_ssm``: the
attention branch, the SSM branch adds nothing to the residual.
``key_one``, ``ssm_b_one``: ``key_multiplier``, ``ssm_multipliers[2]``
(the B segment's) set to 1. ``wrong_group``: every SSM head reads the
OTHER group's B and C. ``ref_fp8`` leaves the program as it is and lowers
the REFEREE: every matrix the float32 reference multiplies by rounded to
float8 e4m3, the nearest precision below the stated bfloat16 (the reading
a limit has to lie under). PERF.md, Findings, PR 51, has each reading.
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


def _silenced(name: str) -> None:
    import jax.numpy as jnp
    from paddle_tpu.models import falcon_h1

    out = getattr(falcon_h1, name)
    setattr(falcon_h1, name,
            lambda *args: jnp.zeros_like(out(*args)))


def _multiplier(**mup) -> None:
    """The PROGRAM's multipliers replaced; the weights stay the stated
    configuration's (``serve_ssm.build`` seeds them from it)."""
    from grid.drivers import serve_ssm

    build = serve_ssm.build
    serve_ssm.build = lambda job, **kw: build(job, **dict({"mup": mup}, **kw))


def ssm_b_one() -> None:
    from grid import manifest

    seg = list(manifest.Cell("falcon-h1-chat-sat").config["ssm_multipliers"])
    seg[2] = 1.0
    _multiplier(ssm_multipliers=seg)


def wrong_group() -> None:
    from paddle_tpu.models import falcon_h1

    inputs = falcon_h1._ssd_inputs

    def swapped(cfg, lp, conv, dt):
        x, xdt, b, c, a = inputs(cfg, lp, conv, dt)
        return x, xdt, b[..., ::-1, :], c[..., ::-1, :], a

    falcon_h1._ssd_inputs = swapped


def ref_fp8() -> None:
    import jax.numpy as jnp
    from grid.reference import falcon_h1 as reference

    reference._f32 = lambda w: w.astype(jnp.float8_e4m3fn).astype(
        jnp.float32)


CONTROLS = {
    "ref_fp8": ref_fp8,
    "state_bf16": state_bf16, "wrong_group": wrong_group,
    "no_attn": lambda: _silenced("_attn_out"),
    "no_ssm": lambda: _silenced("_ssm_out"),
    "key_one": lambda: _multiplier(key_multiplier=1.0),
    "ssm_b_one": ssm_b_one}


def main(argv) -> int:
    if not argv or argv[0] not in CONTROLS:
        print("usage: control_falcon_h1.py {%s} <grid.run's arguments>"
              % "|".join(sorted(CONTROLS)), file=sys.stderr)
        return 2
    CONTROLS[argv[0]]()
    from grid import run

    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
