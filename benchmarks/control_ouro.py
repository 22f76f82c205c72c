"""The controls of ``ouro-math-sat``'s comparison: the cell's own run
through ``grid.run`` with ONE thing wrong, in the PROGRAM or in what it
keeps. ``correct`` has to come out false, by at least one of the
reference's three limits; a control that passes says the comparison does
not see that part of the model.

    python benchmarks/control_ouro.py shared_cache --workload \
        ouro-math-sat --seed 7 --seconds 40 --trace 0

``shared_cache``: the four steps share ONE cache layer a layer of weights
(the cache maps every step to the layer's first cache layer: every step
writes the layer's one row a position and attends over what is there, the
last step's at every earlier position: the family's last-step reuse, which
would be a quarter of the pool's bytes and is a different result).
``three_steps``: the loop runs three steps instead of four (the weights
and the reference stay the stated four's). ``pool_fp8``: every K
and V row rounded to float8 e4m3's precision as it is written to the pool,
the nearest precision below the stated bfloat16 (``reduce_precision``: the
chip's compiler elides a pair of converts). ``ref_fp8`` leaves the program
as it is and lowers the REFEREE: every matrix the float32 reference
multiplies by rounded to float8 e4m3 (the reading a limit has to lie
under). PERF.md, Findings, PR 56, has each reading. Everything after the
control's name is ``grid.run``'s own command line.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def three_steps() -> None:
    """The PROGRAM's loop shortened; the weights stay the stated
    configuration's (``serve_loop.build`` seeds them from it)."""
    from grid.drivers import serve_loop

    build = serve_loop.build
    serve_loop.build = lambda job, **kw: build(job, **dict({"ut_steps": 3},
                                                           **kw))


def shared_cache() -> None:
    from paddle_tpu.serving.kv_cache import PagedKVCache

    PagedKVCache._pool_layer = \
        lambda self, li, step: li * self.cache_steps


def pool_fp8() -> None:
    import jax.numpy as jnp
    from paddle_tpu.models.blocks import at_precision
    from paddle_tpu.serving.kv_cache import PagedKVCache

    write = PagedKVCache._write_rows

    def _write_rows(self, state, layer, dest, k_new, v_new, step=None):
        return write(self, state, layer, dest,
                     at_precision(k_new, jnp.float8_e4m3fn),
                     at_precision(v_new, jnp.float8_e4m3fn), step)

    PagedKVCache._write_rows = _write_rows


def ref_fp8() -> None:
    import jax.numpy as jnp
    from grid.reference import ouro as reference

    reference._f32 = lambda w: w.astype(jnp.float8_e4m3fn).astype(
        jnp.float32)


CONTROLS = {
    "shared_cache": shared_cache, "three_steps": three_steps,
    "pool_fp8": pool_fp8, "ref_fp8": ref_fp8}


def main(argv) -> int:
    if not argv or argv[0] not in CONTROLS:
        print("usage: control_ouro.py {%s} <grid.run's arguments>"
              % "|".join(sorted(CONTROLS)), file=sys.stderr)
        return 2
    CONTROLS[argv[0]]()
    from grid import run

    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
