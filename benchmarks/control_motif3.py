"""The two controls of ``motif3-docreason-sat``'s comparison: the cell's own
run through ``grid.run``, with ONE thing computed at the nearest precision
below the one the configuration states. ``correct`` has to come out false,
by the limit named; a control that passes says the comparison does not see
that part of the model.

    python benchmarks/control_motif3.py maps_bf16 --workload \
        motif3-docreason-sat --seed 7 --seconds 40 --trace 0
    python benchmarks/control_motif3.py pool_fp8 --workload ...

``maps_bf16``: the residual maps (``z``, its products with Phi, the
exponentials and all forty of Sinkhorn's normalisations) and the heads'
lambda at bfloat16's precision (``Motif3Config.maps_dtype``, which nothing
else sets): fails ``STREAM_NORM_LIMIT``. ``pool_fp8``: every latent row
rounded to float8 e4m3 as it is written to either pool, pages and rings:
fails ``MEAN_GAP_LIMIT``. Everything after the control's name is
``grid.run``'s own command line. On the chip a cold run takes about seven
minutes (PERF.md, Findings, PR 43, has both readings).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def maps_bf16() -> None:
    import jax.numpy as jnp
    from grid.drivers import serve_gdla

    stated = serve_gdla.model_config

    def model_config(config):
        cfg = stated(config)
        cfg.maps_dtype = jnp.dtype("bfloat16")
        return cfg

    serve_gdla.model_config = model_config


def pool_fp8() -> None:
    import jax
    from paddle_tpu.serving.kv_cache import LatentPagedCache

    write = LatentPagedCache._write_rows

    def _write_rows(self, state, layer, dest, row_new, _v):
        # reduce_precision: the chip's compiler elides a pair of converts
        low = jax.lax.reduce_precision(row_new.astype("float32"),
                                       exponent_bits=4, mantissa_bits=3)
        return write(self, state, layer, dest, low.astype(row_new.dtype), _v)

    LatentPagedCache._write_rows = _write_rows


CONTROLS = {"maps_bf16": maps_bf16, "pool_fp8": pool_fp8}


def main(argv) -> int:
    if not argv or argv[0] not in CONTROLS:
        print("usage: control_motif3.py {%s} <grid.run's arguments>"
              % "|".join(sorted(CONTROLS)), file=sys.stderr)
        return 2
    CONTROLS[argv[0]]()
    from grid import run

    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
