"""The controls of ``glm53-flash-longdoc-sat``'s comparison: the cell's own
run through ``grid.run`` with ONE thing wrong, a selection that is not the
model's or a precision below the one the configuration states. ``correct``
has to come out false, by a limit named here; a control that passes says
the comparison does not see that part of the model.

    python benchmarks/control_glm5_flash.py dense --workload \
        glm53-flash-longdoc-sat --seed 7 --seconds 40 --trace 0

``dense``: every closed block is read (``index_topk`` = ``max_seq``): the
selection check (more blocks than ``index_topk`` allows) and the rank
limits. ``newest``: the 511 NEWEST closed blocks are read, whatever they
score: ``OVERLAP_LIMIT``, ``MASS_LIMIT``. ``wrong_pool``: a prompt's index
keys pooled over rows 4b-2..4b+1 in place of 4b..4b+3: the same two.
``rows_fp8``, ``index_fp8``: every latent row, every index key (raw and
pooled) rounded to float8 e4m3 before it is kept; ``maps_bf16``: the
residual maps at bfloat16's precision (``Glm5FlashConfig.row_dtype``,
``index_dtype``, ``maps_dtype``, which nothing else sets): one limit at
least (PERF.md, Findings, PR 47, has each reading). Everything after the
control's name is ``grid.run``'s own command line.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _configured(**control):
    from grid.drivers import serve_dsa

    stated = serve_dsa.model_config
    serve_dsa.model_config = lambda config, **kw: stated(
        config, **dict(control, **kw))


def dense() -> None:
    from grid import manifest

    cell = manifest.Cell("glm53-flash-longdoc-sat")
    _configured(index_topk=int(cell.config["engine"]["max_seq"]))


def newest() -> None:
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops

    select = attention_ops.dsa_select

    def dsa_select(scores, own_block, top_blocks):
        blocks = jnp.arange(scores.shape[1], dtype=jnp.float32)[None, :]
        closed = scores > attention_ops.neg_inf(jnp.float32)
        return select(jnp.where(closed, blocks, scores), own_block,
                      top_blocks)

    attention_ops.dsa_select = dsa_select


def wrong_pool() -> None:
    import jax.numpy as jnp
    from paddle_tpu.models import glm5_flash

    pooled = glm5_flash._pooled_keys
    glm5_flash._pooled_keys = lambda cfg, k_idx: pooled(
        cfg, jnp.roll(k_idx, 2, axis=0))


CONTROLS = {
    "dense": dense, "newest": newest, "wrong_pool": wrong_pool,
    "rows_fp8": lambda: _configured(row_dtype="float8_e4m3fn"),
    "index_fp8": lambda: _configured(index_dtype="float8_e4m3fn"),
    "maps_bf16": lambda: _configured(maps_dtype="bfloat16")}


def main(argv) -> int:
    if not argv or argv[0] not in CONTROLS:
        print("usage: control_glm5_flash.py {%s} <grid.run's arguments>"
              % "|".join(sorted(CONTROLS)), file=sys.stderr)
        return 2
    CONTROLS[argv[0]]()
    from grid import run

    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
