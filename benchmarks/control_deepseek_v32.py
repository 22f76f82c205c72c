"""The controls of ``dsv32-sparsedoc-sat``'s comparison: the cell's own run
through ``grid.run`` with ONE thing wrong, a selection or a routing that is
not the model's or a precision below the one the configuration states.
``correct`` has to come out false, by a limit named here; a control that
passes says the comparison does not see that part of the model.

    python benchmarks/control_deepseek_v32.py dense --workload \
        dsv32-sparsedoc-sat --seed 7 --seconds 40 --trace 0

``dense``: every row of the context is read (``index_topk`` = ``max_seq``):
the selection check (more rows than ``index_topk`` allows) and
``OVERLAP_LIMIT``. ``newest_2048``: the 2,048 NEWEST rows are read, whatever
they score (a window in place of the choice): ``OVERLAP_LIMIT``,
``MASS_LIMIT``. ``no_group_limit``: the plain 8 largest of 256 ``s + b``
(``n_group`` = ``topk_group`` = 1): ``MEAN_GAP_LIMIT``. ``fp8_rows``: every
latent row rounded to float8 e4m3 before it is kept
(``DeepSeekV32Config.row_dtype``): ``ROW_GAP_LIMIT``. ``bf16_scores``: the
index products and their weighted sum rounded to bfloat16
(``score_dtype``): ``OVERLAP_LIMIT``. PERF.md, Findings, PR 62, has each
reading. Everything after the control's name is ``grid.run``'s own command
line.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _configured(**control):
    from grid.drivers import serve_rowdsa

    stated = serve_rowdsa.model_config
    serve_rowdsa.model_config = lambda config, **kw: stated(
        config, **dict(control, **kw))


def dense() -> None:
    from grid import manifest

    cell = manifest.Cell("dsv32-sparsedoc-sat")
    _configured(index_topk=int(cell.config["engine"]["max_seq"]))


def newest_2048() -> None:
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops

    select = attention_ops.dsa_select_rows

    def dsa_select_rows(scores, topk):
        rows = jnp.arange(scores.shape[1], dtype=jnp.float32)[None, :]
        live = scores > attention_ops.neg_inf(jnp.float32)
        return select(jnp.where(live, rows, scores), topk)

    attention_ops.dsa_select_rows = dsa_select_rows


CONTROLS = {
    "dense": dense, "newest_2048": newest_2048,
    "no_group_limit": lambda: _configured(n_group=1, topk_group=1),
    "fp8_rows": lambda: _configured(row_dtype="float8_e4m3fn"),
    "bf16_scores": lambda: _configured(score_dtype="bfloat16")}


def main(argv) -> int:
    if not argv or argv[0] not in CONTROLS:
        print("usage: control_deepseek_v32.py {%s} <grid.run's arguments>"
              % "|".join(sorted(CONTROLS)), file=sys.stderr)
        return 2
    CONTROLS[argv[0]]()
    from grid import run

    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
