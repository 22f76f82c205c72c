"""``grid/sweep.py`` (how a serve cell's rate is found, README: The
benchmark) under tier-1: ``grid/tests/test_sweep.py``'s cases as they are,
collected here because tier-1 collects ``tests/`` only. The sweep goes
through the driver of every serve kind (``serve``, ``serve_moe``,
``serve_mla``, ``serve_mixed_gqa``: one case a driver) at toy widths on the
CPU, a backlog window that was not full raises the rate, and at the queue's
ceiling the sweep fails instead of printing a capacity. The fixtures are
the grid's own (``grid/tests/conftest.py`` and the three sparse cells' test
files), imported under their names so that pytest finds them."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402

from grid.tests.conftest import _rewrite  # noqa: E402
from grid.tests.conftest import toy_root as grid_toy_root  # noqa: E402,F401
from grid.tests.test_sweep import (  # noqa: E402,F401
    gqa_root, mla_root, moe_root,
    test_a_train_cell_has_no_rate_to_find,
    test_a_window_that_was_not_full_raises_the_rate,
    test_at_the_queues_ceiling_it_fails_and_prints_no_capacity,
    test_main_fails_where_the_server_was_never_full,
    test_sweep_goes_through_the_driver_of_the_kind,
    test_the_first_rate_keeps_inside_the_queue)


@pytest.fixture
def toy_root(grid_toy_root):
    """The grid's toy checkout with the GPT-2 toy cut to ONE slot. The
    ``serve`` case offers 400 requests/s as "far above a toy engine on the
    CPU"; since an admission is one device program, four toy slots serve
    about 450/s here, the first backlog window holds no queue, and the
    sweep (rightly) runs a second one that the case does not expect. One
    slot serves a quarter of that. ``grid/tests/test_sweep.py`` is the
    benchmark's file and stays as it is."""
    _rewrite(os.path.join(grid_toy_root, "grid", "configs",
                          "gpt2-small-serve.json"),
             lambda doc: doc["engine"].update(slots=1))
    return grid_toy_root
