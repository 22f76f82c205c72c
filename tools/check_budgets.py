"""Assert measured collective traffic against the checked-in budgets.

The enforcement face of ``paddle_tpu.monitor.budgets``: drives the three
explicitly-accounted collective legs — the gpipe ppermute schedule, the
ring-attention K/V rotation (forward AND backward, accumulators included)
and the CTR sparse-row all_to_all exchange — on an 8-device virtual CPU
mesh, reads the ``collectives/*`` counters they record at trace time, and
asserts each against its closed-form bytes-per-step budget.

    python -m tools.check_budgets --selftest
        <5s, no TPU: run all legs, assert measured == budget exactly
        (trace-time accounting is shape math — any drift is a regression),
        and prove a deliberately tightened budget fails loudly. The
        ROADMAP smoke gate closing item 4's "collective-traffic budgets"
        residue.

    python -m tools.check_budgets --table
        Print the budget table (legs, counters, closed forms).

``dryrun_multichip`` runs the same asserts inline against its own legs, so
the MULTICHIP JSON's collective volumes are budget-checked, not just
printed.
"""

from __future__ import annotations

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

N_DEV = 8


def _ensure_virtual_devices(n: int = N_DEV) -> None:
    """Force an n-device virtual CPU platform — must run BEFORE any jax
    backend initializes (XLA parses XLA_FLAGS once per process). An
    existing smaller device-count flag is REPLACED, not kept — keeping it
    would leave the selftest under-provisioned."""
    import re

    flags = os.environ.get("XLA_FLAGS", "")
    want = "--xla_force_host_platform_device_count=%d" % n
    if "xla_force_host_platform_device_count" in flags:
        flags = re.sub(r"--?xla_force_host_platform_device_count=\d+",
                       want, flags)
    else:
        flags += " " + want
    os.environ["XLA_FLAGS"] = flags.strip()
    os.environ["JAX_PLATFORMS"] = "cpu"


def _coll_bytes(op: str) -> int:
    from paddle_tpu.monitor import metrics as mx

    snap = mx.snapshot().get("collectives/%s/bytes" % op)
    return int(snap["value"]) if snap else 0


def run_gpipe_leg() -> dict:
    """Trace one gpipe training step (4 stages × 4 microbatches) and
    check the forward ppermute schedule against gpipe.fwd."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from paddle_tpu.monitor import budgets
    from paddle_tpu.parallel import pipeline_step, stack_stage_params

    s, mb, d_model = 4, 2, 16
    m = 4
    mesh = Mesh(np.array(jax.devices()[:s]), ("pipe",))
    rng = np.random.RandomState(0)

    def stage(params, x):
        w, b = params
        return jnp.tanh(x @ w + b)

    stages = [(jnp.asarray(rng.randn(d_model, d_model).astype("float32") * .3),
               jnp.zeros((d_model,), jnp.float32)) for _ in range(s)]
    stacked = stack_stage_params(stages)
    xs = jnp.asarray(rng.randn(m, mb, d_model).astype("float32"))
    ys = jnp.asarray(rng.randn(m, mb, d_model).astype("float32") * .1)
    step = jax.jit(pipeline_step(stage, lambda o, l: jnp.mean((o - l) ** 2),
                                 mesh, "pipe"))
    before = _coll_bytes("ppermute")
    loss, _ = step(stacked, xs, ys)
    assert np.isfinite(float(loss))
    measured = _coll_bytes("ppermute") - before
    act_bytes = mb * d_model * 4
    return budgets.check_budget("gpipe.fwd", measured,
                                microbatches=m, stages=s,
                                activation_bytes=act_bytes)


def run_ring_attention_leg() -> dict:
    """Forward-only then fwd+bwd ring attention; check fwd and bwd
    rotation volumes (f32 dK/dV accumulators included)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from paddle_tpu.monitor import budgets
    from paddle_tpu.parallel import ring_attention

    sp, b, h, s_loc, d = 4, 2, 2, 8, 8
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(b, h, s_loc * sp, d).astype("float32"))
    k, v = q + 0.1, q + 0.2
    block_elems = b * h * s_loc * d
    block_bytes = block_elems * 4

    before = _coll_bytes("ppermute")
    with mesh:
        out = ring_attention(q, k, v, mesh=mesh, axis_name="sp")
    assert np.isfinite(np.asarray(out)).all()
    fwd_rec = budgets.check_budget(
        "ring_attention.fwd", _coll_bytes("ppermute") - before,
        n_devices=sp, block_bytes=block_bytes)

    before = _coll_bytes("ppermute")
    with mesh:
        g = jax.grad(
            lambda q_, k_, v_: ring_attention(
                q_, k_, v_, mesh=mesh, axis_name="sp").sum())(q, k, v)
    assert np.isfinite(np.asarray(g)).all()
    # grad traces the custom-vjp fwd AND bwd: the measured delta covers both
    fwd_plus_bwd = _coll_bytes("ppermute") - before
    bwd_budget = budgets.budget_bytes("ring_attention.bwd", n_devices=sp,
                                      block_bytes=block_bytes,
                                      block_elems=block_elems)
    bwd_rec = budgets.check_budget(
        "ring_attention.bwd", fwd_plus_bwd - fwd_rec["budget_bytes"],
        n_devices=sp, block_bytes=block_bytes, block_elems=block_elems)
    assert bwd_rec["budget_bytes"] == bwd_budget
    return {"fwd": fwd_rec, "bwd": bwd_rec}


def run_ctr_routing_leg() -> dict:
    """One route_rows_to_shards exchange over the full 8-device axis."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.core.sparse import route_rows_to_shards
    from paddle_tpu.monitor import budgets

    n_shards, n_loc, dim = N_DEV, 16, 8
    V = 1024
    mesh = Mesh(np.array(jax.devices()[:n_shards]), ("model",))
    rng = np.random.RandomState(2)
    ids = jnp.asarray(rng.randint(0, V, n_shards * n_loc).astype("int32"))
    rows = jnp.asarray(
        rng.randn(n_shards * n_loc, dim).astype("float32"))

    def body(ids_loc, rows_loc):
        return route_rows_to_shards(ids_loc, rows_loc, n_shards,
                                    V // n_shards, "model", V)

    before = _coll_bytes("all_to_all")
    rid, rrows = jax.shard_map(
        body, mesh=mesh, in_specs=(P("model"), P("model", None)),
        out_specs=(P("model"), P("model", None)),
        check_vma=False)(ids, rows)
    assert np.asarray(rid).shape[0] == n_shards * n_shards * n_loc
    measured = _coll_bytes("all_to_all") - before
    return budgets.check_budget("ctr.row_routing", measured,
                                n_shards=n_shards, n_local=n_loc, dim=dim,
                                id_itemsize=4, row_itemsize=4)


def selftest() -> int:
    import time

    t0 = time.time()
    import jax

    if len(jax.devices()) < N_DEV:
        # backend initialized too small in-process: re-exec clean. The
        # child env gets the count flag force-replaced; the marker makes a
        # still-too-small child FAIL instead of recursing forever.
        if os.environ.get("_PADDLE_TPU_CHECK_BUDGETS_CHILD"):
            print("check_budgets: child still sees %d < %d devices — "
                  "XLA_FLAGS not honored; aborting"
                  % (len(jax.devices()), N_DEV), file=sys.stderr)
            return 1
        import subprocess

        env = dict(os.environ)
        env["_PADDLE_TPU_CHECK_BUDGETS_CHILD"] = "1"
        r = subprocess.run([sys.executable, "-m", "tools.check_budgets",
                            "--selftest"], env=env, cwd=_REPO)
        return r.returncode

    from paddle_tpu.monitor import budgets

    records = {
        "gpipe.fwd": run_gpipe_leg(),
        "ring_attention": run_ring_attention_leg(),
        "ctr.row_routing": run_ctr_routing_leg(),
    }
    flat = [records["gpipe.fwd"], records["ring_attention"]["fwd"],
            records["ring_attention"]["bwd"], records["ctr.row_routing"]]
    for rec in flat:
        # trace-time accounting is pure shape math: anything but EXACT
        # equality means an emission site or budget formula drifted
        assert rec["measured_bytes"] == rec["budget_bytes"], rec
        print("budget OK  %-20s %8d B == budget (%s)"
              % (rec["leg"], rec["measured_bytes"], rec["counter"]))

    # a deliberately tightened budget must fail LOUDLY, naming the leg
    rec = records["ctr.row_routing"]
    try:
        budgets.check_budget("ctr.row_routing", rec["measured_bytes"],
                             budget=rec["budget_bytes"] - 1)
        raise AssertionError("tightened budget did not trip")
    except budgets.CollectiveBudgetExceeded as e:
        assert "ctr.row_routing" in str(e), e
    print("check_budgets selftest: OK (%.1fs)" % (time.time() - t0))
    return 0


def print_table() -> int:
    from paddle_tpu.monitor.budgets import COLLECTIVE_BUDGETS

    for leg in sorted(COLLECTIVE_BUDGETS):
        spec = COLLECTIVE_BUDGETS[leg]
        print("%-20s %-32s params=%s\n  %s"
              % (leg, spec["counter"], ",".join(spec["params"]), spec["doc"]))
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__.strip())
        return 0 if argv else 2
    if argv[0] == "--table":
        return print_table()
    if argv[0] == "--selftest":
        _ensure_virtual_devices()
        return selftest()
    print(__doc__.strip(), file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main())
