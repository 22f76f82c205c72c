"""Subprocess entry for the multi-process distributed test
(the role of the reference's dist_mnist.py run under test_dist_base.py).

Each process joins the jax.distributed cluster, builds the same program,
and trains over the GLOBAL mesh spanning all processes — the TPU-native
analog of the reference's multi-trainer NCCL2 mode.

Modes (DIST_MODE env):
  dp     — pure data parallel over a 1-axis mesh (default)
  dp_tp  — 2-D mesh {'data': n, 'model': 2} with column+row-parallel FC,
           composing data parallelism ACROSS processes with tensor
           parallelism (the reference has no TP at all; SURVEY §2.3).
  crash  — the multi-process CRASH DRILL: every rank trains the same
           replicated program independently (no cross-process collectives
           — the CPU backend cannot run them, and the drill's subject is
           the failure-handling fabric, not the math), coordinated through
           heartbeat/done marker files. Rank DIST_KILL_RANK SIGKILLs
           itself before step DIST_KILL_AT_STEP (a hard preemption);
           surviving ranks detect the lost peer at the end-of-run barrier
           (stale heartbeat, no done marker) and exit EXIT_PEER_LOST=43
           with a DIST_PEER_LOST diagnostic instead of hanging. Rank 0
           writes a rotating checkpoint after every step (DIST_CKPT_DIR);
           a restart-all with the same dir resumes from the last published
           serial, and per-step DIST_STEP:<rank>:<step>:<loss-hex> lines
           let the parent assert bit-exact loss parity with an
           uninterrupted run.

The task is learnable by construction: a fixed batch whose labels come from
a fixed random linear teacher, trained repeatedly — so the loss-decrease
assertion in the parent test is satisfiable (unlike round 1's fresh random
noise per step).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")

import jax
import numpy as np


def make_batch(batch=8, dim=8, classes=4, seed=7):
    """Fixed learnable batch: labels from a fixed linear teacher of x."""
    rng = np.random.RandomState(seed)
    xs = rng.randn(batch, dim).astype("float32")
    teacher = rng.randn(dim, classes).astype("float32")
    ys = np.argmax(xs @ teacher, axis=1).astype("int64")[:, None]
    return xs, ys


EXIT_PEER_LOST = 43


def _build_crash_model(fluid):
    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = startup.random_seed = 11
    with fluid.program_guard(main_prog, startup):
        x = fluid.layers.data("x", shape=[8])
        y = fluid.layers.data("y", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, size=16, act="relu")
        logits = fluid.layers.fc(h, size=4)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.Momentum(0.05, 0.9).minimize(loss)
    return main_prog, startup, loss


def crash_drill_main(pid: int, n: int, steps: int) -> None:
    """The crash-drill rank body: train, heartbeat, checkpoint (rank 0),
    self-kill on schedule, and hold a detection barrier at the end."""
    import signal
    import time

    import paddle_tpu as fluid

    ckpt_dir = os.environ["DIST_CKPT_DIR"]
    hb_dir = os.environ.get("DIST_HB_DIR", ckpt_dir)
    kill_rank = int(os.environ.get("DIST_KILL_RANK", "-1"))
    kill_at = int(os.environ.get("DIST_KILL_AT_STEP", "-1"))
    hb_timeout = float(os.environ.get("DIST_HB_TIMEOUT", "10"))
    os.makedirs(hb_dir, exist_ok=True)

    def mark(kind, payload=""):
        path = os.path.join(hb_dir, "%s_%d" % (kind, pid))
        with open(path, "w") as f:
            f.write(payload)

    main_prog, startup, loss = _build_crash_model(fluid)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)

    # resume ONLY when the restart policy says so (DIST_RESUME=1): a first
    # launch must not pick up a concurrent rank-0 save as its own past
    start = 0
    if os.environ.get("DIST_RESUME") == "1":
        args = fluid.io.load_checkpoint(exe, ckpt_dir, main_prog)
        if args is not None:
            start = int(args.get("step", 0))
            main_prog._tpu_step_counter = start
            print("DIST_RESUMED:%d:%d" % (pid, start), flush=True)
    mark("loaded")
    if pid == 0:
        # bootstrap barrier before the FIRST save: a slow-starting peer
        # must not restore a serial rank 0 published after racing ahead —
        # every rank resumes from the SAME step. Bounded wait; a peer that
        # never loads is caught by the end-of-run barrier below.
        deadline = time.monotonic() + hb_timeout
        waiting = set(range(n)) - {pid}
        while waiting and time.monotonic() < deadline:
            waiting = {p for p in waiting if not os.path.isfile(
                os.path.join(hb_dir, "loaded_%d" % p))}
            time.sleep(0.02)

    xs, ys = make_batch()
    for step in range(start, steps):
        if pid == kill_rank and step == kill_at:
            # hard preemption: no cleanup, no goodbye — the peers must
            # find out on their own
            os.kill(os.getpid(), signal.SIGKILL)
        l, = exe.run(main_prog, feed={"x": xs, "y": ys}, fetch_list=[loss])
        lv = np.float32(np.asarray(l).ravel()[0])
        print("DIST_STEP:%d:%d:%s" % (pid, step, lv.tobytes().hex()),
              flush=True)
        mark("hb", str(step))
        if pid == 0:
            # step+1 = "resume here"; rotation is rank 0's alone
            fluid.io.save_checkpoint(
                exe, ckpt_dir, main_prog, trainer_id=0,
                trainer_args={"step": step + 1}, max_num_checkpoints=3)
    mark("done")

    # End-of-run barrier with peer-loss detection: a real job would sit in
    # its final collective forever when a peer died — here the wait is
    # bounded, and a lost peer produces a CLEAN diagnostic + marked exit.
    deadline = time.monotonic() + hb_timeout
    missing = set(range(n)) - {pid}
    while missing and time.monotonic() < deadline:
        for peer in sorted(missing):
            if os.path.isfile(os.path.join(hb_dir, "done_%d" % peer)):
                missing.discard(peer)
        time.sleep(0.05)
    if missing:
        for peer in sorted(missing):
            hb = os.path.join(hb_dir, "hb_%d" % peer)
            last = "never-heartbeat"
            if os.path.isfile(hb):
                with open(hb) as f:
                    last = "last_step=%s" % (f.read().strip() or "?")
            print("DIST_PEER_LOST:rank=%d:lost=%d:%s:waited=%.1fs"
                  % (pid, peer, last, hb_timeout), flush=True)
        os._exit(EXIT_PEER_LOST)


def main():
    pid = int(os.environ["PADDLE_TRAINER_ID"])
    n = int(os.environ["PADDLE_TRAINERS_NUM"])
    mode = os.environ.get("DIST_MODE", "dp")
    steps = int(os.environ.get("DIST_STEPS", "5"))

    if mode == "crash":
        crash_drill_main(pid, n, steps)
        return

    import paddle_tpu as fluid

    if n > 1:
        fluid.parallel.init_distributed()
        assert jax.process_count() == n

    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = startup.random_seed = 11
    with fluid.program_guard(main_prog, startup):
        x = fluid.layers.data("x", shape=[8])
        y = fluid.layers.data("y", shape=[1], dtype="int64")
        if mode == "dp_tp":
            h = fluid.parallel.column_parallel_fc(x, size=16, act="relu")
            h = fluid.parallel.row_parallel_fc(h, size=16, act="relu")
            logits = fluid.layers.fc(h, size=4)
        else:
            h = fluid.layers.fc(x, size=16, act="relu")
            logits = fluid.layers.fc(h, size=4)
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.Momentum(0.05, 0.9).minimize(loss)

    # NCCL2-style transpile is a no-op but must keep the script contract
    t = fluid.transpiler.DistributeTranspiler()
    t.transpile(trainer_id=pid, program=main_prog, trainers=os.environ.get(
        "PADDLE_TRAINER_ENDPOINTS", str(n)))
    main_prog = t.get_trainer_program()

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)

    if mode == "dp_tp":
        ndev = len(jax.devices())
        assert ndev % 2 == 0, ndev
        prog = fluid.CompiledProgram(main_prog).with_mesh(
            {"data": ndev // 2, "model": 2}, loss_name=loss.name)
    else:
        prog = fluid.CompiledProgram(main_prog).with_data_parallel(
            loss_name=loss.name)

    xs, ys = make_batch()
    losses = []
    for step in range(steps):
        l, = exe.run(prog, feed={"x": xs, "y": ys}, fetch_list=[loss])
        losses.append(round(float(np.asarray(l)), 6))
    print("DIST_LOSSES:%d:%s" % (pid, ",".join(map(str, losses))), flush=True)


if __name__ == "__main__":
    main()
    sys.exit(0)
