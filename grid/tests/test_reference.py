"""The grid's plain float32 references against the program's own models,
at toy size on the CPU. On the chip the serving reference is compared at
full width inside every run (drivers/serve.check)."""

import jax
import jax.numpy as jnp
import numpy as np

from grid.reference import decoder, transformer

from conftest import TOY_SERVE_MODEL, TOY_TRAIN_MODEL


def test_decoder_reference_matches_decoder_lm():
    from paddle_tpu.models.decoder_lm import (DecoderConfig, init_params,
                                              prefill_forward)

    m = TOY_SERVE_MODEL
    cfg = DecoderConfig(vocab_size=m["vocab_size"], n_layer=m["n_layer"],
                        d_model=m["n_embd"], n_head=m["n_head"],
                        max_seq=m["n_positions"], dtype="float32")
    params = init_params(cfg, seed=3)
    toks = np.random.RandomState(0).randint(0, m["vocab_size"], 40)
    with jax.default_matmul_precision("highest"):
        want, _ = prefill_forward(params, cfg, jnp.asarray(toks)[None],
                                  jnp.asarray([40]))
        got = decoder.forward(params, m["n_head"], jnp.asarray(toks))
    # float32 against float32 in another order of operations
    np.testing.assert_allclose(got, want[0], atol=2e-5)
    # a served greedy sequence ranks at the top of the reference's rows...
    greedy = list(np.asarray(jnp.argmax(want[0], -1))[9:])
    assert decoder.worst_margin(params, m, toks[:10].tolist(),
                                _rollout(params, cfg, toks[:10], 8)) < 1e-4
    # ... and a wrong token falls outside the margin
    wrong = _rollout(params, cfg, toks[:10], 8)
    wrong[3] = (wrong[3] + 1) % m["vocab_size"]
    assert decoder.worst_margin(params, m, toks[:10].tolist(), wrong) \
        > decoder.LOGIT_MARGIN
    del greedy


def _rollout(params, cfg, prompt, n):
    from paddle_tpu.models.decoder_lm import reference_decode

    with jax.default_matmul_precision("highest"):
        return reference_decode(params, cfg, list(prompt), n)[0]


def test_transformer_reference_matches_models_transformer():
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as tfm

    m, seq, rows = TOY_TRAIN_MODEL, 8, 3
    with fluid.unique_name.guard(), fluid.scope_guard(fluid.Scope()):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 5
        with fluid.program_guard(main, startup):
            feeds = [fluid.layers.data("src", shape=[seq], dtype="int64"),
                     fluid.layers.data("trg", shape=[seq], dtype="int64"),
                     fluid.layers.data("lbl", shape=[seq, 1], dtype="int64"),
                     fluid.layers.data("smask", shape=[seq]),
                     fluid.layers.data("tmask", shape=[seq])]
            logits, loss = tfm.transformer(
                *feeds, m["vocab_size"], m["vocab_size"], max_length=seq,
                n_layer=m["n_layer"], n_head=m["n_head"],
                d_model=m["d_model"], d_inner=m["d_inner"], is_test=True)
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup)
        rng = np.random.RandomState(1)
        feed = {"src": rng.randint(2, m["vocab_size"], (rows, seq)),
                "trg": rng.randint(2, m["vocab_size"], (rows, seq)),
                "lbl": rng.randint(2, m["vocab_size"], (rows, seq, 1)),
                "smask": np.ones((rows, seq), "float32"),
                "tmask": np.ones((rows, seq), "float32")}
        with jax.default_matmul_precision("highest"):
            want_logits, want_loss = exe.run(main, feed=feed,
                                             fetch_list=[logits, loss])
        scope = fluid.global_scope()
        params = {p.name: np.asarray(scope.find_var(p.name))
                  for p in main.all_parameters()}
        exe.close()
    got = transformer.logits(params, m["n_layer"], m["n_head"],
                             feed["src"], feed["trg"])
    np.testing.assert_allclose(got, want_logits, atol=2e-5)
    # is_test switches label smoothing off in the program
    got_loss = transformer.loss(got, jnp.asarray(feed["lbl"][..., 0]), 0.0)
    np.testing.assert_allclose(got_loss, np.asarray(want_loss).ravel()[0],
                               rtol=1e-5)
    # the smoothed loss of uniform logits is ln V for any eps
    flat = transformer.loss(jnp.zeros((2, 4, 30)), jnp.zeros((2, 4), int), 0.1)
    np.testing.assert_allclose(flat, np.log(30), rtol=1e-6)
