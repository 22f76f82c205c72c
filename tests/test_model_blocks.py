"""``models/blocks.py``: the tables a served model's config is built from,
held against the benchmark's references at the PUBLISHED configurations'
values, and the one contract class under each of the five served models.

The tables were the references' own functions until PR 46 (the program
imported them from its referee, so an error in one was on both sides of
every comparison). They are now two texts, and this file is where the two
meet: at the numbers of ``grid/configs/*.json``, which are the ones served.
"""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grid.reference import falcon_h1 as falcon_ref
from grid.reference import glm5_flash as glm5_ref
from grid.reference import kimi_k2 as kimi_ref
from grid.reference import laguna as laguna_ref
from grid.reference import ling3_flash as ling3_ref
from grid.reference import motif3 as motif3_ref
from paddle_tpu.models import blocks, falcon_h1, glm5_flash, ling3_flash

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def published(name):
    with open(os.path.join(ROOT, "grid", "configs", name + ".json")) as f:
        return json.load(f)


KIMI = published("kimi-k2-ep32-serve")
MOTIF = published("motif-3-beta-ep16-serve")
LAGUNA = published("laguna-s-ep2-serve")
LING = published("ling-3-flash-ep4-serve")
GLM = published("glm-5.3-flash-ep8-serve")
FALCON = published("falcon-h1-34b-serve")


@pytest.mark.parametrize("want,rot,theta,scaling", [
    (kimi_ref.yarn_inv_freq(KIMI["qk_rope_head_dim"], KIMI["rope_theta"],
                            KIMI["rope_scaling"]),
     KIMI["qk_rope_head_dim"], KIMI["rope_theta"], KIMI["rope_scaling"]),
    (motif3_ref.rotary(MOTIF)[motif3_ref.FULL], MOTIF["qk_rope_head_dim"],
     MOTIF["rope_theta"], MOTIF["rope_scaling"]),
    (motif3_ref.rotary(MOTIF)[motif3_ref.RING], MOTIF["qk_rope_head_dim"],
     MOTIF["swa_rope_theta"], None),
], ids=["kimi-k2", "motif-3 full", "motif-3 window"])
def test_yarn_inv_freq_is_the_references(want, rot, theta, scaling):
    got = blocks.yarn_inv_freq(int(rot), float(theta), scaling)
    assert got.dtype == np.float64 and got.shape == (rot // 2,)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-14, atol=0)
    if scaling:      # the ramp lies inside the table: both ends are there
        plain = float(theta) ** (-np.arange(rot // 2) * 2.0 / rot)
        assert got[0] == plain[0]
        assert abs(got[-1] * scaling["factor"] / plain[-1] - 1) < 1e-12
    assert blocks.yarn_inv_freq.__code__ is not \
        kimi_ref.yarn_inv_freq.__code__


@pytest.mark.parametrize("model", [
    KIMI, dict(MOTIF, qk_nope_head_dim=MOTIF["head_dim"]
               - MOTIF["qk_rope_head_dim"])], ids=["kimi-k2", "motif-3"])
def test_softmax_scale_is_the_references(model):
    d_head = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    got = blocks.mla_softmax_scale(d_head, model["rope_scaling"])
    assert got == pytest.approx(kimi_ref.softmax_scale(model), rel=1e-15)
    # Kimi-K2 scales all dimensions (mscale^2 on the score), Motif-3 not
    assert (got > d_head ** -0.5) == bool(
        model["rope_scaling"].get("mscale_all_dim"))


@pytest.mark.parametrize("kind", sorted(LAGUNA["rope_parameters"]))
def test_rope_table_is_the_references(kind):
    rope = LAGUNA["rope_parameters"][kind]
    freq, factor = blocks.rope_table(LAGUNA["head_dim"], rope)
    want_freq, want_factor = laguna_ref.rope_table(LAGUNA["head_dim"], rope)
    np.testing.assert_allclose(freq, want_freq, rtol=1e-14, atol=0)
    assert factor == pytest.approx(want_factor, rel=1e-15)
    assert len(freq) == LAGUNA["head_dim"] * rope["partial_rotary_factor"] / 2
    assert (factor == 1.0) == (rope["rope_type"] == "default")


def test_rope_table_refuses_a_type_it_does_not_know():
    with pytest.raises(ValueError, match="rope_type"):
        blocks.rope_table(128, {"rope_type": "llama3", "rope_theta": 1e4})


@pytest.mark.parametrize("what", ["log_decay", "l2"])
def test_the_recurrence_helpers_are_the_references(what):
    """Ling-3's gate and its q/k normalisation at the published lower
    bound and head width, over pre-activations far out on both sides."""
    rng = np.random.RandomState(7)
    h, dk = 4, LING["head_dim"]
    z = jnp.asarray(rng.randn(5, h, dk).astype("float32") * 8.0)
    if what == "log_decay":
        a_log = jnp.asarray(rng.randn(h).astype("float32"))
        got = blocks.log_decay(z, a_log, float(LING["kda_lower_bound"]))
        want = ling3_ref.log_decay(z, a_log, float(LING["kda_lower_bound"]))
        assert float(got.max()) <= 0.0
        assert float(got.min()) >= LING["kda_lower_bound"]
    else:
        got, want = blocks.l2_normalize(z), ling3_ref._l2(z)
        np.testing.assert_allclose(np.asarray(jnp.sum(got * got, -1)), 1.0,
                                   atol=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=0)


def test_the_layer_kinds_are_the_references_names():
    assert (ling3_flash.KDA, ling3_flash.MLA) == (ling3_ref.KDA,
                                                  ling3_ref.MLA)
    assert set(LING["layer_types"]) == {ling3_flash.KDA, ling3_flash.MLA}


@pytest.mark.parametrize("what", ["index_rope", "log_decay", "sinkhorn",
                                  "pooled", "swiglu", "kinds"])
def test_the_sparse_hybrids_helpers_are_the_references(what):
    """GLM-5.3-Flash's helpers that the program owns, each against the
    reference's at the configuration's values: the indexer's interleaved
    rotation at 64 of 128 lanes and a base of 8e6 out to position 16,000;
    the KDA gate at ``gate_lower_bound``; the residual path's Sinkhorn
    with ``hc_eps`` in its divisions; the pooled key as the mean of a
    block's four; SwiGLU under ``swiglu_limit``; the layer kinds' names."""
    from grid.drivers import serve_dsa

    rng = np.random.RandomState(11)
    cfg = serve_dsa.model_config(GLM)
    m = GLM["model"]
    if what == "index_rope":
        x = jnp.asarray(rng.randn(6, 32, GLM["index_head_dim"])
                        .astype("float32"))
        pos = jnp.asarray([0, 1, 7, 2047, 8191, 16000])
        got = glm5_flash.index_rope(x, pos, cfg.index_inv_freq)
        want = glm5_ref.index_rope(x, pos, float(m["index_rope_theta"]),
                                   int(m["index_rope_dim"]))
        np.testing.assert_array_equal(np.asarray(got[..., 64:]),
                                      np.asarray(x[..., 64:]))
        # the pairs are neighbours: lane 0 turns with lane 1, not lane 32
        one = glm5_flash.index_rope(
            jnp.zeros((1, 128)).at[0, 0].set(1.0), jnp.asarray([1]),
            cfg.index_inv_freq)
        assert abs(float(one[0, 1]) - np.sin(1.0)) < 1e-6
        assert float(jnp.abs(one[0, 2:]).max()) == 0.0
    elif what == "log_decay":
        lin = GLM["linear_attn_config"]
        z = jnp.asarray(rng.randn(5, 4, lin["head_dim"]).astype("float32")
                        * 8.0)
        a_log = jnp.asarray(rng.randn(4).astype("float32"))
        got = blocks.log_decay(z, a_log, float(lin["gate_lower_bound"]))
        want = glm5_ref.log_decay(z, a_log, float(lin["gate_lower_bound"]))
        assert cfg.lower_bound == lin["gate_lower_bound"] == -5
    elif what == "sinkhorn":
        n = GLM["hc_mult"]
        lp = {"pa": jnp.asarray(rng.randn(n * 8, 2 * n + n * n)
                                .astype("float32")),
              "aa": jnp.full((3,), 0.1), "ba": jnp.asarray(
                  rng.randn(2 * n + n * n).astype("float32"))}
        x = jnp.asarray(rng.randn(n, 3, 8).astype("float32"))
        _, _, got = blocks.mix_in(cfg, lp, "a", x, jnp.ones((8,)))
        _, _, want = glm5_ref.mhc_maps(
            lp["pa"], lp["aa"], lp["ba"], jnp.moveaxis(x, 0, 1), n,
            GLM["hc_sinkhorn_iters"], GLM["rms_norm_eps"], GLM["hc_eps"])
        assert cfg.sinkhorn_eps == GLM["hc_eps"] == 1e-6
        # the divisions carry hc_eps: without it the two differ
        _, _, bare = glm5_ref.mhc_maps(
            lp["pa"], lp["aa"], lp["ba"], jnp.moveaxis(x, 0, 1), n,
            GLM["hc_sinkhorn_iters"], GLM["rms_norm_eps"], 0.0)
        assert float(jnp.abs(bare - want).max()) > 1e-8
    elif what == "pooled":
        k = jnp.asarray(rng.randn(16, 128).astype("float32"))
        want = glm5_ref.pooled_keys(k, GLM["index_kpool"])
        got = jnp.stack([k[4 * b:4 * b + 4].mean(0) for b in range(4)])
        assert cfg.index_row == (4, 128, 512)
    elif what == "swiglu":
        u = jnp.asarray(rng.randn(3, 8).astype("float32") * 40.0)
        wg, wu = (jnp.asarray(rng.randn(8, 8).astype("float32"))
                  for _ in range(2))
        wd = jnp.eye(8)
        got = glm5_flash._mlp(cfg, u, wg, wu, wd)
        want = glm5_ref._mlp(u, wg, wu, wd, float(GLM["swiglu_limit"]))
        plain = blocks.swiglu(u, wg, wu, wd)       # the clamps bind here
        assert float(jnp.abs(plain - want).max()) > 1.0
    else:
        assert (glm5_flash.KDA, glm5_flash.DSA) == (glm5_ref.KDA,
                                                    glm5_ref.DSA)
        assert set(GLM["layer_types"]) == {glm5_flash.KDA, glm5_flash.DSA}
        assert glm5_ref.layer_kinds(GLM) == GLM["layer_types_held"] == [
            GLM["layer_types"][i] for i in GLM["published_layer_indices"]]
        assert cfg.layer_types == tuple(GLM["layer_types_held"])
        return
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-6)


def _falcon_cfg():
    from grid.drivers import serve_ssm

    return serve_ssm.model_config(FALCON)


@pytest.mark.parametrize("what", ["rope", "mup", "sizes", "gated_norm",
                                  "convolution", "seeds"])
def test_the_parallel_hybrids_tables_are_the_references(what):
    """Falcon-H1's tables in the program against the reference's, at the
    published configuration's values: the rotary frequencies at theta
    1e11, the multipliers laid over the SSM projection's five segments,
    the sizes both derive, the gated grouped norm, the convolution, and
    the seeds the configuration's ``assumed`` states."""
    cfg = _falcon_cfg()
    rng = np.random.RandomState(5)
    z = falcon_ref.sizes(FALCON)
    if what == "rope":
        x = jnp.asarray(rng.randn(6, 2, 128).astype("float32"))
        pos = jnp.asarray([0, 1, 7, 1000, 100000, 262143])
        got = blocks.rope(x, pos, cfg.inv_freq)
        want = falcon_ref._rope(x, pos, float(FALCON["rope_theta"]))
        assert cfg.rope_theta == 1e11 and cfg.inv_freq.shape == (64,)
    elif what == "mup":
        got = falcon_h1._mup_vector(cfg)
        want = np.concatenate([
            np.full(w, FALCON["ssm_in_multiplier"] * m, "float32")
            for w, m in zip((4096, 4096, 512, 512, 32),
                            FALCON["ssm_multipliers"])])
        assert got.shape == (9248,) and cfg.in_segments == (
            4096, 4096, 512, 512, 32)
        assert {k: FALCON[k] for k in falcon_h1.MUP_KEYS} == {
            k: (list(v) if isinstance(v, tuple) else v)
            for k, v in cfg.mup.items()}
    elif what == "sizes":
        assert (z["H"], z["N"], z["P"], z["G"]) == (32, 256, 128, 2)
        assert cfg.slot_state == (z["H"], z["N"], z["P"], z["taps"] - 1,
                                  z["d_ssm"] + 2 * z["G"] * z["N"])
        assert cfg.slot_state == (32, 256, 128, 3, 5120)
        assert (cfg.n_head, cfg.n_kv_head, cfg.d_head) == (
            z["n_head"], z["n_kv"], z["d_head"]) == (20, 4, 128)
        assert cfg.chunk == FALCON["mamba_chunk_size"] == 128
        assert cfg.vocab_size == 261120 and cfg.d_ff == 21504
        assert cfg.n_layer == 5 and FALCON["published"] == {
            "num_hidden_layers": 72}
        return
    elif what == "gated_norm":
        y, gate = (jnp.asarray(rng.randn(3, 4096).astype("float32"))
                   for _ in range(2))
        g = jnp.asarray(rng.rand(4096).astype("float32") + 0.5)
        got = blocks.gated_group_norm(y, gate, g, cfg.ssm_groups,
                                      cfg.rms_eps)
        v = (y * jax.nn.silu(gate)).reshape(3, 2, 2048)
        want = (v / jnp.sqrt(jnp.mean(v * v, axis=-1, keepdims=True)
                             + FALCON["rms_norm_eps"])).reshape(3, 4096) * g
    elif what == "convolution":
        u = jnp.asarray(rng.randn(9, 16).astype("float32"))
        cw = jnp.asarray(rng.randn(4, 16).astype("float32"))
        cb = jnp.asarray(rng.randn(16).astype("float32"))
        got, _ = blocks.causal_conv_prefill(u, cw, cb, 9)
        up = jnp.pad(u, ((3, 0), (0, 0)))
        want = jax.nn.silu(sum(cw[j] * up[j:j + 9] for j in range(4)) + cb)
        assert cfg.conv_taps == FALCON["mamba_d_conv"] == 4
    else:
        m = FALCON["model"]
        assert cfg.seed_rms == m["seed_rms"] == falcon_h1.SEED_RMS
        assert cfg.dt_range == tuple(m["dt_range"]) == (0.001, 0.1)
        assert cfg.a_range == tuple(m["a_range"]) == (1.0, 16.0)
        assert cfg.dtype == jnp.bfloat16 and m["state_dtype"] == "float32"
        return
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-6)


SERVED = [("smallthinker", "SmallThinkerLM"), ("kimi_k2", "KimiK2LM"),
          ("laguna", "LagunaLM"), ("ling3_flash", "Ling3FlashLM"),
          ("motif3", "Motif3LM"), ("glm5_flash", "Glm5FlashLM"),
          ("falcon_h1", "FalconH1LM"), ("deepseek_v32", "DeepSeekV32LM"),
          ("nemotron3", "Nemotron3LM")]


@pytest.mark.parametrize("module,cls", SERVED, ids=[m for m, _ in SERVED])
def test_prefill_last_is_the_last_row_of_prefill(module, cls):
    """The contract class, once, under each model at its tests' toy widths:
    two prompts of different lengths in one bucket."""
    model = importlib.import_module("test_" + module).toy_model()
    served = getattr(importlib.import_module("paddle_tpu.models." + module),
                     cls)
    assert type(model) is served and issubclass(served, blocks.ServedLM)
    for name in ("prefill", "prefill_last", "decode", "__init__"):
        assert name not in vars(served), name      # written once, in blocks
    rng = np.random.RandomState(3)
    tokens = jnp.asarray(rng.randint(0, model.cfg.vocab_size, (2, 16)),
                         jnp.int32)
    lengths = jnp.asarray([5, 11], jnp.int32)
    logits, kept = model.prefill(model.params, tokens, lengths)
    last, kept_last = model.prefill_last(model.params, tokens, lengths)
    assert logits.shape == (2, 16, model.cfg.vocab_size)
    assert last.shape == (2, model.cfg.vocab_size)
    assert len(kept) == len(kept_last) == model.cfg.n_layer
    want = np.stack([np.asarray(logits[0, 4]), np.asarray(logits[1, 10])])
    np.testing.assert_allclose(np.asarray(last), want, atol=1e-4, rtol=1e-5)


def test_a_model_without_weights_draws_them_from_its_seed():
    from test_smallthinker import toy_cfg

    from paddle_tpu.models import smallthinker as st

    cfg = toy_cfg(n_layer=1, rope_layout=[1], window_layout=[0])
    got = st.SmallThinkerLM(cfg, seed=5).params
    want = st.init_params(cfg, 5)
    assert sorted(got) == ["gf", "head", "layers", "tok_emb"]
    np.testing.assert_array_equal(np.asarray(got["layers"][0]["wq"]),
                                  np.asarray(want["layers"][0]["wq"]))
    assert np.any(np.asarray(got["head"]) != np.asarray(
        st.init_params(cfg, 6)["head"]))
