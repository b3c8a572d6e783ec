"""Per-assigned-architecture smoke tests (assignment requirement):
reduced same-family config, one forward/train step on CPU, output shape +
finite checks; plus the ID serve lifecycle on representative families.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, get_config
from repro.core.rep import Rep
from repro.models.lm import DecoderLM

LM_ARCHS = [a for a in ARCH_IDS if a != "nemo_cnn"]


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_reduced_train_step(arch):
    cfg = get_config(arch).reduced()
    lm = DecoderLM(cfg, max_seq=32)
    key = jax.random.PRNGKey(0)
    p = lm.init(key)
    qs = lm.init_qstate()
    tokens = jax.random.randint(key, (2, 17), 0, cfg.vocab)

    if cfg.input_mode == "embeds":
        x = jax.random.normal(key, (2, 16, cfg.d_model))
        loss, grads = jax.value_and_grad(
            lambda pp: lm.loss_fn_embeds(pp, qs, x, tokens[:, 1:], Rep.FQ)
        )(p)
    else:
        loss, grads = jax.value_and_grad(
            lambda pp: lm.loss_fn(pp, qs, tokens, Rep.FQ))(p)
    assert np.isfinite(float(loss)), arch
    # gradient flows through the STE to every parameter group
    gnorms = jax.tree.map(lambda g: float(jnp.sum(jnp.abs(g))), grads)
    total = sum(jax.tree.leaves(gnorms))
    assert np.isfinite(total) and total > 0, arch


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_reduced_fp_forward_shapes(arch):
    cfg = get_config(arch).reduced()
    lm = DecoderLM(cfg, max_seq=32)
    key = jax.random.PRNGKey(1)
    p = lm.init(key)
    if cfg.input_mode == "embeds":
        x = jax.random.normal(key, (2, 16, cfg.d_model))
    else:
        tokens = jax.random.randint(key, (2, 16), 0, cfg.vocab)
        x = lm.embed_in(p, tokens, Rep.FP)
    h, _, _ = lm.apply(p, x, Rep.FP)
    logits = lm.logits(p, h, Rep.FP)
    assert logits.shape == (2, 16, cfg.vocab), arch
    assert np.isfinite(np.asarray(logits)).all(), arch


@pytest.mark.parametrize("arch", ["granite_3_2b", "olmoe_1b_7b",
                                  "falcon_mamba_7b", "zamba2_1_2b",
                                  "chatglm3_6b", "musicgen_medium",
                                  "mellum2_12b"])
def test_reduced_id_serve(arch):
    """calibrate -> deploy -> integer prefill + decode; int32 logits."""
    cfg = get_config(arch).reduced()
    lm = DecoderLM(cfg, max_seq=32)
    key = jax.random.PRNGKey(2)
    p = lm.init(key)
    tokens = jax.random.randint(key, (2, 16), 0, cfg.vocab)
    calib = lm.calibrate(p, tokens)
    t = lm.deploy(p, calib)
    t = jax.tree.map(jnp.asarray, t,
                     is_leaf=lambda x: isinstance(x, np.ndarray))
    caches = lm.init_caches(2, 32, Rep.ID)
    logits, caches = lm.prefill(t, tokens, caches)
    assert logits.dtype == jnp.int32
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
    logits2, caches = lm.decode_step(t, tok, caches, 16)
    assert logits2.dtype == jnp.int32 and logits2.shape == (2, 1, cfg.vocab)
    # ID logits track FP direction
    x = lm.embed_in(p, tokens, Rep.FP)
    xf, _, _ = lm.apply(p, x, Rep.FP)
    lf = np.asarray(lm.logits(p, xf, Rep.FP))[:, -1]
    li = np.asarray(logits, np.float64)[:, 0] * float(t["meta"]["eps_logits"])
    cc = np.corrcoef(lf.ravel(), li.ravel())[0, 1]
    # hybrid stacks the longest int8 chain (SSM islands + concat requant +
    # shared attention) — direction check only, accuracy comes from QAT.
    # moe routes discretely at every layer: a random-init router's
    # near-uniform probs sit on top-k decision boundaries, so residual
    # quantization noise flips expert choices (measured ~3-16% of
    # token-expert picks on the reduced olmoe, with the per-layer MoE
    # math itself at cc 0.997 and router-logit cc > 0.95); each flip
    # swaps in an unrelated expert FFN, which no deploy-time numeric
    # can undo — direction check only, like hybrid.  Trained routers
    # are decisive; llama4 (moe_every=2 + shared expert) passes 0.93.
    thresh = 0.7 if cfg.family in ("hybrid", "moe") else 0.8
    assert cc > thresh, (arch, cc)


def test_param_counts_match_published():
    expect = {
        "internvl2_76b": 72e9, "falcon_mamba_7b": 7.3e9,
        "olmoe_1b_7b": 6.9e9, "llama4_maverick_400b_a17b": 400e9,
        "granite_3_2b": 2.6e9, "nemotron_4_340b": 340e9,
        "llama3_2_3b": 3.6e9, "chatglm3_6b": 6.2e9,
        "zamba2_1_2b": 1.2e9, "musicgen_medium": 1.5e9,
    }
    for arch, n_exp in expect.items():
        n = get_config(arch).param_count()
        assert 0.8 <= n / n_exp <= 1.25, (arch, n, n_exp)
    # MoE active params
    assert 1.0e9 <= get_config("olmoe_1b_7b").active_param_count() <= 1.6e9
    assert 12e9 <= get_config("llama4_maverick_400b_a17b").active_param_count() <= 20e9
