"""Pin deploy_specs (abstract dry-run tables) to the real deploy output:
tree structure, shapes and dtypes must match exactly on every reduced
family — this is what makes full-size dry-run lowering trustworthy."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.rep import Rep
from repro.launch.specs import cache_specs, deploy_specs
from repro.models.lm import DecoderLM

FAMILIES = ["granite_3_2b", "olmoe_1b_7b", "falcon_mamba_7b",
            "llama4_maverick_400b_a17b", "zamba2_1_2b", "nemotron_4_340b",
            "musicgen_medium", "mellum2_12b"]


@pytest.mark.parametrize("arch", FAMILIES)
def test_specs_match_real_deploy(arch):
    cfg = get_config(arch).reduced()
    lm = DecoderLM(cfg, max_seq=32)
    key = jax.random.PRNGKey(0)
    p = lm.init(key)
    tokens = jax.random.randint(key, (2, 16), 0, cfg.vocab)
    if cfg.input_mode == "embeds":
        sample = jax.random.normal(key, (2, 16, cfg.d_model))
    else:
        sample = tokens
    calib = lm.calibrate(p, sample)
    t_real = lm.deploy(p, calib)
    t_real.pop("meta")
    t_spec = deploy_specs(lm)

    real_paths = jax.tree_util.tree_flatten_with_path(t_real)[0]
    spec_paths = jax.tree_util.tree_flatten_with_path(t_spec)[0]
    real_map = {jax.tree_util.keystr(k): v for k, v in real_paths}
    spec_map = {jax.tree_util.keystr(k): v for k, v in spec_paths}
    missing = set(real_map) - set(spec_map)
    extra = set(spec_map) - set(real_map)
    assert not missing and not extra, (sorted(missing)[:5], sorted(extra)[:5])
    for k, v in real_map.items():
        sv = spec_map[k]
        v = np.asarray(v)
        assert tuple(v.shape) == tuple(sv.shape), (k, v.shape, sv.shape)
        assert v.dtype == sv.dtype, (k, v.dtype, sv.dtype)


def test_cache_specs_no_allocation():
    cfg = get_config("nemotron_4_340b")  # FULL config: must not allocate
    lm = DecoderLM(cfg, max_seq=32768)
    cs = cache_specs(lm, B=128, max_len=32768)
    leaves = jax.tree.leaves(cs)
    assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
    total = sum(np.prod(l.shape) * l.dtype.itemsize for l in leaves)
    assert total > 1e12  # >1TB KV — proves these were never materialized
