"""Step builders + abstract inputs for every (arch x shape) dry-run cell.

Shape -> step mapping (assignment):
  train_4k    -> train_step   (FQ/QAT + AdamW update, remat per layer)
  prefill_32k -> prefill_step (ID integer serving, fills KV)
  decode_32k  -> serve_step   (ID, one token, KV cache of seq_len)
  long_500k   -> serve_step   (ID, 512k state; SSM/hybrid only)
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, get_config
from repro.core.rep import Rep
from repro.launch import specs as specs_mod
from repro.models.lm import DecoderLM
from repro.optim.adamw import adamw_init, adamw_update
from repro.optim.schedule import cosine_schedule
from repro.sharding.hints import use_profile
from repro.sharding.rules import (
    batch_spec, caches_sharding, params_sharding,
)

SHAPES: Dict[str, dict] = {
    "train_4k": dict(seq=4096, batch=256, kind="train"),
    "prefill_32k": dict(seq=32768, batch=32, kind="prefill"),
    "decode_32k": dict(seq=32768, batch=128, kind="decode"),
    "long_500k": dict(seq=524288, batch=1, kind="decode"),
}


def cell_supported(cfg: ArchConfig, shape: str) -> Optional[str]:
    """None if runnable; otherwise the documented skip reason."""
    if shape == "long_500k" and not cfg.sub_quadratic:
        return ("skip: pure full-attention arch at 524k decode "
                "(assignment: sub-quadratic only)")
    return None


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


# 100B+ param archs keep Adam moments in bf16 (8 bytes/param saved) so a
# full train state fits the 512-chip multi-pod HBM budget.
MOMENTS_BF16 = {"llama4_maverick_400b_a17b", "nemotron_4_340b"}


def build_train_step(lm: DecoderLM, *, microbatches: int = 1):
    """FQ/QAT train step.  ``microbatches`` > 1 enables gradient
    accumulation (sequential lax.scan over batch slices) — activation
    memory scales down by the factor while math stays identical."""
    c = lm.cfg

    def loss_of(tr, mb):
        if c.input_mode == "embeds":
            return lm.loss_fn_embeds(
                tr["params"], tr["qstate"], mb["embeds"], mb["targets"], Rep.FQ
            )
        return lm.loss_fn(tr["params"], tr["qstate"], mb["tokens"], Rep.FQ)

    def train_step(trainable, opt_state, batch):
        if microbatches == 1:
            loss, grads = jax.value_and_grad(loss_of)(trainable, batch)
        else:
            mbs = jax.tree.map(
                lambda x: x.reshape(microbatches, x.shape[0] // microbatches,
                                    *x.shape[1:]),
                batch)

            def acc_body(carry, mb):
                from repro.sharding.hints import hint

                loss_sum, g_sum = carry
                # the (M, B/M, ...) reshape loses the batch sharding —
                # re-pin each microbatch slice to the (pod, data) axes
                mb = jax.tree.map(lambda t: hint(t, "batch0"), mb)
                li, gi = jax.value_and_grad(loss_of)(trainable, mb)
                g_sum = jax.tree.map(jnp.add, g_sum, gi)
                return (loss_sum + li, g_sum), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), trainable
            )
            (loss_sum, g_sum), _ = jax.lax.scan(
                acc_body, (jnp.float32(0.0), g0), mbs)
            inv = 1.0 / microbatches
            loss = loss_sum * inv
            grads = jax.tree.map(lambda g: g * inv, g_sum)
        lr = cosine_schedule(opt_state["step"])
        new_tr, new_opt = adamw_update(trainable, grads, opt_state, lr=lr)
        return loss, new_tr, new_opt

    return train_step


def train_input_specs(lm: DecoderLM, shape: str):
    c = lm.cfg
    s = SHAPES[shape]
    B, S = s["batch"], s["seq"]
    if c.input_mode == "embeds":
        return {
            "embeds": jax.ShapeDtypeStruct((B, S, c.d_model), jnp.bfloat16),
            "targets": jax.ShapeDtypeStruct((B, S), jnp.int32),
        }
    return {"tokens": jax.ShapeDtypeStruct((B, S + 1), jnp.int32)}


def train_state_specs(lm: DecoderLM):
    trainable = {
        "params": specs_mod.float_param_specs(lm),
        "qstate": jax.eval_shape(lm.init_qstate),
    }
    mdt = (jnp.bfloat16 if lm.cfg.name in MOMENTS_BF16 else jnp.float32)
    opt = jax.eval_shape(lambda: adamw_init(
        jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), trainable),
        dtype=mdt))
    return trainable, opt


def train_shardings(lm: DecoderLM, mesh, shape: str, *, zero2=False):
    """zero2: ZeRO-2 layout — params replicated across "data" (no
    per-use weight all-gathers), Adam moments stay 2-D sharded; for
    models whose params fit replicated."""
    trainable, opt = train_state_specs(lm)
    tr_sh = params_sharding(trainable, mesh, weight_stationary=zero2)
    opt_sh = {
        "mu": params_sharding(opt["mu"], mesh),
        "nu": params_sharding(opt["nu"], mesh),
        "step": NamedSharding(mesh, P()),
    }
    batch = train_input_specs(lm, shape)
    b_sh = jax.tree.map(
        lambda s: NamedSharding(
            mesh, batch_spec(mesh, len(s.shape), shape=s.shape)), batch)
    out_sh = (NamedSharding(mesh, P()), tr_sh, opt_sh)
    return (tr_sh, opt_sh, b_sh), out_sh, (trainable, opt, batch)


# ---------------------------------------------------------------------------
# serve (ID)
# ---------------------------------------------------------------------------


def build_prefill_step(lm: DecoderLM):
    def prefill_step(tables, batch, caches):
        return lm.prefill(tables, batch, caches)
    return prefill_step


def build_decode_step(lm: DecoderLM):
    def decode_step(tables, token, caches, pos):
        return lm.decode_step(tables, token, caches, pos)
    return decode_step


def serve_input_specs(lm: DecoderLM, shape: str):
    c = lm.cfg
    s = SHAPES[shape]
    B, S = s["batch"], s["seq"]
    tables = specs_mod.deploy_specs(lm)
    caches = specs_mod.cache_specs(lm, B, S)
    if s["kind"] == "prefill":
        if c.input_mode == "embeds":
            batch = jax.ShapeDtypeStruct((B, S, c.d_model), jnp.int8)
        else:
            batch = jax.ShapeDtypeStruct((B, S), jnp.int32)
        return tables, batch, caches
    if c.input_mode == "embeds":
        tok = jax.ShapeDtypeStruct((B, 1, c.d_model), jnp.int8)
    else:
        tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return tables, tok, caches, pos


def serve_shardings(
    lm: DecoderLM, mesh, shape: str, *, weight_stationary=False
):
    """weight_stationary: replicate the tables across the data axis
    instead of 2-D FSDP sharding (no per-token weight all-gathers)."""
    s = SHAPES[shape]
    ins = serve_input_specs(lm, shape)
    tables = ins[0]
    t_sh = params_sharding(tables, mesh, weight_stationary=weight_stationary)
    c_sh = caches_sharding(ins[2], mesh)
    x_sh = NamedSharding(
        mesh, batch_spec(mesh, len(ins[1].shape), shape=ins[1].shape))
    B = ins[1].shape[0]
    logits_sh = NamedSharding(
        mesh, batch_spec(mesh, 3, shape=(B, 1, lm.cfg.vocab)))
    if s["kind"] == "prefill":
        return (t_sh, x_sh, c_sh), (logits_sh, c_sh), ins
    pos_sh = NamedSharding(mesh, P())
    return (t_sh, x_sh, c_sh, pos_sh), (logits_sh, c_sh), ins


# ---------------------------------------------------------------------------
# cell -> lowered
# ---------------------------------------------------------------------------


# Gradient-accumulation factors for cells whose activations exceed v5e
# HBM at the assigned (huge) global batch; chosen from baseline
# memory_analysis, recorded in EXPERIMENTS.md §Dry-run.
MICROBATCH = {
    ("olmoe_1b_7b", "train_4k"): 4,
    ("llama4_maverick_400b_a17b", "train_4k"): 4,
    ("internvl2_76b", "train_4k"): 4,
    ("nemotron_4_340b", "train_4k"): 8,
    ("chatglm3_6b", "train_4k"): 2,
    ("llama3_2_3b", "train_4k"): 2,
    ("falcon_mamba_7b", "train_4k"): 8,
    ("zamba2_1_2b", "train_4k"): 4,
    ("musicgen_medium", "train_4k"): 2,
}


def lower_cell(
    arch: str, shape: str, mesh, *, check=True, microbatches: int = 0,
    zero2: bool = False, weight_stationary: bool = False,
):
    """Lower one (arch x shape) cell on `mesh`. -> jax.stages.Lowered.

    microbatches: gradient-accumulation override (0: the MICROBATCH
    table); zero2 / weight_stationary: see train_shardings /
    serve_shardings."""
    cfg = get_config(arch)
    reason = cell_supported(cfg, shape)
    if reason and check:
        raise ValueError(reason)
    s = SHAPES[shape]
    mb = microbatches or MICROBATCH.get((arch, shape), 1)
    lm = DecoderLM(cfg, max_seq=s["seq"] + (1 if s["kind"] == "train" else 0))
    with mesh, use_profile(mesh):
        if s["kind"] == "train":
            in_sh, out_sh, in_specs = train_shardings(
                lm, mesh, shape, zero2=zero2)
            step = build_train_step(lm, microbatches=mb)
            lowered = jax.jit(
                step, in_shardings=in_sh, out_shardings=out_sh,
            ).lower(*in_specs)
        elif s["kind"] == "prefill":
            in_sh, out_sh, ins = serve_shardings(
                lm, mesh, shape, weight_stationary=weight_stationary)
            step = build_prefill_step(lm)
            lowered = jax.jit(
                step, in_shardings=in_sh, out_shardings=out_sh,
            ).lower(*ins)
        else:
            in_sh, out_sh, ins = serve_shardings(
                lm, mesh, shape, weight_stationary=weight_stationary)
            step = build_decode_step(lm)
            lowered = jax.jit(
                step, in_shardings=in_sh, out_shardings=out_sh,
            ).lower(*ins)
    return lowered, lm
