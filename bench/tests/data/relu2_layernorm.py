"""Plain reference of a dense pre-norm decoder with LayerNorm (gain and
bias) and an ungated squared-ReLU MLP, Nemotron-4's block, in float32.

Token embedding, then per layer LayerNorm -> grouped-query attention
with rotary embeddings (channel pairs (2i, 2i+1), causal softmax over
q.k / sqrt(head_dim)) -> residual add -> LayerNorm -> relu(x Wu)^2 Wd
-> residual add, then a final LayerNorm and the vocabulary projection.
A test copies this file into `bench/references/` of a scratch copy of
the benchmark, to show that a model of another block joins by files
alone; no cell of the benchmark runs it.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness.reference import BLOCK, HI, quantize, quantize_rows

LINEARS = ("wq", "wk", "wv", "wo", "wu", "wd")
EMBED_ID, HEAD_ID, LAYER_BASE = 0, 1, 1000
RESIDUAL_OUT = ("wo", "wd")
NORMS = ("norm1", "norm2")
BIAS_SCALE = 0.1  # LayerNorm biases N(0, 0.1^2), so that they matter


@dataclasses.dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6

    @classmethod
    def from_config(cls, model: dict) -> "Dims":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in model.items() if k in fields})

    def shape(self, name: str):
        d, hq = self.d_model, self.n_heads * self.head_dim
        hk = self.n_kv_heads * self.head_dim
        return {"wq": (d, hq), "wk": (d, hk), "wv": (d, hk), "wo": (hq, d),
                "wu": (d, self.d_ff), "wd": (self.d_ff, d)}[name]


def check_program(cfg, dims: Dims):
    have = Dims(
        n_layers=cfg.n_layers, d_model=cfg.d_model, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd, d_ff=cfg.d_ff,
        vocab=cfg.vocab, rope_theta=cfg.rope_base, norm_eps=dims.norm_eps)
    if have != dims:
        raise ValueError(f"program config {have} differs from {dims}")
    if (cfg.family, cfg.gated, cfg.act, cfg.norm, cfg.norm_bias,
            cfg.rope_fraction) != ("dense", False, "relu2", "layer", True,
                                   1.0):
        raise ValueError("the reference models an ungated squared-ReLU "
                         "decoder with biased LayerNorm and full rotary")


def _norm(key, d):
    return {"g": jnp.ones((d,), jnp.float32),
            "b": BIAS_SCALE * jax.random.normal(key, (d,), jnp.float32)}


def layer_params(key, dims: Dims, layer) -> dict:
    lk = jax.random.fold_in(key, LAYER_BASE + layer)
    out = {}
    for i, name in enumerate(LINEARS):
        rows, cols = dims.shape(name)
        w = jax.random.normal(jax.random.fold_in(lk, i), (rows, cols),
                              jnp.float32)
        scale = 1.0 / rows ** 0.5
        if name in RESIDUAL_OUT:
            scale /= (2.0 * dims.n_layers) ** 0.5
        out[name] = w * scale
    for j, name in enumerate(NORMS):
        out[name] = _norm(jax.random.fold_in(lk, len(LINEARS) + j),
                          dims.d_model)
    return out


def embed_head(key, dims: Dims) -> dict:
    emb = jax.random.normal(jax.random.fold_in(key, EMBED_ID),
                            (dims.vocab, dims.d_model), jnp.float32)
    head = jax.random.normal(jax.random.fold_in(key, HEAD_ID),
                             (dims.d_model, dims.vocab), jnp.float32)
    return {"embed": emb, "head": head * (1.0 / dims.d_model ** 0.5),
            "norm_f": _norm(jax.random.fold_in(key, 2), dims.d_model)}


def program_params(key, dims: Dims, vocab_padded: int) -> dict:
    eh = embed_head(key, dims)
    pad = vocab_padded - dims.vocab
    layers = jax.vmap(lambda i: layer_params(key, dims, i))(
        jnp.arange(dims.n_layers))
    return {
        "embed": {"table": jnp.pad(eh["embed"], ((0, pad), (0, 0)))},
        "norm_f": eh["norm_f"],
        "head": {"w": jnp.pad(eh["head"], ((0, 0), (0, pad)))},
        "segments": [{
            "norm1": layers["norm1"],
            "attn": {n: {"w": layers[n]} for n in ("wq", "wk", "wv", "wo")},
            "norm2": layers["norm2"],
            "mlp": {n: {"w": layers[n]} for n in ("wu", "wd")},
        }],
    }


def _ln(x, p, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * p["g"] + p["b"])


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2) / hd))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], -1).reshape(
        x.shape)


@functools.partial(jax.jit, static_argnums=(1, 4))
def layer(key, dims: Dims, index, x, bits):
    w = layer_params(key, dims, index)
    w = {k: (quantize(v, bits, -1) if k in LINEARS else v)
         for k, v in w.items()}
    T = x.shape[0]
    H, K, hd = dims.n_heads, dims.n_kv_heads, dims.head_dim
    pos = jnp.arange(T)
    h = quantize_rows(_ln(x, w["norm1"], dims.norm_eps), bits)
    q = jnp.dot(h, w["wq"], precision=HI).reshape(T, H, hd)
    k = jnp.dot(h, w["wk"], precision=HI).reshape(T, K, hd)
    v = jnp.dot(h, w["wv"], precision=HI).reshape(T, K, hd)
    q = _rope(q.transpose(1, 0, 2), pos, dims.rope_theta)
    k = _rope(k.transpose(1, 0, 2), pos, dims.rope_theta)
    q = q.reshape(K, H // K, T, hd)
    v = v.transpose(1, 0, 2)
    outs = []
    for lo in range(0, T, BLOCK):
        qb = q[:, :, lo:lo + BLOCK]
        s = jnp.einsum("kgsd,ktd->kgst", qb, k, precision=HI) / hd ** 0.5
        keep = pos[None, :] <= (lo + jnp.arange(qb.shape[2]))[:, None]
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("kgst,ktd->kgsd", p, v, precision=HI))
    ctx = jnp.concatenate(outs, axis=2).reshape(H, T, hd)
    ctx = ctx.transpose(1, 0, 2).reshape(T, H * hd)
    x = x + jnp.dot(quantize_rows(ctx, bits), w["wo"], precision=HI)
    h = quantize_rows(_ln(x, w["norm2"], dims.norm_eps), bits)
    a = jnp.square(jax.nn.relu(jnp.dot(h, w["wu"], precision=HI)))
    return x + jnp.dot(quantize_rows(a, bits), w["wd"], precision=HI)


@functools.partial(jax.jit, static_argnums=(1, 3))
def embed(key, dims: Dims, tokens, bits):
    return quantize(embed_head(key, dims)["embed"], bits, 0)[tokens]


@functools.partial(jax.jit, static_argnums=(1, 5))
def head_rows(key, dims: Dims, x, rows, targets, bits):
    eh = embed_head(key, dims)
    h = quantize_rows(_ln(x[rows], eh["norm_f"], dims.norm_eps), bits)
    logits = jnp.dot(h, quantize(eh["head"], bits, -1), precision=HI)
    at = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
    return jnp.max(logits, -1), at, jnp.argmax(logits, -1)


def step_ops(rows, dims: Dims) -> int:
    """2 operations per weight per token, the head once per row, and 4
    head_dim per (query head, visible position) in attention."""
    d, hd = dims.d_model, dims.head_dim
    per_layer = d * (dims.n_heads + 2 * dims.n_kv_heads) * hd
    per_layer += dims.n_heads * hd * d + 2 * d * dims.d_ff
    ops = 2 * sum(n for _, n in rows) * per_layer * dims.n_layers
    ops += 2 * len(rows) * d * dims.vocab
    visible = sum(n * s + n * (n + 1) // 2 for s, n in rows)
    return ops + dims.n_layers * 4 * hd * dims.n_heads * visible
