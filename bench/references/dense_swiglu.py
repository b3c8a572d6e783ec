"""Plain reference of a dense pre-norm SwiGLU decoder, in float32.

Token embedding, then per layer RMSNorm -> grouped-query attention with
rotary embeddings on the leading `rope_fraction` of each head (channel
pairs (2i, 2i+1), causal softmax over q.k / sqrt(head_dim)) -> residual
add -> RMSNorm -> SwiGLU (silu(x Wg) * (x Wu)) Wd -> residual add, then
a final RMSNorm and the vocabulary projection.  Query head h reads kv
head h // (heads / kv heads).  No kernel, cache, batching or
quantization.

Weights: embedding N(0, 1), linear weights N(0, 1/d_in), with the two
projections that write into the residual stream (attention output and
MLP down) further scaled by 1/sqrt(2 n_layers) as in GPT-2's
initialisation, and norm gains 1.  This keeps a deep random model in an
ordered regime, where a small change of the input makes a small change
of the logits, as in a trained model: with an embedding of scale 0.02
and no residual scaling, rounding the weights alone to an int8 grid
decorrelates the logits of a 16-layer model from its float32 ones, and
no comparison with a float32 reference could tell int8 from int4.

The contract every reference module keeps is in `harness/reference.py`.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness import spec
from harness.reference import BLOCK, HI, quantize, quantize_rows

# per-layer matrices, in the order their keys are folded
LINEARS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")
EMBED_ID, HEAD_ID, LAYER_BASE = 0, 1, 1000
# projections that write into the residual stream
RESIDUAL_OUT = ("wo", "wd")

PA = spec.counts("paged_attention")


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes a dense decoder is built from, as the cell runs it."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0
    norm_eps: float = 1e-5

    @classmethod
    def from_config(cls, model: dict) -> "Dims":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in model.items() if k in fields})

    def shape(self, name: str):
        d, hq = self.d_model, self.n_heads * self.head_dim
        hk = self.n_kv_heads * self.head_dim
        return {
            "wq": (d, hq), "wk": (d, hk), "wv": (d, hk), "wo": (hq, d),
            "wg": (d, self.d_ff), "wu": (d, self.d_ff),
            "wd": (self.d_ff, d),
        }[name]


def check_program(cfg, dims: Dims):
    """Raise where the program's ArchConfig is not this model."""
    have = Dims(
        n_layers=cfg.n_layers, d_model=cfg.d_model, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd, d_ff=cfg.d_ff,
        vocab=cfg.vocab, rope_theta=cfg.rope_base,
        rope_fraction=cfg.rope_fraction, norm_eps=dims.norm_eps,
    )
    if have != dims:
        raise ValueError(f"program config {have} differs from {dims}")
    if cfg.family != "dense" or not cfg.gated or cfg.act != "silu":
        raise ValueError("the reference models a dense SwiGLU decoder")


# -- weights ------------------------------------------------------------------

def layer_params(key, dims: Dims, layer) -> dict:
    """One layer's float32 weights; `layer` may be traced (vmap)."""
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
    out["norm1"] = jnp.ones((dims.d_model,), jnp.float32)
    out["norm2"] = jnp.ones((dims.d_model,), jnp.float32)
    return out


def embed_head(key, dims: Dims) -> dict:
    """Embedding table (vocab, d), head (d, vocab), final norm gain."""
    emb = jax.random.normal(jax.random.fold_in(key, EMBED_ID),
                            (dims.vocab, dims.d_model), jnp.float32)
    head = jax.random.normal(jax.random.fold_in(key, HEAD_ID),
                             (dims.d_model, dims.vocab), jnp.float32)
    return {
        "embed": emb,
        "head": head * (1.0 / dims.d_model ** 0.5),
        "norm_f": jnp.ones((dims.d_model,), jnp.float32),
    }


def program_params(key, dims: Dims, vocab_padded: int) -> dict:
    """Every weight, stacked over layers, in the parameter tree of
    `repro.models.lm.DecoderLM.init` for a dense model.  Padded vocab
    rows and columns are zero: no token reaches them and the program
    masks their logits.  Call it under `jax.jit` so that the weights are
    made on the device in one call."""
    eh = embed_head(key, dims)
    pad = vocab_padded - dims.vocab
    layers = jax.vmap(lambda i: layer_params(key, dims, i))(
        jnp.arange(dims.n_layers))
    return {
        "embed": {"table": jnp.pad(eh["embed"], ((0, pad), (0, 0)))},
        "norm_f": {"g": eh["norm_f"]},
        "head": {"w": jnp.pad(eh["head"], ((0, 0), (0, pad)))},
        "segments": [{
            "norm1": {"g": layers["norm1"]},
            "attn": {n: {"w": layers[n]} for n in ("wq", "wk", "wv", "wo")},
            "norm2": {"g": layers["norm2"]},
            "mlp": {n: {"w": layers[n]} for n in ("wg", "wu", "wd")},
        }],
    }


# -- forward pass -------------------------------------------------------------

def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, dims: Dims):
    """x (..., T, hd); rotates channel pairs (2i, 2i+1), i < rot / 2."""
    hd = dims.head_dim
    rot = int(hd * dims.rope_fraction)
    rot -= rot % 2
    inv = 1.0 / (dims.rope_theta ** (np.arange(0, rot, 2) / rot))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    y = jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], -1)
    y = y.reshape(x.shape[:-1] + (rot,))
    return jnp.concatenate([y, x[..., rot:]], -1)


@functools.partial(jax.jit, static_argnums=(1, 4))
def layer(key, dims: Dims, index, x, bits):
    w = layer_params(key, dims, index)
    w = {k: (quantize(v, bits, -1) if v.ndim == 2 else v)
         for k, v in w.items()}
    T = x.shape[0]
    H, K, hd = dims.n_heads, dims.n_kv_heads, dims.head_dim
    pos = jnp.arange(T)
    h = quantize_rows(_rms(x, w["norm1"], dims.norm_eps), bits)
    q = jnp.dot(h, w["wq"], precision=HI).reshape(T, H, hd)
    k = jnp.dot(h, w["wk"], precision=HI).reshape(T, K, hd)
    v = jnp.dot(h, w["wv"], precision=HI).reshape(T, K, hd)
    q = _rope(q.transpose(1, 0, 2), pos, dims)  # (H, T, hd)
    k = _rope(k.transpose(1, 0, 2), pos, dims)  # (K, T, hd)
    v = v.transpose(1, 0, 2)
    g = H // K
    q = q.reshape(K, g, T, hd)
    outs = []
    for lo in range(0, T, BLOCK):  # query blocks bound the score tile
        qb = q[:, :, lo:lo + BLOCK]
        s = jnp.einsum("kgsd,ktd->kgst", qb, k, precision=HI) / hd ** 0.5
        keep = pos[None, :] <= (lo + jnp.arange(qb.shape[2]))[:, None]
        s = jnp.where(keep, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("kgst,ktd->kgsd", p, v, precision=HI))
    ctx = jnp.concatenate(outs, axis=2).reshape(H, T, hd)
    ctx = ctx.transpose(1, 0, 2).reshape(T, H * hd)
    x = x + jnp.dot(quantize_rows(ctx, bits), w["wo"], precision=HI)
    h = quantize_rows(_rms(x, w["norm2"], dims.norm_eps), bits)
    a = jax.nn.silu(jnp.dot(h, w["wg"], precision=HI))
    a = a * jnp.dot(h, w["wu"], precision=HI)
    return x + jnp.dot(quantize_rows(a, bits), w["wd"], precision=HI)


@functools.partial(jax.jit, static_argnums=(1, 3))
def embed(key, dims: Dims, tokens, bits):
    return quantize(embed_head(key, dims)["embed"], bits, 0)[tokens]


@functools.partial(jax.jit, static_argnums=(1, 5))
def head_rows(key, dims: Dims, x, rows, targets, bits):
    """(max logit, logit of target, argmax) at the given rows."""
    eh = embed_head(key, dims)
    h = quantize_rows(_rms(x[rows], eh["norm_f"], dims.norm_eps), bits)
    logits = jnp.dot(h, quantize(eh["head"], bits, -1), precision=HI)
    at = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
    return jnp.max(logits, -1), at, jnp.argmax(logits, -1)


# -- operations ---------------------------------------------------------------

def layer_weights(dims: Dims) -> int:
    """Weights one token passes through in one layer."""
    d, hd = dims.d_model, dims.head_dim
    attn = d * (dims.n_heads + 2 * dims.n_kv_heads) * hd
    attn += dims.n_heads * hd * d
    return attn + 3 * d * dims.d_ff


def step_ops(rows, dims: Dims) -> int:
    """Integer operations one unified step needs; rows: (start, n) per
    real row.  Matrix products: 2 operations per weight per token that
    passes through it, for every layer's q, k, v, o, gate, up and down
    projections; the vocabulary projection once per row, at the
    position whose next token the step produces.  Attention: as
    `counts/paged_attention.py`, at each row's true context.  Norms,
    rotary and the softmax are not counted."""
    tokens = sum(n for _, n in rows)
    ops = 2 * tokens * layer_weights(dims) * dims.n_layers
    ops += 2 * len(rows) * dims.d_model * dims.vocab
    ops += dims.n_layers * sum(
        4 * dims.head_dim * dims.n_heads * PA.visible(s, n)
        for s, n in rows)
    return ops
