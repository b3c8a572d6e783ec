"""Float32 weights of a dense decoder, made from a key, layer by layer.

The benchmark owns the weights: it hands them to the program to
calibrate and deploy, and the plain reference regenerates the same
numbers here on its own, one layer at a time, so that the reference
takes nothing the program has made.

Distributions: embedding N(0, 1), linear weights N(0, 1/d_in), with the
two projections that write into the residual stream (attention output
and MLP down) further scaled by 1/sqrt(2 n_layers) as in GPT-2's
initialisation, and norm gains 1.  This keeps a deep random model in an
ordered regime, where a small change of the input makes a small change
of the logits, as in a trained model: with an embedding of scale 0.02
and no residual scaling, rounding the weights alone to an int8 grid
decorrelates the logits of a 16-layer model from its float32 ones, and
no comparison with a float32 reference could tell int8 from int4.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

# per-layer matrices: name -> (rows, cols) as a function of the dims
LINEARS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")
EMBED_ID, HEAD_ID, LAYER_BASE = 0, 1, 1000
# projections that write into the residual stream
RESIDUAL_OUT = ("wo", "wd")


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


def layer_weights(key, dims: Dims, layer) -> dict:
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
    layers = jax.vmap(lambda i: layer_weights(key, dims, i))(
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
