"""Plain reference of a pre-norm decoder whose every MLP is a sparse
mixture of SwiGLU experts and whose attention layers mix a sliding
window with full attention (Mellum2-12B-A2.5B's block), in float32.

Token embedding, then per layer RMSNorm -> grouped-query attention ->
residual add -> RMSNorm -> mixture of experts -> residual add, then a
final RMSNorm and the vocabulary projection (untied).  No kernel,
cache, batching or quantization.

- Layer kinds repeat `pattern` ("sliding" or "full").  A sliding layer
  lets the query at position i see keys j with i - window < j <= i
  (HF `q_idx - kv_idx < sliding_window`); a full layer is causal.
- Rotary on every channel pair (2i, 2i+1) of each head at base
  `rope_theta`.  Sliding layers use the plain frequencies.  Full layers
  use YaRN's: each frequency blended between itself and itself / factor
  by a linear ramp over the channel pairs that turn between beta_fast
  and beta_slow times in the original context, and cos and sin scaled
  by `yarn_attention_factor` (so q.k by its square), as HF's
  `_compute_yarn_parameters` with truncation.
- Softmax over q.k / sqrt(head_dim).  Query head h reads kv head
  h // (heads / kv heads).
- Router: softmax over x Wr (n_experts logits), the top_k largest
  probabilities renormalised to sum 1; each token's output is the
  weighted sum of its top_k experts' silu(x Wg) * (x Wu) Wd.  Computed
  densely over every expert with weight 0 for the unchosen, which is
  the same sum.

Weights as `dense_swiglu`: embedding N(0, 1), linear weights N(0,
1/d_in), the projections into the residual stream (attention output,
each expert's down projection) further scaled by 1/sqrt(2 n_layers),
norm gains 1.  The router is N(0, 1/d_model), so its logits have unit
scale.

The contract every reference module keeps is in `harness/reference.py`.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from harness import spec
from harness.reference import BLOCK, HI, quantize, quantize_rows

# per-layer matrices, in the order their keys are folded
LINEARS = ("wq", "wk", "wv", "wo", "wr", "wg", "wu", "wd")
EMBED_ID, HEAD_ID, LAYER_BASE = 0, 1, 1000
RESIDUAL_OUT = ("wo", "wd")
EXPERT = ("wg", "wu", "wd")  # stacked over experts

PAW = spec.counts("paged_attention_windowed")


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the model is built from, as the cell runs it."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_expert: int
    n_experts: int
    top_k: int
    vocab: int
    window: int
    pattern: tuple = ("sliding", "sliding", "sliding", "full")
    rope_theta: float = 500000.0
    yarn_factor: float = 16.0
    yarn_original_max_position: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.0
    norm_eps: float = 1e-6

    @classmethod
    def from_config(cls, model: dict) -> "Dims":
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in model.items() if k in fields}
        kw["pattern"] = tuple(kw.get("pattern", cls.pattern))
        return cls(**kw)

    def kind(self, layer: int) -> str:
        return self.pattern[layer % len(self.pattern)]

    def shape(self, name: str):
        d, hq = self.d_model, self.n_heads * self.head_dim
        hk = self.n_kv_heads * self.head_dim
        E, f = self.n_experts, self.d_expert
        return {
            "wq": (d, hq), "wk": (d, hk), "wv": (d, hk), "wo": (hq, d),
            "wr": (d, E), "wg": (E, d, f), "wu": (E, d, f),
            "wd": (E, f, d),
        }[name]


def windows(dims: Dims) -> list:
    """Each layer's window, None for a full layer."""
    return [dims.window if dims.kind(i) == "sliding" else None
            for i in range(dims.n_layers)]


def check_program(cfg, dims: Dims):
    """Raise where the program's ArchConfig is not this model."""
    def kind_of(k):  # (window or None, YaRN's numbers or None)
        y = k.yarn
        return (k.window or None, y and (
            y.factor, y.original_max_position, y.beta_fast, y.beta_slow,
            y.attention_factor))

    want_yarn = (dims.yarn_factor, dims.yarn_original_max_position,
                 dims.yarn_beta_fast, dims.yarn_beta_slow,
                 dims.yarn_attention_factor)
    want = [(w, None if w else want_yarn) for w in windows(dims)]
    have = [kind_of(cfg.attn_kind(i)) for i in range(cfg.n_layers)]
    sizes = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
             cfg.d_ff, cfg.n_experts, cfg.top_k, cfg.vocab, cfg.rope_base)
    mine = (dims.n_layers, dims.d_model, dims.n_heads, dims.n_kv_heads,
            dims.head_dim, dims.d_expert, dims.n_experts, dims.top_k,
            dims.vocab, dims.rope_theta)
    if sizes != mine or have != want:
        raise ValueError(f"program config {sizes} {have} differs from "
                         f"{mine} {want}")
    if (cfg.family != "moe" or cfg.moe_every != 1 or cfg.shared_expert
            or not cfg.gated or cfg.act != "silu"):
        raise ValueError("the reference models a decoder whose every MLP "
                         "is a sparse mixture of SwiGLU experts")


# -- weights ------------------------------------------------------------------

def layer_params(key, dims: Dims, layer) -> dict:
    """One layer's float32 weights; `layer` may be traced (vmap)."""
    lk = jax.random.fold_in(key, LAYER_BASE + layer)
    out = {}
    for i, name in enumerate(LINEARS):
        shape = dims.shape(name)
        w = jax.random.normal(jax.random.fold_in(lk, i), shape, jnp.float32)
        scale = 1.0 / shape[-2] ** 0.5
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


def runs(dims: Dims) -> list:
    """(first layer, count) of each run of consecutive layers of one
    kind: the program holds one stacked segment per run."""
    out = []
    for i in range(dims.n_layers):
        if out and dims.kind(i) == dims.kind(out[-1][0]):
            out[-1][1] += 1
        else:
            out.append([i, 1])
    return out


def program_params(key, dims: Dims, vocab_padded: int) -> dict:
    """Every weight in the parameter tree of
    `repro.models.lm.DecoderLM.init` for this model: one segment per
    run of layers of one kind, stacked over its layers.  Padded vocab
    rows and columns are zero.  Call it under `jax.jit`."""
    eh = embed_head(key, dims)
    pad = vocab_padded - dims.vocab
    segments = []
    for first, n in runs(dims):
        w = jax.vmap(lambda i: layer_params(key, dims, i))(
            first + jnp.arange(n))
        segments.append({
            "norm1": {"g": w["norm1"]},
            "attn": {n_: {"w": w[n_]} for n_ in ("wq", "wk", "wv", "wo")},
            "norm2": {"g": w["norm2"]},
            "moe": {"router": {"w": w["wr"]},
                    **{n_: w[n_] for n_ in EXPERT}},
        })
    return {
        "embed": {"table": jnp.pad(eh["embed"], ((0, pad), (0, 0)))},
        "norm_f": {"g": eh["norm_f"]},
        "head": {"w": jnp.pad(eh["head"], ((0, 0), (0, pad)))},
        "segments": segments,
    }


# -- forward pass -------------------------------------------------------------

def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def inv_freq(dims: Dims, yarn: bool) -> np.ndarray:
    """(head_dim / 2,) inverse frequencies, float64."""
    hd, base = dims.head_dim, dims.rope_theta
    inv = 1.0 / base ** (np.arange(0, hd, 2) / hd)
    if not yarn:
        return inv

    def pair_turning(n_rot):
        return (hd * math.log(dims.yarn_original_max_position
                              / (n_rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(pair_turning(dims.yarn_beta_fast)), 0)
    high = min(math.ceil(pair_turning(dims.yarn_beta_slow)), hd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(hd // 2) - low) / (high - low), 0.0, 1.0)
    return inv * (1.0 - ramp) + inv / dims.yarn_factor * ramp


def _rope(x, pos, inv, gain):
    """x (..., T, hd); rotates channel pairs (2i, 2i+1); cos and sin
    scaled by `gain`."""
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    c, s = jnp.cos(ang) * gain, jnp.sin(ang) * gain
    x1, x2 = x[..., 0::2], x[..., 1::2]
    y = jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], -1)
    return y.reshape(x.shape)


def _attention(dims: Dims, w, h, full, bits):
    """Windowed (full False) or causal attention over h (T, d); `full`
    may be traced."""
    T = h.shape[0]
    H, K, hd = dims.n_heads, dims.n_kv_heads, dims.head_dim
    pos = jnp.arange(T)
    inv = jnp.where(full, jnp.asarray(inv_freq(dims, True), jnp.float32),
                    jnp.asarray(inv_freq(dims, False), jnp.float32))
    gain = jnp.where(full, jnp.float32(dims.yarn_attention_factor), 1.0)
    q = jnp.dot(h, w["wq"], precision=HI).reshape(T, H, hd)
    k = jnp.dot(h, w["wk"], precision=HI).reshape(T, K, hd)
    v = jnp.dot(h, w["wv"], precision=HI).reshape(T, K, hd)
    q = _rope(q.transpose(1, 0, 2), pos, inv, gain)  # (H, T, hd)
    k = _rope(k.transpose(1, 0, 2), pos, inv, gain)  # (K, T, hd)
    v = v.transpose(1, 0, 2)
    g = H // K
    q = q.reshape(K, g, T, hd)
    reach = jnp.where(full, T, dims.window)  # positions a query sees
    outs = []
    for lo in range(0, T, BLOCK):  # query blocks bound the score tile
        qb = q[:, :, lo:lo + BLOCK]
        s = jnp.einsum("kgsd,ktd->kgst", qb, k, precision=HI) / hd ** 0.5
        i = (lo + jnp.arange(qb.shape[2]))[:, None]
        j = pos[None, :]
        s = jnp.where((j <= i) & (i - j < reach), s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("kgst,ktd->kgsd", p, v, precision=HI))
    ctx = jnp.concatenate(outs, axis=2).reshape(H, T, hd)
    ctx = ctx.transpose(1, 0, 2).reshape(T, H * hd)
    return jnp.dot(quantize_rows(ctx, bits), w["wo"], precision=HI)


def _experts(dims: Dims, w, h, bits):
    """The mixture over h (T, d): softmax router, top_k renormalised."""
    probs = jax.nn.softmax(jnp.dot(h, w["wr"], precision=HI), axis=-1)
    top, idx = jax.lax.top_k(probs, dims.top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    weight = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top)  # (T, E)

    def one(y, e):
        wg, wu, wd, we = e
        a = jax.nn.silu(jnp.dot(h, wg, precision=HI))
        a = a * jnp.dot(h, wu, precision=HI)
        out = jnp.dot(quantize_rows(a, bits), wd, precision=HI)
        return y + we[:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (w["wg"], w["wu"], w["wd"], weight.T))
    return y


@functools.partial(jax.jit, static_argnums=(1, 4))
def layer(key, dims: Dims, index, x, bits):
    w = layer_params(key, dims, index)
    w = {k: (jax.vmap(lambda m: quantize(m, bits, -1))(v) if k in EXPERT
             else quantize(v, bits, -1) if v.ndim == 2 else v)
         for k, v in w.items()}
    full = jnp.asarray([kd == "full" for kd in dims.pattern])[
        index % len(dims.pattern)]
    h = quantize_rows(_rms(x, w["norm1"], dims.norm_eps), bits)
    x = x + _attention(dims, w, h, full, bits)
    h = quantize_rows(_rms(x, w["norm2"], dims.norm_eps), bits)
    return x + _experts(dims, w, h, bits)


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

def token_weights(dims: Dims) -> int:
    """Weights one token passes through in one layer: the attention
    projections, the router and its top_k experts."""
    d, hd = dims.d_model, dims.head_dim
    attn = d * (dims.n_heads + 2 * dims.n_kv_heads) * hd
    attn += dims.n_heads * hd * d
    return attn + d * dims.n_experts + dims.top_k * 3 * d * dims.d_expert


def step_ops(rows, dims: Dims) -> int:
    """Integer operations one unified step needs; rows: (start, n) per
    real row.  Matrix products: 2 operations per weight per token that
    passes through it (routed experts only, `token_weights`); the
    vocabulary projection once per row.  Attention: 4 * head_dim per
    (query head, query, visible position), a sliding layer's positions
    capped at the window.  Norms, rotary, the softmaxes and the
    weighted sum of the experts are not counted."""
    tokens = sum(n for _, n in rows)
    ops = 2 * tokens * token_weights(dims) * dims.n_layers
    ops += 2 * len(rows) * dims.d_model * dims.vocab
    per_query = 4 * dims.head_dim * dims.n_heads
    for w in windows(dims):
        ops += per_query * sum(PAW.visible(s, n, w) for s, n in rows)
    return ops
