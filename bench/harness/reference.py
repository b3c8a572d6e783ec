"""The plain reference: a dense pre-norm decoder in float32 jax.numpy.

No kernel, cache, batching or quantization: token embedding, then per
layer RMSNorm -> grouped-query attention with rotary embeddings on the
leading `rope_fraction` of each head (channel pairs (2i, 2i+1), causal
softmax over q.k / sqrt(head_dim)) -> residual add -> RMSNorm -> SwiGLU
(silu(x Wg) * (x Wu)) Wd -> residual add, then a final RMSNorm and the
vocabulary projection.  Query head h reads kv head h // (heads / kv
heads).  Every product runs at `Precision.HIGHEST`, since a TPU would
otherwise round float32 operands to bfloat16.

It imports nothing of the program: its weights come from
`harness.weights`, regenerated here layer by layer on the device, so
that it fits beside nothing else.

`bits` computes the same model with every weight matrix rounded to a
symmetric `bits`-bit grid per output channel and every input of a
matrix product to one per token: the control that a comparison has to
reject.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness.weights import Dims, embed_head, layer_weights

HI = jax.lax.Precision.HIGHEST
BLOCK = 512  # sequences are padded to a multiple of this; causal, so
# padding behind the last real token changes nothing before it


def _q(w, bits, axis):
    """Round `w` to a symmetric `bits`-bit grid, one step per slice
    along `axis` (the output channel)."""
    if bits is None:
        return w
    top = 2 ** (bits - 1) - 1
    red = tuple(a for a in range(w.ndim) if a != axis % w.ndim)
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=red, keepdims=True), 1e-12)
    s = s / top
    return jnp.clip(jnp.round(w / s), -top, top) * s


def _qa(x, bits):
    """Round activations `x` (rows, features) to a symmetric `bits`-bit
    grid, one step per row (token)."""
    return x if bits is None else _q(x, bits, 0)


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
def _layer(key, dims: Dims, layer, x, bits):
    w = layer_weights(key, dims, layer)
    w = {k: (_q(v, bits, -1) if v.ndim == 2 else v) for k, v in w.items()}
    T = x.shape[0]
    H, K, hd = dims.n_heads, dims.n_kv_heads, dims.head_dim
    pos = jnp.arange(T)
    h = _qa(_rms(x, w["norm1"], dims.norm_eps), bits)
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
    x = x + jnp.dot(_qa(ctx, bits), w["wo"], precision=HI)
    h = _qa(_rms(x, w["norm2"], dims.norm_eps), bits)
    a = jax.nn.silu(jnp.dot(h, w["wg"], precision=HI))
    a = a * jnp.dot(h, w["wu"], precision=HI)
    return x + jnp.dot(_qa(a, bits), w["wd"], precision=HI)


@functools.partial(jax.jit, static_argnums=(1, 3))
def _embed(key, dims: Dims, tokens, bits):
    return _q(embed_head(key, dims)["embed"], bits, 0)[tokens]


@functools.partial(jax.jit, static_argnums=(1, 5))
def _head_rows(key, dims: Dims, x, rows, targets, bits):
    """(max logit, logit of target, argmax) at the given rows."""
    eh = embed_head(key, dims)
    h = _qa(_rms(x[rows], eh["norm_f"], dims.norm_eps), bits)
    logits = jnp.dot(h, _q(eh["head"], bits, -1), precision=HI)
    at = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
    return jnp.max(logits, -1), at, jnp.argmax(logits, -1)


def rows(key, dims: Dims, tokens, rows, targets, *, length: int,
         bits=None):
    """Run the model over `tokens` (T,), padded to `length` (a multiple
    of BLOCK, so that one program serves every request of a cell), and
    read, at each of `rows`, the largest logit, the logit of `targets`
    and the argmax token.  Returns three numpy arrays of len(rows)."""
    tokens = np.asarray(tokens, np.int32)
    if len(tokens) > length or length % BLOCK:
        raise ValueError(f"{len(tokens)} tokens do not pad to {length}")
    padded = np.zeros(length, np.int32)
    padded[:len(tokens)] = tokens
    x = _embed(key, dims, jnp.asarray(padded), bits)
    for layer in range(dims.n_layers):
        x = _layer(key, dims, jnp.int32(layer), x, bits)
    outs = []
    for lo in range(0, len(rows), BLOCK):  # the head in blocks of rows
        r = np.zeros(BLOCK, np.int32)
        t = np.zeros(BLOCK, np.int32)
        n = min(BLOCK, len(rows) - lo)
        r[:n], t[:n] = rows[lo:lo + n], targets[lo:lo + n]
        o = _head_rows(key, dims, x, jnp.asarray(r), jnp.asarray(t), bits)
        outs.append([np.asarray(a)[:n] for a in o])
    return tuple(np.concatenate(c) for c in zip(*outs))


def padded_length(tokens: int) -> int:
    return -(-tokens // BLOCK) * BLOCK


def served_gaps(key, dims: Dims, prompt, served, *, length: int,
                bits=None):
    """Gap by which each served token's logit lies below the model's
    best at its position: max(logits) - logit(served), >= 0."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)])
    P, n = len(prompt), len(served)
    rws = np.arange(P - 1, P - 1 + n)
    best, at, _ = rows(key, dims, seq[:P - 1 + n], rws, served,
                       length=length, bits=bits)
    return best - at


def control_gaps(key, dims: Dims, prompt, served, bits: int, *,
                 length: int):
    """The control at the same prompt and tokens: at each position the
    token that the `bits`-bit model puts first, read against the
    float32 reference."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)])
    P, n = len(prompt), len(served)
    rws = np.arange(P - 1, P - 1 + n)
    tokens = seq[:P - 1 + n]
    _, _, pick = rows(key, dims, tokens, rws, served, length=length,
                      bits=bits)
    best, at, _ = rows(key, dims, tokens, rws, pick, length=length)
    return best - at
