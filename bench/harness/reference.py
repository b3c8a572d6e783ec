"""The plain reference, run over a configuration's own model.

Each configuration names its reference (`"reference": "<name>"` in
`bench/configs/<config>.json`), a module `bench/references/<name>.py`
found by `harness.spec.reference`.  It holds everything that depends
on the model's architecture, and imports nothing of the program: the
benchmark owns the weights, hands them to the program to calibrate and
deploy, and the reference regenerates the same numbers on its own, one
layer at a time on the device, so that it takes nothing the program
has made and fits beside nothing else.  A reference module provides:

  Dims                  a frozen dataclass of the model's sizes, with
                        `Dims.from_config(config["model"])`; its fields
                        include n_layers, n_heads, n_kv_heads and
                        head_dim, which `counts/paged_attention.py` reads
  program_params(key, dims, vocab_padded)
                        the float32 weights in the parameter tree of the
                        program's `DecoderLM.init`; run under `jax.jit`
  check_program(cfg, dims)
                        raises where the program's `ArchConfig` is not
                        the model of these sizes
  embed(key, dims, tokens, bits) -> x (T, d)
  layer(key, dims, index, x, bits) -> x
  head_rows(key, dims, x, rows, targets, bits)
                        -> (max logit, logit of target, argmax) per row
                        the forward pass in float32, every product at
                        `HI`; each jitted with `dims` and `bits` static.
                        With `bits` set, every weight matrix is rounded
                        by `quantize` and every input of a matrix
                        product by `quantize_rows`: the control that a
                        comparison has to reject
  step_ops(rows, dims)  integer operations one unified step needs, rows
                        (start, n) per real row (`mfu.decode`)

This module keeps what is the same for every model: the padding of a
sequence, the head read in blocks of rows, and the gaps the check
compares.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# every product at HIGHEST: a TPU would otherwise round float32 operands
# to bfloat16
HI = jax.lax.Precision.HIGHEST
BLOCK = 512  # sequences are padded to a multiple of this; causal, so
# padding behind the last real token changes nothing before it


def quantize(w, bits, axis):
    """Round `w` to a symmetric `bits`-bit grid, one step per slice
    along `axis` (the output channel); `bits` None leaves it."""
    if bits is None:
        return w
    top = 2 ** (bits - 1) - 1
    red = tuple(a for a in range(w.ndim) if a != axis % w.ndim)
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=red, keepdims=True), 1e-12)
    s = s / top
    return jnp.clip(jnp.round(w / s), -top, top) * s


def quantize_rows(x, bits):
    """Round activations `x` (rows, features) to a symmetric `bits`-bit
    grid, one step per row (token)."""
    return x if bits is None else quantize(x, bits, 0)


def rows(ref, key, dims, tokens, rows, targets, *, length: int,
         bits=None):
    """Run reference `ref` over `tokens` (T,), padded to `length` (a
    multiple of BLOCK, so that one program serves every request of a
    cell), and read, at each of `rows`, the largest logit, the logit of
    `targets` and the argmax token.  Returns three numpy arrays of
    len(rows)."""
    tokens = np.asarray(tokens, np.int32)
    if len(tokens) > length or length % BLOCK:
        raise ValueError(f"{len(tokens)} tokens do not pad to {length}")
    padded = np.zeros(length, np.int32)
    padded[:len(tokens)] = tokens
    x = ref.embed(key, dims, jnp.asarray(padded), bits)
    for layer in range(dims.n_layers):
        x = ref.layer(key, dims, jnp.int32(layer), x, bits)
    outs = []
    for lo in range(0, len(rows), BLOCK):  # the head in blocks of rows
        r = np.zeros(BLOCK, np.int32)
        t = np.zeros(BLOCK, np.int32)
        n = min(BLOCK, len(rows) - lo)
        r[:n], t[:n] = rows[lo:lo + n], targets[lo:lo + n]
        o = ref.head_rows(key, dims, x, jnp.asarray(r), jnp.asarray(t),
                          bits)
        outs.append([np.asarray(a)[:n] for a in o])
    return tuple(np.concatenate(c) for c in zip(*outs))


def padded_length(tokens: int) -> int:
    return -(-tokens // BLOCK) * BLOCK


def served_gaps(ref, key, dims, prompt, served, *, length: int,
                bits=None):
    """Gap by which each served token's logit lies below the model's
    best at its position: max(logits) - logit(served), >= 0."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)])
    P, n = len(prompt), len(served)
    rws = np.arange(P - 1, P - 1 + n)
    best, at, _ = rows(ref, key, dims, seq[:P - 1 + n], rws, served,
                       length=length, bits=bits)
    return best - at


def control_gaps(ref, key, dims, prompt, served, bits: int, *,
                 length: int):
    """The control at the same prompt and tokens: at each position the
    token that the `bits`-bit model puts first, read against the
    float32 reference."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)])
    P, n = len(prompt), len(served)
    rws = np.arange(P - 1, P - 1 + n)
    tokens = seq[:P - 1 + n]
    _, _, pick = rows(ref, key, dims, tokens, rws, served, length=length,
                      bits=bits)
    best, at, _ = rows(ref, key, dims, tokens, rws, pick, length=length)
    return best - at
