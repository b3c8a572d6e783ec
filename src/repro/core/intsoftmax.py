"""Integer-only softmax (beyond-paper; shrinks the §3.8 attention island).

The paper assigns exponentials to real-valued fallback (§3.8).  I-BERT
(Kim et al., 2021) showed exp can stay integer with a polynomial on a
bounded range; we adapt that to NEMO's staircase formalism:

  exp(x) for x <= 0 is decomposed as  exp(x) = 2^(-z) * exp(r),
  z = floor(-x / ln2),  r = x + z*ln2 in (-ln2, 0];
  exp(r) is a LUT over the r-quantized grid (256 entries — exactly the
  paper's Eq. 8 staircase with enumerated thresholds);
  the 2^(-z) factor is a right shift of the LUT output.

Pipeline (all int32):
  s        : integer scores, quantum eps_s
             (attention: eps_q*eps_k/sqrt(hd))
  m        : rowmax(s)                           (integer max)
  t        : s - m                               (<= 0)
  z        : (t * m_ln2) >> d_ln2                (fixed-point /ln2, negated)
  r_img    : t + (z * ln2_img)                   (in ln2-quantum units)
  e        : LUT[r_img] >> z                     (Q-bit exp image, eps=1/2^Q)
  p_img    : (e * 2^Q) / sum(e)                  (one integer divide per row)

Output: probability image in [0, 127] with quantum 1/127 — identical
interface to the float-island path, so attention can swap islands
(`QAttention.apply_id(..., int_softmax=True)`).  Error vs float
softmax <= ~1% (test).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

EXP_BITS = 14          # exp LUT output precision
R_LEVELS = 256         # staircase resolution over (-ln2, 0]


def make_int_softmax_tables(eps_s: float) -> dict:
    """Static tables for score quantum eps_s (host-side, transform time)."""
    ln2 = float(np.log(2.0))
    # z = floor(-t*eps_s/ln2)  ->  fixed-point multiplier
    d_ln2 = 24
    m_ln2 = int(np.floor(eps_s / ln2 * (1 << d_ln2)))
    # r = t + z * (ln2/eps_s)  (in score-quantum units), r in (-ln2/eps_s, 0]
    ln2_img = int(np.round(ln2 / eps_s))
    # LUT over r in quantized steps: index = floor(-r / step), step chosen
    # so 256 entries span (-ln2, 0]
    step = max(1, int(np.ceil(ln2_img / R_LEVELS)))
    r_real = -np.arange(R_LEVELS) * step * eps_s
    lut = np.round(np.exp(r_real) * (1 << EXP_BITS)).astype(np.int32)
    return {
        "m_ln2": np.int32(m_ln2), "d_ln2": np.int32(d_ln2),
        "ln2_img": np.int32(ln2_img), "r_step": np.int32(step),
        "exp_lut": lut,
    }


def int_softmax(s, tables, *, axis: int = -1, mask=None, p_bits: int = 7):
    """Integer softmax: s int32 scores -> probability image int8.

    mask: optional bool (True = keep).  Output quantum 1/(2^p_bits - 1),
    zero-point 0 (matches the attention island contract).
    """
    s = s.astype(jnp.int32)
    neg_inf = jnp.int32(-(2 ** 30))
    if mask is not None:
        s = jnp.where(mask, s, neg_inf)
    m = jnp.max(s, axis=axis, keepdims=True)
    t = s - m                                     # <= 0
    # z = floor(-t * eps_s / ln2) via fixed point; t >= -2^26 guard
    t_c = jnp.maximum(t, -(2 ** 26))
    z = jnp.right_shift((-t_c) * tables["m_ln2"] >> 12, 12)  # staged x2
    z = jnp.minimum(z, EXP_BITS + 16)
    r = t_c + z * tables["ln2_img"]               # (-ln2_img, 0] approx
    idx = jnp.clip((-r) // tables["r_step"], 0, R_LEVELS - 1)
    e = jnp.take(jnp.asarray(tables["exp_lut"]), idx, axis=0)
    e = jnp.right_shift(e, jnp.minimum(z, 31))    # 2^-z factor
    e = jnp.where(t <= -(2 ** 26), 0, e)          # masked lanes -> 0
    denom = jnp.maximum(jnp.sum(e, axis=axis, keepdims=True), 1)
    pmax = (1 << p_bits) - 1
    # rounded division (floor biases the probability mass ~15% low)
    p = (e * pmax + jnp.right_shift(denom, 1)) // denom
    return jnp.clip(p, 0, pmax).astype(jnp.int8)


def int_softmax_ref_float(
    s, eps_s: float, *, axis: int = -1, mask=None, p_bits: int = 7
):
    """Float oracle: softmax(s*eps_s) quantized to the same image grid."""
    x = s.astype(jnp.float32) * eps_s
    if mask is not None:
        x = jnp.where(mask, x, -1e9)
    p = jax.nn.softmax(x, axis=axis)
    pmax = (1 << p_bits) - 1
    return jnp.round(p * pmax).astype(jnp.int8)
