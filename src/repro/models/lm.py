"""The unified decoder LM covering all assigned architecture families.

Layer stacks are lax.scan'ed over stacked parameters (bounds HLO size at
96 layers x 512 devices).  The segment plan per family:

  dense                : [scan(DenseBlock) x L]
  moe  (moe_every = 1) : [scan(DenseBlock+MoE) x L]
  moe  (moe_every = 2) : [scan(pair: dense -> moe) x L/2]
  ssm                  : [scan(MambaBlock) x L]
  hybrid (zamba2)      : [scan(group: k x Mamba2 + shared-attn) x G, tail]

A dense or moe_every = 1 model whose layers mix attention kinds
(`ArchConfig.attn_pattern`: sliding window, YaRN) scans each run of
consecutive layers of one kind as a segment of its own.

Lifecycle: init (FP) -> calibrate (FP, eager per-layer scopes) ->
deploy (host, per-layer tables -> stacked) -> ID apply (scan over tables).
FQ uses the same apply with rep=Rep.FQ + a qstate pytree (PACT clips).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.calibrate import Calibrator
from repro.core.rep import Rep
from repro.layers.attention import INACTIVE_POS
from repro.layers.common import ActKind, DeployCtx, stack_trees
from repro.layers.embedding import QEmbed
from repro.layers.linear import QLinear
from repro.layers.norms import QNorm
from repro.models.blocks import DenseBlock, MambaBlock, SharedAttnBlock

ACT_MAP = {
    "silu": ActKind.SILU,
    "gelu": ActKind.GELU,
    "relu": ActKind.RELU,
    "relu2": ActKind.RELU2,
}


def _tree_slice(tree, i):
    return jax.tree.map(lambda x: x[i], tree)


@dataclasses.dataclass(frozen=True)
class DecoderLM:
    cfg: ArchConfig
    max_seq: int = 4096

    # ------------------------------------------------------------------
    # segment plan
    # ------------------------------------------------------------------
    def _dense_tpl(self, moe: bool, kind=None) -> DenseBlock:
        c = self.cfg
        kind = kind or c.attn_kind(0)
        return DenseBlock(
            d_model=c.d_model, n_heads=c.n_heads, n_kv_heads=c.n_kv_heads,
            head_dim=c.hd, d_ff=c.d_ff, act=ACT_MAP[c.act], gated=c.gated,
            norm=c.norm, norm_bias=c.norm_bias, rope_base=c.rope_base,
            rope_fraction=c.rope_fraction, max_seq=self.max_seq,
            n_experts=(c.n_experts if moe else 0), top_k=c.top_k,
            moe_group=c.moe_group, shared_expert=(c.shared_expert and moe),
            window=kind.window, yarn=kind.yarn,
        )

    def _mamba_tpl(self) -> MambaBlock:
        c = self.cfg
        return MambaBlock(
            d_model=c.d_model,
            ssm_kind=c.ssm_kind,
            d_state=c.ssm_state,
            expand=c.ssm_expand,
            head_dim=c.ssm_head_dim,
            norm=c.norm,
        )

    def _shared_tpl(self) -> SharedAttnBlock:
        c = self.cfg
        return SharedAttnBlock(
            d_model=c.d_model,
            n_heads=c.n_heads,
            n_kv_heads=c.n_kv_heads,
            head_dim=c.hd,
            max_seq=self.max_seq,
            norm=c.norm,
        )

    def _runs(self, moe: bool):
        """One "dense" segment per run of consecutive layers that share
        an attention kind (configs.base.AttnKind): a sliding-window
        layer and a full one are different templates."""
        segs = []
        for i in range(self.cfg.n_layers):
            kind = self.cfg.attn_kind(i)
            if segs and segs[-1][0] == kind:
                segs[-1][1] += 1
            else:
                segs.append([kind, 1])
        return [("dense", self._dense_tpl(moe, kind), n)
                for kind, n in segs]

    def plan(self):
        """-> list of segments: (kind, template(s), n_steps)."""
        c = self.cfg
        if c.family == "dense" or (
            c.family == "moe" and c.moe_every == 1 and c.n_experts == 0
        ):
            return self._runs(False)
        if c.family == "moe" and c.moe_every == 1:
            return self._runs(True)
        if c.family == "moe" and c.moe_every == 2:
            assert c.n_layers % 2 == 0
            pair = (self._dense_tpl(False), self._dense_tpl(True))
            return [("pair", pair, c.n_layers // 2)]
        if c.family == "ssm":
            return [("mamba", self._mamba_tpl(), c.n_layers)]
        if c.family == "hybrid":
            k = c.shared_attn_every
            groups, tail = divmod(c.n_layers, k)
            segs = [
                ("hybrid", (self._mamba_tpl(), self._shared_tpl()), groups)
            ]
            if tail:
                segs.append(("mamba", self._mamba_tpl(), tail))
            return segs
        raise ValueError(c.family)

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    def init(self, key) -> dict:
        c = self.cfg
        keys = jax.random.split(key, 8)
        p: Dict[str, Any] = {}
        if c.input_mode == "tokens":
            p["embed"] = QEmbed(c.vocab_padded, c.d_model).init(keys[0])
        p["norm_f"] = QNorm(c.d_model, kind=c.norm,
                            use_bias=c.norm_bias).init(keys[1])
        p["head"] = QLinear(c.d_model, c.vocab_padded,
                            per_channel=False).init(keys[2])
        segs = []
        kidx = 3
        for si, (kind, tpl, n) in enumerate(self.plan()):
            seg_key = (keys[kidx + si] if kidx + si < 7
                       else jax.random.fold_in(keys[7], si))
            layer_keys = jax.random.split(seg_key, n)
            if kind in ("dense", "mamba"):
                stacked = jax.vmap(tpl.init)(layer_keys)
            elif kind == "pair":
                a, b = tpl
                k2 = jax.vmap(lambda k: jax.random.split(k))(layer_keys)
                stacked = {
                    "a": jax.vmap(a.init)(k2[:, 0]),
                    "b": jax.vmap(b.init)(k2[:, 1]),
                }
            elif kind == "hybrid":
                mam, sha = tpl
                k = self.cfg.shared_attn_every
                km = jax.vmap(
                    lambda kk: jax.random.split(kk, k))(layer_keys)
                stacked = {"m": jax.vmap(jax.vmap(mam.init))(km)}
            segs.append(stacked)
        p["segments"] = segs
        if c.family == "hybrid":
            p["shared_attn"] = self._shared_tpl().init(keys[7])
        return p

    def init_qstate(self) -> dict:
        qs_segs = []
        for kind, tpl, n in self.plan():
            if kind == "dense":
                one = tpl.init_qstate()
                qs_segs.append(jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (n,) + x.shape), one))
            elif kind == "pair":
                a, b = tpl
                qs_segs.append({
                    "a": jax.tree.map(
                        lambda x: jnp.broadcast_to(x, (n,) + x.shape),
                        a.init_qstate()),
                    "b": jax.tree.map(
                        lambda x: jnp.broadcast_to(x, (n,) + x.shape),
                        b.init_qstate()),
                })
            else:
                qs_segs.append({})
        return {"segments": qs_segs}

    # ------------------------------------------------------------------
    # float forward (FP / FQ)
    # ------------------------------------------------------------------
    def embed_in(self, p, batch, rep, calib=None):
        c = self.cfg
        if c.input_mode == "tokens":
            return QEmbed(c.vocab_padded, c.d_model).apply(
                p["embed"], batch, rep, calib=calib, scope="")
        return batch  # embeds provided by the (stubbed) modality frontend

    def apply(
        self, p, x, rep, *, qstate=None, caches=None, pos=None, calib=None,
        valid=None, paged_kernel=True,
    ):
        """x: embedded input (B,S,d) float. -> (hidden, caches, aux_sum)

        valid (B, S) bool (ID only): the real tokens of a serving
        dispatch; MoE layers route the others nowhere.
        paged_kernel (ID only): attention over paged caches runs the
        fused paged kernel; False runs the write-then-gather oracle."""
        c = self.cfg
        aux_total = jnp.float32(0.0)
        new_caches = []
        x0 = x  # hybrid shared-attn side input
        ci = 0
        for si, (kind, tpl, n) in enumerate(self.plan()):
            seg_p = p["segments"][si]
            seg_qs = (
                (qstate or {}).get("segments", [None] * 8)[si]
                if qstate
                else None
            )
            if calib is not None:
                # eager per-layer walk with unique scopes
                x, caches_i, aux = self._seg_eager(
                    kind, tpl, seg_p, seg_qs, x, x0, rep,
                    caches[ci] if caches else None, pos, calib,
                    f"S{si}.", p)
            else:
                x, caches_i, aux = self._seg_scan(
                    kind, tpl, seg_p, seg_qs, x, x0, rep,
                    caches[ci] if caches else None, pos, p, valid,
                    paged_kernel)
            aux_total = aux_total + aux
            new_caches.append(caches_i)
            ci += 1
        return x, (new_caches if caches else None), aux_total

    def _seg_eager(
        self,
        kind,
        tpl,
        seg_p,
        seg_qs,
        x,
        x0,
        rep,
        caches,
        pos,
        calib,
        scope,
        p_root,
    ):
        """Python loop over layers (calibration: unique scope per layer)."""
        aux_total = jnp.float32(0.0)
        n = (
            jax.tree.leaves(seg_p)[0].shape[0]
            if kind != "pair"
            else jax.tree.leaves(seg_p["a"])[0].shape[0]
        )
        outs = []
        for i in range(n):
            sc = f"{scope}L{i}."
            cache_i = _tree_slice(caches, i) if caches is not None else None
            if kind == "dense":
                x, cache_i, aux = tpl.apply_float(
                    _tree_slice(seg_p, i), x, rep,
                    qs=_tree_slice(seg_qs, i) if seg_qs else None,
                    cache=cache_i, pos=pos, calib=calib, scope=sc)
                aux_total += (aux if aux is not None else 0.0)
            elif kind == "mamba":
                x, cache_i, _ = tpl.apply_float(
                    _tree_slice(seg_p, i), x, rep, cache=cache_i, pos=pos,
                    calib=calib, scope=sc)
            elif kind == "pair":
                a, b = tpl
                ca = _tree_slice(cache_i, 0) if cache_i is not None else None
                cb = _tree_slice(cache_i, 1) if cache_i is not None else None
                x, ca, _ = a.apply_float(
                    _tree_slice(seg_p["a"], i), x, rep,
                    qs=_tree_slice(seg_qs["a"], i) if seg_qs else None,
                    cache=ca, pos=pos, calib=calib, scope=sc + "a.")
                x, cb, aux = b.apply_float(
                    _tree_slice(seg_p["b"], i), x, rep,
                    qs=_tree_slice(seg_qs["b"], i) if seg_qs else None,
                    cache=cb, pos=pos, calib=calib, scope=sc + "b.")
                aux_total += (aux if aux is not None else 0.0)
                cache_i = jax.tree.map(
                    lambda a_, b_: jnp.stack([a_, b_]), ca, cb
                ) if ca is not None else None
            elif kind == "hybrid":
                mam, sha = tpl
                k = self.cfg.shared_attn_every
                cm = (
                    _tree_slice(cache_i, slice(0, k))
                    if cache_i is not None
                    else None
                )
                for j in range(k):
                    cmj = _tree_slice(cm, j) if cm is not None else None
                    x, cmj, _ = mam.apply_float(
                        _tree_slice(_tree_slice(seg_p["m"], i), j), x, rep,
                        cache=cmj, pos=pos, calib=calib, scope=f"{sc}m{j}.")
                cs = cache_i["sh"] if cache_i is not None else None
                x, cs, _ = sha.apply_float(
                    p_root["shared_attn"], x, x0, rep, cache=cs, pos=pos,
                    calib=calib, scope=sc + "sh.")
                cache_i = None  # eager path: caches unsupported for hybrid
            outs.append(cache_i)
        caches_out = stack_trees(outs) if (caches is not None) else None
        return x, caches_out, aux_total

    def _seg_scan(
        self, kind, tpl, seg_p, seg_qs, x, x0, rep, caches, pos, p_root,
        valid=None, paged_kernel=True,
    ):
        """lax.scan over stacked layer params (jit path)."""
        c = self.cfg
        aux0 = jnp.float32(0.0)

        if kind in ("dense", "mamba"):
            def body(carry, xs):
                h, aux = carry
                lp, lqs, lc = xs
                if rep is Rep.ID:
                    kw = ({"valid": valid, "paged_kernel": paged_kernel}
                          if kind == "dense" else {})
                    h2, lc2 = tpl.apply_id(lp, h, cache=lc, pos=pos, **kw)
                    a2 = aux
                else:
                    h2, lc2, a = tpl.apply_float(
                        lp, h, rep, qs=lqs, cache=lc, pos=pos
                    )
                    a2 = aux + (a if a is not None else 0.0)
                return (h2, a2), lc2

            if (c.family != "cnn" and rep in (Rep.FP, Rep.FQ)
                    and c.n_layers > 1):
                body = jax.checkpoint(body)  # remat per layer for train
            qs_xs = seg_qs if seg_qs else None
            (x, aux), caches_out = jax.lax.scan(
                body, (x, aux0),
                (seg_p, qs_xs, caches) if caches is not None
                else (seg_p, qs_xs, None))
            return x, caches_out, aux

        if kind == "pair":
            a_tpl, b_tpl = tpl

            def body(carry, xs):
                h, aux = carry
                lp, lqs, lc = xs
                ca = _tree_slice(lc, 0) if lc is not None else None
                cb = _tree_slice(lc, 1) if lc is not None else None
                if rep is Rep.ID:
                    h, ca2 = a_tpl.apply_id(lp["a"], h, cache=ca, pos=pos,
                                            valid=valid,
                                            paged_kernel=paged_kernel)
                    h, cb2 = b_tpl.apply_id(lp["b"], h, cache=cb, pos=pos,
                                            valid=valid,
                                            paged_kernel=paged_kernel)
                    a_sum = aux
                else:
                    h, ca2, _ = a_tpl.apply_float(
                        lp["a"], h, rep,
                        qs=lqs["a"] if lqs else None, cache=ca, pos=pos)
                    h, cb2, aux_b = b_tpl.apply_float(
                        lp["b"], h, rep,
                        qs=lqs["b"] if lqs else None, cache=cb, pos=pos)
                    a_sum = aux + (aux_b if aux_b is not None else 0.0)
                lc2 = (
                    jax.tree.map(lambda u, v: jnp.stack([u, v]), ca2, cb2)
                    if ca2 is not None
                    else None
                )
                return (h, a_sum), lc2

            if rep in (Rep.FP, Rep.FQ):
                body = jax.checkpoint(body)
            (x, aux), caches_out = jax.lax.scan(
                body, (x, aux0), (seg_p, seg_qs, caches))
            return x, caches_out, aux

        if kind == "hybrid":
            mam_tpl, sha_tpl = tpl
            k = c.shared_attn_every
            sh_p = p_root.get("shared_attn")

            def body(carry, xs):
                h, aux = carry
                lp, lc = xs

                def mbody(hh, mxs):
                    mp, mc = mxs
                    if rep is Rep.ID:
                        h2, mc2 = mam_tpl.apply_id(mp, hh, cache=mc, pos=pos)
                    else:
                        h2, mc2, _ = mam_tpl.apply_float(
                            mp, hh, rep, cache=mc, pos=pos
                        )
                    return h2, mc2

                mc_in = lc["m"] if lc is not None else None
                h, mc_out = jax.lax.scan(mbody, h, (lp["m"], mc_in))
                sc_in = lc["sh"] if lc is not None else None
                if rep is Rep.ID:
                    h, sc_out = sha_tpl.apply_id(
                        lp["sh"], h, x0, cache=sc_in, pos=pos,
                        paged_kernel=paged_kernel,
                    )
                else:
                    h, sc_out, _ = sha_tpl.apply_float(
                        sh_p, h, x0, rep, cache=sc_in, pos=pos
                    )
                lc2 = {"m": mc_out, "sh": sc_out} if lc is not None else None
                return (h, aux), lc2

            if rep in (Rep.FP, Rep.FQ):
                body = jax.checkpoint(body)
            # ID: seg_p carries per-application shared-attn tables ("sh");
            # FP/FQ: the single shared weight set rides in the closure.
            (x, aux), caches_out = jax.lax.scan(body, (x, aux0),
                                                (seg_p, caches))
            return x, caches_out, aux
        raise ValueError(kind)

    # ------------------------------------------------------------------
    # heads / losses
    # ------------------------------------------------------------------
    def logits(self, p, x, rep, calib=None):
        c = self.cfg
        h = QNorm(c.d_model, kind=c.norm, use_bias=c.norm_bias).apply(
            p["norm_f"], x, rep, calib=calib, scope="final.")
        if calib is not None:
            calib.observe("final.head_in", h)
        from repro.sharding.hints import hint

        head = QLinear(c.d_model, c.vocab_padded, per_channel=False)
        logits = hint(head.apply(p["head"], h, rep), "logits")
        if c.vocab_padded != c.vocab:  # mask padded vocab slots
            mask = jnp.arange(c.vocab_padded) < c.vocab
            logits = jnp.where(mask, logits, jnp.asarray(-1e9, logits.dtype))
        return logits

    def loss_fn(self, p, qstate, tokens, rep, calib=None):
        """Next-token cross entropy (+ MoE aux). tokens (B, S+1) int32."""
        inp, tgt = tokens[:, :-1], tokens[:, 1:]
        x = self.embed_in(p, inp, rep, calib=calib)
        if calib is None:  # mixed-precision training (f32 params)
            x = x.astype(jnp.bfloat16)
        x, _, aux = self.apply(p, x, rep, qstate=qstate, calib=calib)
        logits = self.logits(p, x, rep, calib=calib).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        return jnp.mean(nll) + 0.01 * aux

    def loss_fn_embeds(self, p, qstate, embeds, tgt, rep):
        x, _, aux = self.apply(
            p, embeds.astype(jnp.bfloat16), rep, qstate=qstate
        )
        logits = self.logits(p, x, rep).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        return jnp.mean(nll) + 0.01 * aux

    # ------------------------------------------------------------------
    # calibration + deploy
    # ------------------------------------------------------------------
    def calibrate(self, p, sample, *, n_batches: int = 1) -> Calibrator:
        """FP run(s) with per-layer scopes; sample: tokens or embeds."""
        calib = Calibrator()
        x = self.embed_in(p, sample, Rep.FP, calib=calib)
        x, _, _ = self.apply(p, x, Rep.FP, calib=calib)
        self.logits(p, x, Rep.FP, calib=calib)
        return calib

    def deploy(
        self,
        p,
        calib: Optional[Calibrator],
        *,
        factor: int = 256,
        eps_in: Optional[float] = None,
    ) -> dict:
        """-> ID params: integer tables, stacked to mirror the plan."""
        c = self.cfg
        ctx = DeployCtx(calib=calib, factor=factor)
        p_np = jax.tree.map(np.asarray, p)
        t: Dict[str, Any] = {"meta": {}}
        if c.input_mode == "tokens":
            emb = QEmbed(c.vocab_padded, c.d_model)
            te, eps_x, _ = emb.deploy(ctx, p_np["embed"])
            t["embed"] = te
        else:
            eps_x = eps_in or (2.0 * 8.0 / 255.0)
        t["meta"]["eps_in"] = eps_x
        segs_t = []
        for si, (kind, tpl, n) in enumerate(self.plan()):
            seg_p = p_np["segments"][si]
            layer_tables = []
            for i in range(n):
                sc = f"S{si}.L{i}."
                if kind == "dense":
                    ti, eps_x = tpl.deploy(
                        ctx, sc, _tree_slice(seg_p, i), eps_x
                    )
                elif kind == "mamba":
                    ti, eps_x = tpl.deploy(
                        ctx, sc, _tree_slice(seg_p, i), eps_x
                    )
                elif kind == "pair":
                    a, b = tpl
                    ta, eps_x = a.deploy(
                        ctx, sc + "a.", _tree_slice(seg_p["a"], i), eps_x
                    )
                    tb, eps_x = b.deploy(
                        ctx, sc + "b.", _tree_slice(seg_p["b"], i), eps_x
                    )
                    ti = {"a": ta, "b": tb}
                elif kind == "hybrid":
                    mam, sha = tpl
                    k = c.shared_attn_every
                    tms = []
                    for j in range(k):
                        tm, eps_x = mam.deploy(
                            ctx, f"{sc}m{j}.",
                            _tree_slice(_tree_slice(seg_p["m"], i), j), eps_x)
                        tms.append(tm)
                    tsh, eps_x = sha.deploy(ctx, sc + "sh.",
                                            p_np["shared_attn"], eps_x,
                                            t["meta"]["eps_in"])
                    ti = {"m": stack_trees(tms), "sh": tsh}
                layer_tables.append(ti)
            segs_t.append(stack_trees(layer_tables))
        t["segments"] = segs_t
        qn = QNorm(c.d_model, kind=c.norm, use_bias=c.norm_bias)
        tn, eps_h, _ = qn.deploy(ctx, "final.", p_np["norm_f"], eps_x)
        t["norm_f"] = tn
        head = QLinear(c.d_model, c.vocab_padded, per_channel=False)
        th, eps_logits = head.deploy(p_np["head"], eps_h, 0)
        t["head"] = th
        t["meta"]["eps_logits"] = float(np.max(eps_logits))
        return t

    # ------------------------------------------------------------------
    # serving (ID)
    # ------------------------------------------------------------------
    def embed_in_id(self, t, batch):
        c = self.cfg
        if c.input_mode == "tokens":
            return QEmbed(c.vocab_padded, c.d_model).apply_id(
                t["embed"], batch)
        return batch  # already int8 images (frontend stub quantizes)

    def logits_id(self, t, s_x):
        c = self.cfg
        h = QNorm(c.d_model, kind=c.norm, use_bias=c.norm_bias).apply_id(
            t["norm_f"], s_x)
        from repro.sharding.hints import hint

        head = QLinear(c.d_model, c.vocab_padded, per_channel=False)
        logits = hint(head.apply_id(t["head"], h), "logits")
        if c.vocab_padded != c.vocab:  # integer mask for padded slots
            mask = jnp.arange(c.vocab_padded) < c.vocab
            logits = jnp.where(mask, logits, jnp.int32(-(2 ** 30)))
        return logits

    def prefill(self, t, batch, caches, *, last_only: bool = True,
                last_index=None):
        """ID prefill: fill caches at pos 0, return last-token logits.

        last_index (traced scalar) gathers the hidden state at that
        sequence position before the vocab projection — the serving
        engine's bucketed prefill right-pads prompts to a shape bucket
        and reads the logits of the TRUE last prompt token without
        materializing (B, bucket, V) logits.  last_only=False returns
        logits for every position instead.
        """
        x = self.embed_in_id(t, batch)
        x, caches, _ = self.apply(t, x, Rep.ID, caches=caches, pos=0)
        if last_index is not None:
            h = jax.lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)
        else:
            h = x[:, -1:, :] if last_only else x
        return self.logits_id(t, h), caches

    def prefill_chunk(self, t, batch, caches, start_pos, last_index, *,
                      paged_kernel=True):
        """ID batched + chunked prefill over a shared cache arena.

        batch (B, C) int32: one C-token prompt chunk per arena row, for
        several requests at once (B = n_slots, the fixed dispatch shape
        — one compilation per chunk size).  start_pos (B,) int32: the
        sequence offset each row's chunk is written at; rows with no
        chunk this step are parked at attention.INACTIVE_POS, which
        masks their cache writes to a no-op (layers/attention.py).
        Chunk K/V is written straight into `caches` — the serving
        arena's decode view, contiguous rows or paged pools + tables —
        so a long prompt accumulates across calls while other rows
        keep decoding between chunks.

        Returns (logits (B, 1, V) int32, caches): each row's hidden
        state is gathered at its own last_index (B,) — the position of
        the final prompt token *within the chunk* — before the vocab
        projection, so no (B, C, V) logits are materialized.  Only rows
        whose final chunk just completed have meaningful logits; the
        engine ignores the rest.  paged_kernel: as in `apply`.
        """
        x = self.embed_in_id(t, batch)
        # real tokens: a row's columns up to its last_index, unless the
        # row is parked (decode rows are width-1 chunks, last_index 0)
        valid = ((start_pos < INACTIVE_POS)[:, None]
                 & (jnp.arange(batch.shape[1]) <= last_index[:, None]))
        x, caches, _ = self.apply(t, x, Rep.ID, caches=caches, pos=start_pos,
                                  valid=valid, paged_kernel=paged_kernel)
        idx = jnp.broadcast_to(
            last_index[:, None, None], (x.shape[0], 1, x.shape[-1]))
        h = jnp.take_along_axis(x, idx, axis=1)
        return self.logits_id(t, h), caches

    def decode_step(self, t, token, caches, pos, *, paged_kernel=True):
        """ID single-token decode. token (B,1) -> int32 logits (B,1,V).

        pos: scalar (lockstep batch) or per-slot vector (B,) — the
        continuous-batching engine advances each slot at its own offset.

        caches: the pytree layout of init_caches, OR a paged layout
        where each attention {'k','v'} dict additionally carries a
        per-slot page "table" and its KV leaves are page pools
        (serving.cache.PagedArena.decode_view) — the table is scanned
        alongside the layer-stacked leaves, so paging needs no change
        to this step function or its single compilation.  paged_kernel:
        as in `apply`.
        """
        x = self.embed_in_id(t, token)
        x, caches, _ = self.apply(t, x, Rep.ID, caches=caches, pos=pos,
                                  paged_kernel=paged_kernel)
        return self.logits_id(t, x), caches

    def init_caches(self, B: int, max_len: int, rep: Rep, dtype=None):
        """Allocate the cache pytree for `B` slots of length `max_len`.

        dtype None resolves by representation: int8 for Rep.ID (KV
        caches hold integer *images*; a float KV cache would silently
        break the integer-only serving invariant) and bfloat16 for
        FP/FQ.  SSM recurrent `h` state stays f32 in all reps — that is
        the documented scan float island (DESIGN.md), not a KV cache.

        The serving arenas treat this pytree as the structural
        template: the batch axis of every leaf and the sequence axis of
        every KV leaf are discovered by comparing eval_shape templates
        (serving.cache._probe_axes), so new cache layouts page/scatter
        correctly as long as KV leaves live in {'k','v'} dicts and keep
        the sequence axis after the batch axis.
        """
        if dtype is None:
            dtype = jnp.int8 if rep is Rep.ID else jnp.bfloat16
        caches = []
        for kind, tpl, n in self.plan():
            if kind in ("dense", "mamba"):
                one = tpl.init_cache(B, max_len, rep, dtype)
                caches.append(jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), one))
            elif kind == "pair":
                a, b = tpl
                ca = a.init_cache(B, max_len, rep, dtype)
                cb = b.init_cache(B, max_len, rep, dtype)
                two = jax.tree.map(lambda u, v: jnp.stack([u, v]), ca, cb)
                caches.append(jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), two))
            elif kind == "hybrid":
                mam, sha = tpl
                k = self.cfg.shared_attn_every
                cm = mam.init_cache(B, max_len, rep, dtype)
                cm = jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None], (k,) + x.shape), cm)
                cs = sha.init_cache(B, max_len, rep, dtype)
                one = {"m": cm, "sh": cs}
                caches.append(jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), one))
        return caches
