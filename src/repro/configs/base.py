"""Architecture configuration schema + registry.

One `ArchConfig` per assigned architecture lives in configs/<id>.py with
the exact figures from the assignment; `reduced()` derives the CPU smoke-
test variant of the same family.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, Optional, Tuple

_REGISTRY: Dict[str, "ArchConfig"] = {}

ARCH_IDS = [
    "internvl2_76b", "falcon_mamba_7b", "olmoe_1b_7b",
    "llama4_maverick_400b_a17b", "granite_3_2b", "nemotron_4_340b",
    "llama3_2_3b", "chatglm3_6b", "zamba2_1_2b", "musicgen_medium",
    "nemo_cnn", "mellum2_12b",
]


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN rotary scaling (arXiv:2309.00071) as HF `rope_type: yarn`
    states it: frequencies blended between interpolated and original
    by a ramp over [beta_fast, beta_slow] rotations, and q.k scaled by
    attention_factor squared."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class AttnKind:
    """One layer's attention: `window` 0 is full causal; W > 0 lets the
    query at position i see keys j with i - W < j <= i.  Optional YaRN
    scaling of its layers' rotary frequencies (base: rope_base)."""

    window: int = 0
    yarn: Optional[Yarn] = None


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | cnn
    n_layers: int
    d_model: int
    vocab: int
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0           # 0 -> d_model // n_heads
    d_ff: int = 0
    act: str = "silu"           # silu | gelu | relu | relu2
    gated: bool = True
    norm: str = "rms"           # rms | layer
    norm_bias: bool = False
    rope_base: float = 10000.0
    rope_fraction: float = 1.0
    # one period of per-layer attention kinds, repeated over the layers;
    # empty: every layer full causal
    attn_pattern: Tuple[AttnKind, ...] = ()
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 1
    moe_every: int = 1          # 2 = MoE on every other layer (llama4)
    shared_expert: bool = False
    moe_group: int = 512
    # --- SSM ---
    ssm_kind: str = ""          # mamba1 | mamba2
    ssm_state: int = 16
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    # --- hybrid (zamba2) ---
    shared_attn_every: int = 0
    # --- IO / modality ---
    input_mode: str = "tokens"  # tokens | embeds (vlm/audio frontend stub)
    # --- misc ---
    sub_quadratic: bool = False  # eligible for long_500k
    notes: str = ""

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    def attn_kind(self, layer: int) -> AttnKind:
        if not self.attn_pattern:
            return AttnKind()
        return self.attn_pattern[layer % len(self.attn_pattern)]

    @property
    def vocab_padded(self) -> int:
        """Vocab padded to 256 so embedding/head shard cleanly on the
        model axis (standard production practice; logits beyond `vocab`
        are masked)."""
        return -(-self.vocab // 256) * 256

    def reduced(self) -> "ArchConfig":
        """Tiny same-family variant for CPU smoke tests."""
        kw = {f.name: getattr(self, f.name)
              for f in dataclasses.fields(self)}
        kw.update(
            n_layers=min(
                self.n_layers,
                max(2, len(self.attn_pattern))
                if self.shared_attn_every == 0
                else self.shared_attn_every + 1,
            ),
            # a window the reduced max_seq reaches
            attn_pattern=tuple(
                dataclasses.replace(k, window=min(k.window, 8))
                for k in self.attn_pattern),
            d_model=128,
            vocab=256,
            d_ff=256 if self.d_ff else 0,
            n_heads=4 if self.n_heads else 0,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads else 0,
            head_dim=32 if self.n_heads else 0,
            n_experts=min(self.n_experts, 4),
            top_k=min(self.top_k, 2),
            moe_group=64,
            ssm_state=min(self.ssm_state, 16),
            ssm_head_dim=(
                32 if self.ssm_kind == "mamba2" else self.ssm_head_dim
            ),
            shared_attn_every=(2 if self.shared_attn_every else 0),
            name=self.name + "_reduced",
        )
        return ArchConfig(**kw)

    def param_count(self) -> int:
        """Analytic parameter count (used for MODEL_FLOPS = 6*N*D)."""
        d, L, V = self.d_model, self.n_layers, self.vocab
        hd = self.hd
        n = 2 * V * d  # embed + head
        for i in range(L):
            if self.family in ("dense", "moe"):
                attn = (d * self.n_heads * hd
                        + 2 * d * self.n_kv_heads * hd
                        + self.n_heads * hd * d)
                n += attn + 2 * d  # norms
                is_moe = self.n_experts > 0 and (
                    i % self.moe_every == self.moe_every - 1
                )
                ff_mats = 3 if self.gated else 2
                if is_moe:
                    n += self.n_experts * ff_mats * d * self.d_ff
                    n += d * self.n_experts  # router
                    if self.shared_expert:
                        n += ff_mats * d * self.d_ff
                else:
                    n += ff_mats * d * self.d_ff
            elif self.family == "ssm":
                di = self.ssm_expand * d
                rank = max(1, -(-d // 16))
                n += (
                    d * 2 * di
                    + di * (rank + 2 * self.ssm_state)
                    + rank * di
                    + di * d
                    + di * self.ssm_state
                    + 2 * di
                    + d
                )
            elif self.family == "hybrid":
                di = self.ssm_expand * d
                H = di // self.ssm_head_dim
                n += (
                    d * (2 * di + 2 * self.ssm_state + H)
                    + di * d
                    + 3 * H
                    + 2 * di
                    + d
                )
        if self.family == "hybrid" and self.shared_attn_every:
            n += (
                2 * d * (self.n_heads + 2 * self.n_kv_heads) * hd
                + self.n_heads * hd * d
            )
        return int(n)

    def active_param_count(self) -> int:
        """Active params per token (MoE: routed top-k + shared only)."""
        if self.n_experts == 0:
            return self.param_count()
        full = self.param_count()
        L_moe = self.n_layers // self.moe_every
        ff_mats = 3 if self.gated else 2
        inactive = ((self.n_experts - self.top_k) * ff_mats
                    * self.d_model * self.d_ff * L_moe)
        return int(full - inactive)


def register(cfg: ArchConfig) -> ArchConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ArchConfig:
    name = name.replace("-", "_").replace(".", "_")
    if name not in _REGISTRY:
        importlib.import_module(f"repro.configs.{name}")
    return _REGISTRY[name]


def all_configs() -> Dict[str, ArchConfig]:
    for a in ARCH_IDS:
        get_config(a)
    return dict(_REGISTRY)
