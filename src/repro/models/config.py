"""Architecture configuration schema.

One ``ArchConfig`` instance fully describes a model: the registry in
``repro.configs`` holds one per assigned architecture. ``pattern`` is the
repeating block group (scanned over); ``n_layers`` that is not a multiple of
the group length leaves a tail of unrolled blocks (e.g. RecurrentGemma's
38 = 12 x (rec, rec, attn) + 2 x rec).

Block specs are "<mixer>[+<ffn>]" strings:
  mixers: attn | local | mla | rglru | mlstm | slstm
  ffns:   mlp | moe | none
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

from .attention import MLAConfig
from .layers import YarnRope
from .moe import MoEConfig
from .recurrent import RGLRUConfig
from .xlstm import XLSTMConfig


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0              # 0 => d_model // n_heads
    pattern: tuple[str, ...] = ("attn+mlp",)
    head: tuple[str, ...] = ()     # unrolled leading blocks (e.g. ds-v2's
                                   # first dense layer)
    tail: tuple[str, ...] = ()     # unrolled remainder blocks
    norm: str = "rms"              # rms | ln
    act: str = "silu"              # silu | gelu
    qkv_bias: bool = False
    pos: str = "rope"              # rope | mrope | sinusoidal
    rope_theta: float = 10000.0
    yarn: YarnRope | None = None   # YaRN scaling of mla blocks' rope
    mrope_sections: tuple[int, ...] = ()
    tie_embeddings: bool = False
    logit_softcap: float = 0.0
    logit_mult: float = 1.0
    attn_softcap: float = 0.0
    mlp_gated: bool = True
    emb_mult: float = 1.0          # granite/minicpm mu-P style multipliers
    resid_mult: float = 1.0
    attn_scale: float = 0.0        # 0 => 1/sqrt(head_dim)
    window: int = 0                # sliding window for "local" blocks
    moe: MoEConfig | None = None
    mla: MLAConfig | None = None
    rglru: RGLRUConfig | None = None
    xlstm: XLSTMConfig | None = None
    inputs: str = "tokens"         # tokens | embeds (vlm) | codes (audio)
    codebooks: int = 0             # musicgen: # parallel code streams
    max_seq: int = 524288
    # long_500k applicability: quadratic-attention archs skip it
    subquadratic: bool = False
    # execution knobs (not architecture):
    remat: bool = True
    scan_layers: bool = True
    attn_block_k: int = 1024
    compute_dtype: str = "bfloat16"

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def n_groups(self) -> int:
        return ((self.n_layers - len(self.tail) - len(self.head))
                // len(self.pattern))

    def __post_init__(self):
        body = self.n_layers - len(self.tail) - len(self.head)
        if body % len(self.pattern) != 0:
            raise ValueError(
                f"{self.name}: {self.n_layers} layers - {len(self.head)} "
                f"head - {len(self.tail)} tail not divisible by group "
                f"{len(self.pattern)}")

    def with_(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)


__all__ = ["ArchConfig", "MLAConfig", "MoEConfig", "RGLRUConfig",
           "XLSTMConfig", "YarnRope"]
