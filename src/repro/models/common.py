"""Architecture configuration shared by the model zoo.

A model is a repeating *pattern* of layer blocks (the smallest repeating
unit): dense archs have a 1-element pattern, gemma2 a [local, global] pair,
jamba an 8-element mamba/attention block, etc.  Blocks at the same pattern
position are stacked along a leading axis and executed with ``lax.scan`` so
the lowered HLO stays small at 80+ layers.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Literal

import jax.numpy as jnp

LayerKind = Literal["attn", "mamba"]
MlpKind = Literal["swiglu", "geglu", "gelu", "moe", "none"]
AttnKind = Literal["causal", "window", "bidir"]


@dataclass(frozen=True)
class LayerSpec:
    kind: LayerKind = "attn"
    attn: AttnKind = "causal"
    window: int | None = None          # sliding-window size (tokens)
    mlp: MlpKind = "swiglu"
    cross_attn: bool = False           # decoder layers attending to encoder


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: Literal["dense", "moe", "ssm", "hybrid", "vlm", "audio"]
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    pattern: tuple[LayerSpec, ...]

    head_dim: int | None = None        # default d_model // n_heads
    # --- MoE
    n_experts: int = 0                 # experts the router scores
    top_k: int = 0
    capacity_factor: float = 1.25
    shared_expert: bool = False        # llama4-style always-on expert
    d_ff_shared: int = 0               # shared expert's width (0: d_ff)
    # the share of the experts this chip holds under expert parallelism:
    # experts [expert_offset, expert_offset + experts_held); 0 = all
    experts_held: int = 0
    expert_offset: int = 0
    # --- attention details
    qkv_bias: bool = False
    position_embedding: Literal["rope", "nope"] = "rope"
    rope_theta: float = 10_000.0
    attention_multiplier: float | None = None   # score scale; None: hd^-0.5
    attn_softcap: float | None = None  # gemma2 logit soft-capping
    final_softcap: float | None = None
    # --- SSM (mamba2 / SSD)
    ssm_state: int = 0
    ssm_heads: int = 0                 # P = d_head for SSD; heads = d_inner/P
    ssm_expand: int = 2
    ssm_chunk: int = 128
    conv_width: int = 4
    ssm_gated_norm: bool = False       # Mamba-2's w * rmsnorm(y * silu(z))
    # --- enc-dec / multimodal stubs
    encoder_layers: int = 0            # whisper encoder depth
    frontend_tokens: int = 0           # stub patch/frame embeddings length
    frontend_dim: int = 0              # stub embedding dim (before projector)
    # --- numerics
    dtype: str = "bfloat16"
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    sandwich_norm: bool = False        # gemma2 pre+post block norms
    # Granite's scalar multipliers (1.0: absent): x0 = embed * e, each
    # sublayer's output * r before the residual add, logits / s
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # activation rematerialization at super-block granularity (the pjit-path
    # analogue of the paper's memory planning — DESIGN.md §2)
    remat: bool = True

    def __post_init__(self):
        assert self.n_layers % len(self.pattern) == 0, \
            (self.name, self.n_layers, len(self.pattern))

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def n_super(self) -> int:
        return self.n_layers // len(self.pattern)

    @property
    def d_shared(self) -> int:
        return self.d_ff_shared or self.d_ff

    @property
    def n_held(self) -> int:
        """Routed experts whose weights this chip holds."""
        return self.experts_held or self.n_experts

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_p(self) -> int:
        """SSD head dim P."""
        return self.d_inner // self.ssm_heads if self.ssm_heads else 0

    def activation_dtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32

    # ---- parameter count (for 6·N·D model-FLOPs bookkeeping) -------------
    def param_count(self, active_only: bool = False) -> int:
        D, F, V = self.d_model, self.d_ff, self.vocab
        H, K, hd = self.n_heads, self.n_kv_heads, self.hd
        total = V * D + D  # embed, final norm
        if not self.tie_embeddings:
            total += D * V
        for spec in self.pattern:
            n = self.n_super
            if spec.kind == "attn":
                attn = D * H * hd + 2 * D * K * hd + H * hd * D
                total += n * (attn + D)      # + the mixer's norm
                if spec.cross_attn:
                    total += n * (attn + D)
            else:  # mamba2 (B/C shared across heads: n_groups=1)
                di, N, Hs = self.d_inner, self.ssm_state, self.ssm_heads
                ssm = (D * (2 * di + 2 * N + Hs)   # in_proj (z,x,B,C,dt)
                       + (self.conv_width + 1) * (di + 2 * N)
                       + di * D + 3 * Hs
                       + (di if self.ssm_gated_norm else 0))
                total += n * (ssm + D)
            if spec.mlp == "moe":
                # the router scores every expert; this chip holds
                # n_held of them, of which a token reaches top_k * n_held
                # / n_experts in expectation; the shared expert once
                held = self.n_held
                per_expert = 3 * D * F
                total += n * (D * self.n_experts + D)   # router, norm
                total += round(n * per_expert * (
                    self.top_k * held / self.n_experts if active_only
                    else held))
                if self.shared_expert:
                    total += n * 3 * D * self.d_shared
            elif spec.mlp in ("swiglu", "geglu"):
                total += n * (3 * D * F + D)
            elif spec.mlp == "gelu":
                total += n * (2 * D * F + D)
        if self.encoder_layers:
            attn = D * H * hd + 2 * D * K * hd + H * hd * D
            mlp = 2 * D * F
            total += self.encoder_layers * (attn + mlp + 2 * D)
        if self.frontend_tokens:
            total += self.frontend_dim * D  # projector stub
        return int(total)


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"]


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
    # the full-sequence long-context shape (DESIGN.md §8): quadratic
    # attention cannot fit it per device — it exists for the
    # sequence-sharded ring path (PerfFlags.seq_shard, dist/ring.py)
    "long_500k_prefill": InputShape("long_500k_prefill", 524_288, 1,
                                    "prefill"),
}


def reduced(cfg: ArchConfig, **overrides) -> ArchConfig:
    """A tiny same-family variant for CPU smoke tests (<=2 super-blocks,
    d_model<=512, <=4 experts)."""
    pat = cfg.pattern
    small = dict(
        n_layers=len(pat) * min(2, cfg.n_super),
        d_model=256,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 2) or 2,
        d_ff=512,
        vocab=512,
        head_dim=64,
        n_experts=4 if cfg.n_experts else 0,
        top_k=min(cfg.top_k, 2) if cfg.top_k else 0,
        d_ff_shared=384 if cfg.d_ff_shared else 0,
        experts_held=0,
        expert_offset=0,
        # drop-free capacity so prefill/decode routing agree exactly in tests
        capacity_factor=8.0,
        ssm_state=32 if cfg.ssm_state else 0,
        ssm_heads=8 if cfg.ssm_heads else 0,
        ssm_chunk=16,
        encoder_layers=2 if cfg.encoder_layers else 0,
        frontend_tokens=16 if cfg.frontend_tokens else 0,
        frontend_dim=64 if cfg.frontend_dim else 0,
        dtype="float32",
    )
    small.update(overrides)
    return replace(cfg, **small)
