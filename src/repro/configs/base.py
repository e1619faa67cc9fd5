"""Config dataclasses for the SplitEE reproduction framework.

Every assigned architecture is expressed as a :class:`ModelConfig`; input
shapes (train_4k / prefill_32k / decode_32k / long_500k) are
:class:`InputShape`. Configs are plain frozen dataclasses so they hash, can
be used as jit static args, and never touch jax device state on import.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    # capacity factor used for the dense-dispatch expert-parallel matmul
    capacity_factor: float = 1.25


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """State-space / RWKV parameters."""
    kind: str = "rwkv6"            # "rwkv6" | "mamba2"
    state_size: int = 64           # per-head recurrent state (rwkv head_dim / mamba2 N)
    num_heads: int = 0             # 0 -> derive from d_model // state_size
    expand: int = 2                # mamba2 inner expansion
    chunk_size: int = 128          # chunked-scan length


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    """Encoder stack for enc-dec (audio) architectures."""
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    # number of (stub) frontend frames fed to the encoder for decode shapes
    source_len: int = 4096


@dataclasses.dataclass(frozen=True)
class ExitConfig:
    """The paper's technique: exit head after every layer (or stride)."""
    enabled: bool = True
    stride: int = 1                # attach an exit after every `stride` layers
    # LM archs tie all exits to a single unembedding (per-layer vocab heads
    # would dominate params); classification testbeds use per-exit heads.
    share_head: bool = True
    # confidence = max softmax prob (paper's C_i). "entropy" used by DeeBERT.
    confidence: str = "maxprob"


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    arch_id: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // num_heads
    num_classes: int = 0           # classification exits; 0 -> LM head (vocab)

    # attention flavour
    causal: bool = True            # False -> bidirectional (BERT-style)
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    position_embedding: str = "rope"   # rope | nope (rope_theta unused)
    attention_multiplier: float = 0.0  # score scale; 0 -> 1/sqrt(head_dim)
    mrope: bool = False            # multimodal rotary (qwen2-vl)
    sliding_window: int = 0        # 0 -> full causal attention (native)
    # beyond-paper: force a window for long_500k on full-attention archs
    sliding_window_override: int = 0

    # block composition
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    # hybrid (zamba2): 1 shared attention block interleaved every k mamba blocks
    hybrid_attn_every: int = 0     # 0 -> not hybrid
    # hybrid (granite-4.0-h): the mixer of each layer, "mamba" or
    # "attention", each layer with its own mixer weights and its own MLP;
    # () -> every layer is the family's block
    layer_types: Tuple[str, ...] = ()
    encoder: Optional[EncoderConfig] = None

    # frontends (stubbed per assignment: input_specs() feeds embeddings)
    modality: str = "text"         # text | vision_stub | audio_stub
    norm: str = "rmsnorm"          # rmsnorm | layernorm
    activation: str = "swiglu"     # swiglu | gelu_mlp
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    # Granite's scalings: the embedding is multiplied, each block's branch
    # multiplied before its residual add, the logits divided
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0

    exits: ExitConfig = ExitConfig()
    dtype: str = "bfloat16"

    # citation for the config numbers
    source: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def attn_rope_theta(self) -> float:
        """The rotary base attention applies; 0 (none) under NoPE."""
        return self.rope_theta if self.position_embedding == "rope" else 0.0

    def layer_kinds(self) -> Tuple[str, ...]:
        """The per-layer mixer types, checked against the depth."""
        if len(self.layer_types) != self.num_layers or not set(
                self.layer_types) <= {"mamba", "attention"}:
            raise ValueError(
                f"{self.arch_id}: layer_types must name 'mamba' or "
                f"'attention' for each of the {self.num_layers} layers, got "
                f"{self.layer_types!r}")
        return self.layer_types

    @property
    def exit_layers(self) -> Tuple[int, ...]:
        """1-indexed layers with an exit head attached (always includes L)."""
        n = self.decoder_layers
        s = self.exits.stride
        layers = tuple(i for i in range(s, n + 1, s))
        if not layers or layers[-1] != n:
            layers = layers + (n,)
        return layers

    @property
    def decoder_layers(self) -> int:
        return self.num_layers

    def effective_window(self, seq_len: int) -> int:
        """Attention window for a given sequence length (0 = full)."""
        if self.sliding_window:
            return self.sliding_window
        if self.sliding_window_override and seq_len > self.sliding_window_override:
            return self.sliding_window_override
        return 0

    def param_count(self) -> int:
        """Analytic parameter count (embedding + decoder + exits + encoder)."""
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        hd = self.resolved_head_dim
        q = self.num_heads * hd
        kv = self.num_kv_heads * hd
        attn = d * q + 2 * d * kv + q * d
        if self.activation == "swiglu":
            mlp = 3 * d * f
        else:
            mlp = 2 * d * f
        per_layer = 0
        n_attn = n_mix = self.num_layers
        if self.layer_types:
            # per-layer mixers (mamba2 or attention), each with its own MLP
            from repro.models.mamba2 import HEAD_DIM
            d_in = self.ssm.expand * d
            conv_dim = d_in + 2 * self.ssm.state_size
            heads = d_in // HEAD_DIM
            mamba = (d * (d_in + conv_dim + heads) + 4 * conv_dim
                     + conv_dim + 3 * heads + d_in + d_in * d)
            n_attn = sum(t == "attention" for t in self.layer_types)
            total_layers = (n_attn * attn + (self.num_layers - n_attn) * mamba
                            + self.num_layers * (mlp + 2 * d))
        elif self.family == "ssm" and self.ssm is not None:
            # rwkv6: time-mix (~4.5 d^2 with lora decays) + channel-mix 2*d*f
            per_layer = int(5 * d * d) + 2 * d * f
            total_layers = per_layer * self.num_layers
        elif self.family == "hybrid" and self.ssm is not None:
            # every layer is a mamba block (no per-layer MLP); one shared
            # attn+mlp block applied every k layers (weights counted once)
            k = max(self.hybrid_attn_every, 1)
            d_in = self.ssm.expand * d
            conv_dim = d_in + 2 * self.ssm.state_size
            mamba = d * (d_in + conv_dim + d_in // 64) + d_in * d
            total_layers = self.num_layers * mamba + (attn + mlp)
        elif self.family == "moe" and self.moe is not None:
            moe_mlp = self.moe.num_experts * 3 * d * f + d * self.moe.num_experts
            total_layers = self.num_layers * (attn + moe_mlp)
        else:
            total_layers = self.num_layers * (attn + mlp)
        emb = v * d
        head_out = self.num_classes if self.num_classes else v
        n_heads_p = 1 if (not self.exits.enabled or self.exits.share_head) \
            else len(self.exit_layers)
        exits_p = n_heads_p * d * head_out
        enc = 0
        if self.encoder is not None:
            e = self.encoder
            eq = e.num_heads * (e.d_model // e.num_heads)
            ekv = e.num_kv_heads * (e.d_model // e.num_heads)
            e_attn = e.d_model * eq + 2 * e.d_model * ekv + eq * e.d_model
            e_mlp = 2 * e.d_model * e.d_ff
            # decoder cross-attention adds another attn block per decoder layer
            enc = e.num_layers * (e_attn + e_mlp) + self.num_layers * attn
        return emb + total_layers + exits_p + enc

    def active_param_count(self) -> int:
        """Params touched per token (MoE top-k instead of all experts)."""
        if self.family != "moe" or self.moe is None:
            return self.param_count()
        d, f = self.d_model, self.d_ff
        hd = self.resolved_head_dim
        attn = d * self.num_heads * hd + 2 * d * self.num_kv_heads * hd \
            + self.num_heads * hd * d
        active_mlp = self.moe.top_k * 3 * d * f + d * self.moe.num_experts
        layers = self.num_layers * (attn + active_mlp)
        head_out = self.num_classes if self.num_classes else self.vocab_size
        n_heads_p = 1 if (not self.exits.enabled or self.exits.share_head) \
            else len(self.exit_layers)
        return self.vocab_size * d + layers + n_heads_p * d * head_out


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                      # train | prefill | decode


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


def smoke_variant(cfg: ModelConfig) -> ModelConfig:
    """Reduced config of the same family: 2 layers (one of each type where
    layers are typed), d_model<=512, <=4 experts."""
    d = min(cfg.d_model, 128)
    heads = min(cfg.num_heads, 4)
    kv = max(1, min(cfg.num_kv_heads, heads))
    # keep the GQA ratio flavour: if original had grouping, keep kv < heads
    if cfg.num_kv_heads < cfg.num_heads:
        kv = max(1, heads // 2)
    moe = None
    if cfg.moe is not None:
        moe = dataclasses.replace(cfg.moe, num_experts=min(4, cfg.moe.num_experts))
    ssm = None
    if cfg.ssm is not None:
        ssm = dataclasses.replace(cfg.ssm, state_size=min(16, cfg.ssm.state_size),
                                  chunk_size=16, num_heads=0)
    enc = None
    if cfg.encoder is not None:
        enc = dataclasses.replace(
            cfg.encoder, num_layers=2, d_model=d, num_heads=heads,
            num_kv_heads=kv, d_ff=4 * d, source_len=32)
    # one layer of each mixer type, in the order they first appear
    kinds = tuple(dict.fromkeys(cfg.layer_types))
    return dataclasses.replace(
        cfg,
        num_layers=len(kinds) or 2,
        layer_types=kinds,
        d_model=d,
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=0,
        d_ff=4 * d,
        vocab_size=min(cfg.vocab_size, 512),
        sliding_window=min(cfg.sliding_window, 64) if cfg.sliding_window else 0,
        hybrid_attn_every=2 if cfg.hybrid_attn_every else 0,
        moe=moe,
        ssm=ssm,
        encoder=enc,
    )
