"""Model configuration — one dataclass covers the 10 assigned backbones plus
the paper's own four RALM configs (Table 2).

Layer heterogeneity (gemma3's 5:1 local:global, hymba's sparse global layers)
is expressed with a *layer pattern*: a cycle of layer-class names; the stack
groups parameters by class and scans each class's layers with a uniform body
(compile-economy: HLO size independent of depth, DESIGN.md §8). Leading
dense layers of an MoE model (DeepSeek's ``first_k_dense_replace``) are a
class of their own, ``LEAD``, ahead of the pattern.

Multi-head latent attention (DeepSeek-V2, arXiv 2405.04434 §2.1) is on
where ``kv_lora_rank > 0``: queries of ``qk_nope_head_dim +
qk_rope_head_dim`` per head, keys and values expanded from one cached
latent row per position, values of ``v_head_dim``; YaRN scales its rotary
frequencies and softmax where ``yarn_factor > 1``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

#: class name of the leading dense layers of an MoE model (``first_k_dense``)
LEAD = "lead"


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    d_head: int = 0                      # 0 -> d_model // n_heads

    # block family: "dense" | "moe" | "rwkv6" | "hybrid" (attn ∥ mamba)
    block: str = "dense"

    # attention pattern: cycle of "global" / "local" layer classes
    layer_pattern: Tuple[str, ...] = ("global",)
    window: int = 0                      # sliding window for "local" layers

    # attention details
    qkv_bias: bool = False
    rope_theta: float = 1e4
    rope_mode: str = "rope"              # "rope" | "mrope" | "none"
    mrope_sections: Tuple[int, ...] = (16, 24, 24)   # qwen2-vl t/h/w split

    # MoE
    n_experts: int = 0
    top_k: int = 0
    d_expert: int = 0                    # routed expert width; 0 -> d_ff
    n_shared_experts: int = 0            # one SwiGLU of n * d_expert, all tokens
    first_k_dense: int = 0               # leading layers with a dense d_ff MLP
    norm_topk_prob: bool = True          # renormalise the top-k router weights
    routed_scaling_factor: float = 1.0   # scales unrenormalised weights

    # multi-head latent attention (MLA; 0 -> off)
    kv_lora_rank: int = 0                # latent width of the cached row
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0            # the roped part, shared by heads in k
    v_head_dim: int = 0

    # YaRN rotary scaling (yarn_factor <= 1 -> off)
    yarn_factor: float = 0.0
    yarn_original_max_position: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    # SSM (hybrid mamba branch / rwkv6)
    ssm_state: int = 0
    conv_width: int = 4

    # encoder-decoder
    arch: str = "decoder"                # "decoder" | "encdec"
    n_enc_layers: int = 0

    # norm / act
    norm_eps: float = 1e-5
    act: str = "silu"                    # silu (SwiGLU) | gelu (GeGLU)
    tie_embeddings: bool = False

    # modality frontend stub: None | "vision" | "audio"
    frontend: Optional[str] = None

    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.d_head == 0:
            object.__setattr__(self, "d_head", self.d_model // max(self.n_heads, 1))

    # ---- derived ----
    @property
    def q_per_kv(self) -> int:
        return self.n_heads // max(self.n_kv_heads, 1)

    @property
    def mla(self) -> bool:
        """Multi-head latent attention: one cached latent row per position."""
        return self.kv_lora_rank > 0

    @property
    def latent_width(self) -> int:
        """Width of an MLA cache row: the normed latent and the roped key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def expert_width(self) -> int:
        return self.d_expert or self.d_ff

    def yarn_mscale_of(self, mscale: float) -> float:
        """YaRN's attention-temperature factor ``0.1 mscale ln(s) + 1``
        (1 without scaling)."""
        if self.yarn_factor <= 1.0:
            return 1.0
        return 0.1 * mscale * math.log(self.yarn_factor) + 1.0

    def mla_softmax_scale(self) -> float:
        """``(nope + rope)^-1/2`` times the square of YaRN's
        ``mscale_all_dim`` factor (DeepSeek-V2's attention)."""
        m = self.yarn_mscale_of(self.yarn_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    def is_moe_class(self, cls: str) -> bool:
        return self.block == "moe" and cls != LEAD

    def pattern_classes(self) -> Tuple[str, ...]:
        """Distinct layer-class names in stack order of first appearance."""
        seen, out = set(), []
        for c in self.layer_classes():
            if c not in seen:
                seen.add(c)
                out.append(c)
        return tuple(out)

    def layer_classes(self) -> Tuple[str, ...]:
        """Per-layer class name, length n_layers: ``first_k_dense`` layers
        of ``LEAD``, then the pattern's cycle."""
        k = self.first_k_dense
        return (LEAD,) * k + tuple(
            self.layer_pattern[i % len(self.layer_pattern)]
            for i in range(self.n_layers - k))

    def class_layers(self, cls: str) -> Tuple[int, ...]:
        """Global layer indices belonging to class `cls`."""
        return tuple(i for i, c in enumerate(self.layer_classes()) if c == cls)

    def param_count(self) -> int:
        """Total parameters (embedding + blocks), for 6ND model-flops math."""
        d, f, L, V = self.d_model, self.d_ff, self.n_layers, self.vocab_size
        nh, nkv, dh = self.n_heads, self.n_kv_heads, self.d_head
        emb = V * d * (1 if self.tie_embeddings else 2)
        if self.block == "rwkv6":
            # r,k,v,g,w projections + output + ffn(2 mats) + small lora decays
            tmix = d * (nh * dh) * 4 + d * (nh * dh) + d * 64 * 2 * 5
            cmix = d * f + f * d + d * d
            blk = tmix + cmix
        else:
            attn = self._attn_params()
            if self.block == "moe":
                mlp = self._moe_mlp_params(self.n_experts)
            else:
                mlp = 3 * d * f
            blk = attn + mlp
            if self.block == "hybrid":
                d_in = nh * dh
                blk += 2 * d * d_in + d_in * (2 * self.ssm_state + 1) + d_in * d
        enc = 0
        if self.arch == "encdec":
            # encoder layers + decoder cross-attention
            enc_attn = d * nh * dh + 2 * d * nkv * dh + nh * dh * d
            enc = self.n_enc_layers * (enc_attn + 3 * d * f)
            blk += enc_attn  # cross-attn per decoder layer
        lead = 0
        if self.block == "moe" and self.first_k_dense:
            # leading layers carry a dense d_ff MLP instead of the experts
            lead = self.first_k_dense * (3 * d * f - mlp)
        return emb + L * blk + enc + lead

    def _attn_params(self) -> int:
        d, nh, nkv, dh = (self.d_model, self.n_heads, self.n_kv_heads,
                          self.d_head)
        if not self.mla:
            return d * nh * dh + 2 * d * nkv * dh + nh * dh * d
        r, nope = self.kv_lora_rank, self.qk_nope_head_dim
        return (d * nh * (nope + self.qk_rope_head_dim)   # q
                + d * self.latent_width + r               # kv_a, its norm
                + r * nh * (nope + self.v_head_dim)       # kv_b
                + nh * self.v_head_dim * d)               # o

    def _moe_mlp_params(self, experts: int) -> int:
        """One MoE layer's MLP with ``experts`` routed experts counted:
        router, experts, shared experts."""
        d, fe = self.d_model, self.expert_width
        return (d * self.n_experts + experts * 3 * d * fe
                + self.n_shared_experts * 3 * d * fe)

    def active_param_count(self) -> int:
        """Active parameters per token (MoE: only top_k experts)."""
        if self.block != "moe":
            return self.param_count()
        moe_layers = self.n_layers - self.first_k_dense
        return self.param_count() - moe_layers * (
            self._moe_mlp_params(self.n_experts)
            - self._moe_mlp_params(self.top_k))
