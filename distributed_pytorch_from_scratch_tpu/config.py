"""Typed configuration for the TPU-native framework.

The reference scattered configuration across three channels: argparse flags
(`/root/reference/train.py:25-52`), a frozen dataclass (`ModelArgumments`,
`/root/reference/constants.py:9-17`) and ambient environment variables
(``DTYPE``/``DEVICE``, read at `/root/reference/models/model.py:39-40,153`).
Here everything is a typed dataclass; dtype is an explicit field, and the CLI
produces these dataclasses instead of an untyped `Namespace`.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional

import jax.numpy as jnp

from .ops.rope import YarnScaling

# Special-token conventions, byte-compatible with the reference
# (`/root/reference/constants.py:3-6`) so its tokenizer.json and token-JSON
# files interoperate.
BOS_TOKEN = "<BOS>"
EOS_TOKEN = "<EOS>"
UNK_TOKEN = "<UNK>"
IGNORE_INDEX = -1

_DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
}


def resolve_dtype(name: str):
    if name not in _DTYPES:
        raise ValueError(f"Unknown dtype {name!r}; expected one of {sorted(_DTYPES)}")
    return _DTYPES[name]


@dataclass(frozen=True)
class LatentMoEConfig:
    """What the `mla_moe` family (models/mla_moe.py) needs beyond
    `ModelConfig`'s own fields: latent attention, a sigmoid router over
    routed experts of which this job may hold a slice, a shared expert,
    leading dense layers and multi-token-prediction modules. The keys are
    DeepSeek-V3's `config.json` names where one exists. In `ModelConfig`,
    `attn_dim` is the model width, `ffn_dim` the dense layers' SwiGLU
    width, `num_layers` the main model's layers (dense + expert),
    `num_experts` the ROUTED experts the router scores (all of them,
    whatever is held here) and `moe_top_k` the experts a token takes."""

    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    moe_intermediate_size: int
    n_shared_experts: int = 1
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 1.0
    # The job's share of an expert-parallel deployment: experts
    # [expert_offset, expert_offset + experts_held) live here (None: all).
    # The router still scores and ranks every routed expert; what an absent
    # expert would have added is left out (parallel/moe.SharedRoutedFFN).
    experts_held: "int | None" = None
    expert_offset: int = 0
    num_nextn_predict_layers: int = 0
    mtp_loss_weight: float = 0.3
    rms_norm_eps: float = 1e-6
    # `rope_scaling` of type `yarn` (ops/rope.YarnScaling: the blended
    # frequencies, and the softmax scale's mscale^2 in parallel/mla.py);
    # None: plain RoPE
    rope_scaling: "YarnScaling | None" = None
    # Manifold-constrained hyper-connections: the residual state as
    # `hc_mult` streams (parallel/hyper.py). Only the `mhc_mla_moe` family
    # (models/mhc_mla_moe.py) reads these facts, and `mla_moe` refuses them
    hyper: "HyperConnectionConfig | None" = None

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclass(frozen=True)
class HyperConnectionConfig:
    """The `hc_*` / `mhc_*` keys of a `config.json` (Xing4.0's,
    DeepSeek-V4's): mHC, arXiv:2512.24880."""

    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0


@dataclass(frozen=True)
class GdnMoEConfig:
    """What the `gdn_moe` family (models/gdn_moe.py) needs beyond
    `ModelConfig`'s own fields: Gated DeltaNet linear-attention layers, one
    gated grouped-query full-attention layer closing every period of
    `full_attention_interval` layers, and in every layer a softmax top-k
    router over routed experts (of which this job may hold a slice) with a
    gated shared expert. The keys are Qwen3-Next's `config.json` names
    where one exists. In `ModelConfig`, `attn_dim` is the model width,
    `num_heads` / `num_kv_heads` the full-attention layers' query and
    key-value heads, `num_layers` the layers (a whole number of periods),
    `num_experts` the ROUTED experts the router scores, `moe_top_k` the
    experts a token takes, and `ffn_dim` the shared expert's width (there is
    no dense MLP)."""

    head_dim: int                   # the full-attention heads' width
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    linear_conv_kernel_dim: int = 4
    full_attention_interval: int = 4
    partial_rotary_factor: float = 0.25
    # the job's share of an expert-parallel deployment, as LatentMoEConfig's
    experts_held: "int | None" = None
    expert_offset: int = 0
    rms_norm_eps: float = 1e-6

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)


@dataclass(frozen=True)
class KdaMlaMoEConfig:
    """What the `kda_mla_moe` family (models/kda_mla_moe.py) needs beyond
    `ModelConfig`'s own fields: Kimi Delta Attention layers (a delta rule
    whose decay is a channel's, under a bounded gate) with one head-gated
    latent-attention layer closing every `layer_group_size` layers, leading
    dense layers, a sigmoid router with a group-limited selection over
    routed experts (of which this job may hold a slice) with a shared
    expert, and a multi-token-prediction module. The keys are Ling-3.0's
    `config.json` names (`bailing_hybrid`) where one exists. In
    `ModelConfig`, `attn_dim` is the model width, `num_heads` the heads of
    BOTH mixers, `ffn_dim` the leading dense layers' SwiGLU width,
    `num_layers` the main model's layers (whole groups), `num_experts` the
    ROUTED experts the router scores and `moe_top_k` the experts a token
    takes."""

    head_dim: int                   # a KDA head's width: d_k = d_v
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    moe_intermediate_size: int
    q_lora_rank: "int | None" = None
    layer_group_size: int = 6       # layer i is latent where (i+1) % it == 0
    first_k_dense_replace: int = 2
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    n_shared_experts: int = 1
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    # the job's share of an expert-parallel deployment, as LatentMoEConfig's
    experts_held: "int | None" = None
    expert_offset: int = 0
    num_nextn_predict_layers: int = 0
    mtp_loss_weight: float = 0.0    # `mtp_loss_scaling_factor`
    rms_norm_eps: float = 1e-6

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclass(frozen=True)
class SsmMoEConfig:
    """What the `ssm_moe` family (models/ssm_moe.py) needs beyond
    `ModelConfig`'s own fields: layers of ONE sublayer each, by a pattern of
    one letter a layer (`M` a Mamba-2 state-space mixer, `*` grouped-query
    attention with no positions, `E` a sigmoid-routed expert FFN whose
    routed experts read and write a latent narrower than the model, of two
    matrices each under a squared ReLU, with a shared expert of the same
    form at the model's width), and a multi-token-prediction module that is
    a pattern of its own. The keys are Nemotron-H's `config.json` names
    (`nemotron_h`) where one exists. In `ModelConfig`, `attn_dim` is the
    model width, `num_heads` / `num_kv_heads` the attention layers' heads
    HELD, `num_layers` = `len(hybrid_override_pattern)`, `ffn_dim` the
    shared expert's width (there is no dense MLP), `num_experts` the ROUTED
    experts the router scores and `moe_top_k` the experts a token takes.
    The mixers are built at the heads and groups the job HOLDS
    (`mamba_num_heads`, `n_groups`, `num_heads`, `num_kv_heads`: one
    tensor-parallel rank's), the first Mamba head held `mamba_head_offset`
    among all of them."""

    hybrid_override_pattern: str    # "M" | "*" | "E", a layer each
    mamba_num_heads: int
    mamba_head_dim: int
    ssm_state_size: int
    n_groups: int
    head_dim: int                   # the attention heads' width
    moe_intermediate_size: int
    moe_latent_size: int
    moe_shared_expert_intermediate_size: int
    conv_kernel: int = 4
    chunk_size: int = 128
    mamba_head_offset: int = 0
    routed_scaling_factor: float = 1.0
    n_group: int = 1
    topk_group: int = 1
    # the job's share of an expert-parallel deployment, as LatentMoEConfig's
    experts_held: "int | None" = None
    expert_offset: int = 0
    num_nextn_predict_layers: int = 0
    mtp_hybrid_override_pattern: str = "*E"
    mtp_loss_weight: float = 0.3    # not published; DeepSeek-V3's first
    time_step_min: float = 1e-3
    time_step_max: float = 1e-1
    time_step_floor: float = 1e-4
    norm_eps: float = 1e-5


@dataclass(frozen=True)
class ConvMoEConfig:
    """What the `conv_moe` family (models/conv_moe.py) needs beyond
    `ModelConfig`'s own fields: which layers mix by a gated short
    convolution and which by grouped-query attention with q/k norms, how
    many leading layers keep a dense SwiGLU, and a sigmoid top-k router
    with a selection bias and no shared expert over routed experts of which
    this job may hold a slice. The keys are LFM2's `config.json` names
    (`lfm2_moe`). In `ModelConfig`, `attn_dim` is the model width,
    `num_heads` / `num_kv_heads` the attention layers' heads (head width
    `attn_dim / num_heads`), `num_layers` = `len(layer_types)`, `ffn_dim`
    the leading dense layers' SwiGLU width, `num_experts` the ROUTED
    experts the router scores and `moe_top_k` the experts a token takes."""

    layer_types: tuple              # "conv" | "full_attention", a layer each
    moe_intermediate_size: int
    num_dense_layers: int = 2
    conv_L_cache: int = 3           # the convolution's taps
    routed_scaling_factor: float = 1.0
    # the job's share of an expert-parallel deployment, as LatentMoEConfig's
    experts_held: "int | None" = None
    expert_offset: int = 0
    norm_eps: float = 1e-5


@dataclass(frozen=True)
class BdMoEConfig:
    """What the `bd_moe` family (models/bd_moe.py) needs beyond
    `ModelConfig`'s own fields: a grouped-query expert decoder whose heads
    are `head_dim` wide whatever the model's width (heads x head_dim need
    not be `attn_dim`), with q/k norms and a softmax top-k router with no
    shared expert over routed experts of which this job may hold a slice,
    trained by BLOCK DIFFUSION: blocks of `block_length` positions, one
    noise level a sequence, `mask_token_id` in place of a masked token. The
    keys are SDAR's `config.json` names (`sdar_moe`) where one exists. In
    `ModelConfig`, `attn_dim` is the model width, `num_heads` /
    `num_kv_heads` the heads, `num_experts` the ROUTED experts the router
    scores and `moe_top_k` the experts a token takes; `ffn_dim` is not
    read (every layer is an expert layer)."""

    head_dim: int
    moe_intermediate_size: int
    block_length: int = 4
    mask_token_id: int = 0
    # the noise level's floor: p = (1 - noise_eps) t + noise_eps, t ~ U(0, 1)
    noise_eps: float = 1e-3
    # the job's share of an expert-parallel deployment, as LatentMoEConfig's
    experts_held: "int | None" = None
    expert_offset: int = 0
    rms_norm_eps: float = 1e-6


@dataclass(frozen=True)
class SwaMoEConfig:
    """What the `swa_moe` family (models/swa_moe.py) needs beyond
    `ModelConfig`'s own fields: a grouped-query expert decoder whose
    attention layers are of two kinds over ONE parameter tree,
    `sliding_attention` (a row sees itself and the `sliding_window` - 1 rows
    before it, RoPE on q and k) and `full_attention` (the whole past, no
    positions at all), with heads `head_dim` wide whatever the model's
    width, q/k norms, an output gate, four norms a layer, leading layers
    with a dense SwiGLU, and then a sigmoid top-k router with a selection
    bias over routed experts of which this job may hold a slice, beside
    `num_shared_experts` shared ones. The keys are Trinity's `config.json`
    names (`afmoe`). In `ModelConfig`, `attn_dim` is the model width,
    `num_heads` / `num_kv_heads` the heads, `num_layers` =
    `len(layer_types)`, `ffn_dim` the leading dense layers' SwiGLU width,
    `num_experts` the ROUTED experts the router scores and `moe_top_k` the
    experts a token takes."""

    layer_types: tuple      # "sliding_attention" | "full_attention" a layer
    head_dim: int
    moe_intermediate_size: int
    sliding_window: int
    num_dense_layers: int = 2
    num_shared_experts: int = 1
    route_scale: float = 1.0
    # the speed of the selection bias's update after every optimizer step
    # (training/optim.router_bias_step); None: nothing updates the bias
    load_balance_coeff: "float | None" = None
    mup_enabled: bool = True        # the embedding's rows times sqrt(width)
    # the job's share of an expert-parallel deployment, as LatentMoEConfig's
    experts_held: "int | None" = None
    expert_offset: int = 0
    rms_norm_eps: float = 1e-5


@dataclass(frozen=True)
class EarlyMoEConfig:
    """What the `early_moe` family (models/early_moe.py) needs beyond
    `ModelConfig`'s own fields: a grouped-query expert decoder whose
    attention layers are of two kinds over ONE parameter tree, by two
    layouts of 0 / 1 a layer that this family takes only where they agree:
    a layer with `sliding_window_layout` 1 attends under a window (a row
    sees itself and the `sliding_window_size` - 1 rows before it) and, by
    `rope_layout` 1, takes RoPE on q and k; a layer with 0 in both attends
    to its whole past and takes no positions at all. Heads `head_dim` wide
    whatever the model's width, no q/k norms, no gate, two norms a layer,
    every layer an expert layer: a softmax top-k router that reads the
    LAYER'S INPUT (before attention) over ReLU-gated routed experts of
    which this job may hold a slice, no shared expert, no bias. The keys
    are SmallThinker's `config.json` names (`smallthinker`). In
    `ModelConfig`, `attn_dim` is the model width, `num_heads` /
    `num_kv_heads` the heads, `num_layers` = `len(sliding_window_layout)`,
    `num_experts` the ROUTED experts the router scores
    (`moe_num_primary_experts`) and `moe_top_k` the experts a token takes
    (`moe_num_active_primary_experts`); `ffn_dim` is not read."""

    sliding_window_layout: tuple    # 1: a window layer, 0: a full layer
    rope_layout: tuple              # 1: RoPE on q and k, 0: no positions
    head_dim: int
    moe_ffn_hidden_size: int
    sliding_window_size: int
    # the job's share of an expert-parallel deployment, as LatentMoEConfig's
    experts_held: "int | None" = None
    expert_offset: int = 0
    rms_norm_eps: float = 1e-6


@dataclass(frozen=True)
class DsaMoEConfig:
    """What the `dsa_moe` family (models/dsa_moe.py) needs beyond
    `ModelConfig`'s own fields: a grouped-query expert decoder whose every
    attention layer CHOOSES its keys: `indexer_num_heads` index heads of
    `indexer_head_dim` over one index key head score every earlier token, a
    row keeps the `topk` keys of largest score and attends over them alone,
    and the indexer trains on a loss of its own (parallel/dsa.py,
    ops/index_select.py). Heads `head_dim` wide whatever the model's width,
    q/k norms, a softmax top-k router normalised over the chosen with no
    shared expert, over routed experts of which this job may hold a slice.
    The keys are Keye-VL-2.0's `config.json` names (`KeyeVL2`, the
    indexer's under its `sa_config`). In `ModelConfig`, `attn_dim` is the
    model width, `num_heads` / `num_kv_heads` the heads, `num_experts` the
    ROUTED experts the router scores and `moe_top_k` the experts a token
    takes; `ffn_dim` is not read (every layer is an expert layer)."""

    head_dim: int
    moe_intermediate_size: int
    indexer_num_heads: int
    indexer_head_dim: int
    topk: int
    # the job's share of an expert-parallel deployment, as LatentMoEConfig's
    experts_held: "int | None" = None
    expert_offset: int = 0
    rms_norm_eps: float = 1e-6


@dataclass(frozen=True)
class LoopLlamaConfig:
    """What the `loop_llama` family (models/loop_llama.py) needs beyond
    `ModelConfig`'s own fields: the llama block (RoPE, RMSNorm, SwiGLU, an
    untied head, no bias anywhere) with a norm behind each sublayer too,
    whose stack of `num_layers` layers is RUN `loop_steps` TIMES A STEP
    over the same weights, the final norm after every pass, an exit (the
    one head) at the end of every pass and a learned exit gate whose
    distribution over the passes weighs the exits' losses, less
    `exit_entropy_coef` times that distribution's entropy (Ouro's
    `total_ut_steps`, and the beta of its Stage-I objective). A dense
    family: it holds no share of any expert."""

    loop_steps: int = 4
    exit_entropy_coef: float = 0.05
    rms_norm_eps: float = 1e-6


@dataclass(frozen=True)
class SsmDenseConfig:
    """What the `ssm_dense` family (models/ssm_dense.py) needs beyond
    `ModelConfig`'s own fields: a DENSE hybrid whose every layer is a mixer
    and then a SwiGLU, the mixer by `layer_types` a Mamba-2 state-space
    mixer (`mamba`) or grouped-query attention with no positions
    (`attention`), under four published scalars: the embedding's rows times
    `embedding_multiplier`, both sublayers' outputs times
    `residual_multiplier` before the residual adds them, the softmax over
    `q k^T * attention_multiplier` (NOT `1 / sqrt(head_dim)`) and the
    logits of the tied head over `logits_scaling`. The keys are
    `config.json`'s own (`granitemoehybrid` with `num_local_experts` 0). In
    `ModelConfig`, `attn_dim` is the model width, `num_heads` /
    `num_kv_heads` the attention layers' heads, `num_layers` =
    `len(layer_types)`, `ffn_dim` the SwiGLU's width (the published
    `shared_intermediate_size`), `num_experts` 0. A dense family: it holds
    no share of any expert, and its mixers are whole."""

    layer_types: "tuple[str, ...]"      # "mamba" | "attention", a layer each
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    mamba_expand: int = 2               # n_heads x d_head = expand x d
    mamba_conv_bias: bool = True        # the only form the mixer computes
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: "float | None" = None    # None: 1 / sqrt(head_dim)
    logits_scaling: float = 1.0
    position_embedding_type: str = "nope"
    rms_norm_eps: float = 1e-5
    # the tied table's normal(0, .) at init
    initializer_range: float = 0.1
    # dt at init (`config.json` publishes none: Mamba-2's own defaults)
    time_step_min: float = 1e-3
    time_step_max: float = 1e-1
    time_step_floor: float = 1e-4


@dataclass(frozen=True)
class SambaYConfig:
    """What the `sambay` family (models/sambay.py) needs beyond
    `ModelConfig`'s own fields: a decoder-hybrid-decoder (SambaY,
    arXiv:2507.06607, with differential attention, arXiv:2410.05258) whose
    every layer is a mixer and then a SwiGLU between two LayerNorms. The
    lower half alternates Mamba-1 mixers and window-`sliding_window`
    differential attention; layer `N / 2` is a Mamba-1 mixer that also
    LEAVES its scan's output (the memory), layer `N / 2 + 1` a full
    differential attention that also leaves its keys and values; every
    layer above reads one of the two (a gated memory unit, or
    cross-attention with queries only). The keys are `config.json`'s own
    (`phi4flash`); what it does not carry (the Mamba-1 sizes, the biases,
    `initializer_range`) stands at the published code's defaults. In
    `ModelConfig`, `attn_dim` is the model width, `num_heads` /
    `num_kv_heads` the published 40 / 20 (a differential head is two query
    heads; a key-value pair two key heads and one value twice as wide),
    `ffn_dim` the SwiGLU's width, `num_layers` = `len(layers_here)`,
    `num_experts` 0."""

    num_hidden_layers: int              # the PUBLISHED depth, N % 4 == 0
    # the published layers this job holds, in order (None: all of them)
    layers_here: "tuple[int, ...] | None" = None
    mb_per_layer: int = 2               # every mb_per_layer-th layer a scan
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: "int | None" = None  # None: ceil(d / 16)
    attention_bias: bool = True         # on W_qkv / W_q and W_o
    # the tied table's and every matrix's normal(0, .) at init
    initializer_range: float = 0.02
    lambda_std: float = 0.1             # the four lambda vectors' normal(0, .)
    # dt at init (Mamba-1's own defaults)
    time_step_min: float = 1e-3
    time_step_max: float = 1e-1
    time_step_floor: float = 1e-4


@dataclass(frozen=True)
class ModelConfig:
    """LLaMA-style decoder-only transformer shape.

    Defaults mirror the reference's `ModelArgumments`
    (`/root/reference/constants.py:9-17`): a ~45M-parameter model.
    """

    attn_dim: int = 512
    ffn_dim: int = 2048
    num_heads: int = 8
    num_layers: int = 12
    vocab_size: int = 1024
    maxlen: int = 1000
    rope_theta: float = 10000.0
    # Grouped-query attention: number of K/V heads (each shared by
    # num_heads/num_kv_heads query heads). None = num_heads = the
    # reference's plain multi-head attention.
    num_kv_heads: "int | None" = None
    # Dtype used for matmuls/activations inside the forward pass. Parameters
    # and the loss always stay float32 (the reference's autocast semantics:
    # `/root/reference/train.py:99-104`).
    compute_dtype: str = "float32"
    # Mixture-of-Experts: 0 = dense SwiGLU FFN (the reference's only FFN,
    # `/root/reference/models/model.py:81-95`); > 0 swaps every layer's FFN
    # for a top-k routed MoE (parallel/moe.py) with experts sharded over the
    # mesh axis 'ep'. No reference counterpart (SURVEY §2.4 "EP ❌").
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0
    moe_aux_coef: float = 0.01   # load-balance loss weight (Switch: 0.01)
    moe_z_coef: float = 1e-3     # router z-loss weight (ST-MoE: 1e-3)
    # The `mla_moe` family's facts (None for every other family).
    latent_moe: "LatentMoEConfig | None" = None
    # The `gdn_moe` family's facts (None for every other family).
    gdn_moe: "GdnMoEConfig | None" = None
    # The `conv_moe` family's facts (None for every other family).
    conv_moe: "ConvMoEConfig | None" = None
    # The `bd_moe` family's facts (None for every other family).
    bd_moe: "BdMoEConfig | None" = None
    # The `swa_moe` family's facts (None for every other family).
    swa_moe: "SwaMoEConfig | None" = None
    # The `early_moe` family's facts (None for every other family).
    early_moe: "EarlyMoEConfig | None" = None
    # The `kda_mla_moe` family's facts (None for every other family).
    kda_mla_moe: "KdaMlaMoEConfig | None" = None
    # The `ssm_moe` family's facts (None for every other family).
    ssm_moe: "SsmMoEConfig | None" = None
    # The `loop_llama` family's facts (None for every other family).
    loop_llama: "LoopLlamaConfig | None" = None
    # The `ssm_dense` family's facts (None for every other family).
    ssm_dense: "SsmDenseConfig | None" = None
    # The `dsa_moe` family's facts (None for every other family).
    dsa_moe: "DsaMoEConfig | None" = None
    # The `sambay` family's facts (None for every other family).
    sambay: "SambaYConfig | None" = None

    @property
    def head_dim(self) -> int:
        assert self.attn_dim % self.num_heads == 0
        return self.attn_dim // self.num_heads

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads if self.num_kv_heads is not None else self.num_heads

    @property
    def kv_dim(self) -> int:
        """Output width of wk/wv: kv_heads * head_dim (== attn_dim for MHA)."""
        return self.kv_heads * self.head_dim

    @property
    def family_facts(self) -> "str | None":
        """The name of the field that carries one family's facts, if this
        configuration has any (`DecoderStack.config_extra` names the one a
        family reads)."""
        for name in FAMILY_FACTS:
            if getattr(self, name) is not None:
                return name
        return None

    @property
    def experts_held(self) -> int:
        """Routed experts this job holds: all of them, unless the family's
        facts name a share with `experts_held`."""
        share = self.family_facts and getattr(self, self.family_facts)
        # (a dense family's facts name no share)
        if not share or getattr(share, "experts_held", None) is None:
            return self.num_experts
        return share.experts_held

    @property
    def expert_offset(self) -> int:
        """The first routed expert this job holds."""
        share = self.family_facts and getattr(self, self.family_facts)
        return getattr(share, "expert_offset", 0) if share else 0

    def padded_vocab_size(self, tp_size: int) -> int:
        """Vocab size rounded up to a multiple of tp_size.

        The reference handles non-divisible vocabs by giving the LAST rank a
        ragged partition (`/root/reference/models/layers.py:126-131`). Ragged
        shards are hostile to SPMD/XLA, so we instead pad the vocab dimension
        and mask the padded logits to -inf (see models/transformer.py).
        """
        return ((self.vocab_size + tp_size - 1) // tp_size) * tp_size

    def num_params(self) -> int:
        if self.family_facts is not None:
            # the family that reads these facts counts what it makes
            from .models import facts_family
            return facts_family(self).num_params(self)
        d, f, v, L = self.attn_dim, self.ffn_dim, self.vocab_size, self.num_layers
        kd = self.kv_dim
        attn = 2 * d * d + 2 * d * kd + 2 * d + 2 * kd  # wq/wo + wk/wv (+ biases)
        if self.num_experts:
            ffn = self.num_experts * 3 * d * f + d * self.num_experts  # experts + router
        else:
            ffn = 3 * d * f + 2 * f + d          # gate/up/down weights + biases
        norms = 2 * d
        return v * d + L * (attn + ffn + norms) + d + v * d + v  # emb + layers + final norm + lm_head


# the ModelConfig fields that carry one family's facts each
FAMILY_FACTS = ("latent_moe", "gdn_moe", "conv_moe", "bd_moe", "swa_moe",
                "early_moe", "kda_mla_moe", "ssm_moe", "loop_llama",
                "ssm_dense", "dsa_moe", "sambay")

# CLI flag-string -> Transformer.remat value (shared by train.py/bench.py)
REMAT_CHOICES = {"true": True, "dots": "dots", "false": False}

# Named model presets (BASELINE.md "configs to cover"). "45m" is the
# reference's exact shape (`/root/reference/constants.py:9-17`); "gpt2-124m"
# is BASELINE config 3 (GPT-2 small: d=768, 12 heads/layers, vocab 50257,
# ctx 1024 — untied lm_head like the reference, so ~190M actual params);
# "tiny" is BASELINE config 1 (2-layer d_model=128 GPT for CPU smoke runs).
MODEL_PRESETS = {
    "45m": ModelConfig(),
    "gpt2-124m": ModelConfig(attn_dim=768, ffn_dim=3072, num_heads=12,
                             num_layers=12, vocab_size=50257, maxlen=1024),
    "tiny": ModelConfig(attn_dim=128, ffn_dim=512, num_heads=4,
                        num_layers=2, vocab_size=1024, maxlen=256),
    # the 45m shape with its FFN swapped for 8 routed experts (top-2):
    # ~160M total params, 45m-class active compute per token
    "45m-moe8": ModelConfig(num_experts=8, moe_top_k=2),
    # GPT-2 Medium shape — 3x the reference's biggest config; params+Adam
    # state ~4.3 GiB f32, fits the 16 GiB chip with remat at b4xt1024
    "gpt2-355m": ModelConfig(attn_dim=1024, ffn_dim=4096, num_heads=16,
                             num_layers=24, vocab_size=50257, maxlen=1024),
    # the `mla_moe` family at a CPU size: latent attention (q/k 24 wide, v
    # 16), one dense layer then two expert layers of 8 routed experts
    # (sigmoid top-2, a shared expert), one multi-token-prediction module
    "tiny-mla-moe": ModelConfig(
        attn_dim=64, ffn_dim=128, num_heads=4, num_layers=3,
        vocab_size=1024, maxlen=256, rope_theta=10000.0, num_experts=8,
        moe_top_k=2, latent_moe=LatentMoEConfig(
            q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, moe_intermediate_size=32,
            routed_scaling_factor=2.5, num_nextn_predict_layers=1)),
    # the `mhc_mla_moe` family at a CPU size: `tiny-mla-moe` with its
    # residual state as 4 hyper-connection streams (20 Sinkhorn rounds) and
    # YaRN positions (factor 8 over an original context of 64 rows, shorter
    # than any test's sequence: two of the four rotary pairs are blended)
    "tiny-mhc-mla-moe": ModelConfig(
        attn_dim=64, ffn_dim=128, num_heads=4, num_layers=3,
        vocab_size=1024, maxlen=256, rope_theta=10000.0, num_experts=8,
        moe_top_k=2, latent_moe=LatentMoEConfig(
            q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, moe_intermediate_size=32,
            routed_scaling_factor=2.5, num_nextn_predict_layers=1,
            rope_scaling=YarnScaling(
                factor=8.0, original_max_position_embeddings=64,
                mscale=1.0, mscale_all_dim=1.0),
            hyper=HyperConnectionConfig())),
    # the `gdn_moe` family at a CPU size: two periods of three Gated
    # DeltaNet layers (2 key heads, 4 value heads, 16 wide) and one gated
    # full-attention layer (4 query heads over 2 key-value heads, 32 wide, a
    # quarter of it rotary); in every layer 8 routed experts (softmax top-2)
    # and a gated shared expert
    "tiny-gdn-moe": ModelConfig(
        attn_dim=64, ffn_dim=32, num_heads=4, num_kv_heads=2, num_layers=8,
        vocab_size=1024, maxlen=256, rope_theta=10000.0, num_experts=8,
        moe_top_k=2, gdn_moe=GdnMoEConfig(
            head_dim=32, linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=16, linear_value_head_dim=16,
            moe_intermediate_size=32, shared_expert_intermediate_size=32)),
    # the `conv_moe` family at a CPU size: LFM2's pattern in small, two
    # leading dense layers with convolution mixers, then (attention, conv,
    # conv) twice and (attention, conv) twice: periods of two lengths; 4
    # query heads over 2 key-value heads, 16 wide, with q/k norms; 8 routed
    # experts (sigmoid top-2, a selection bias, no shared expert)
    "tiny-conv-moe": ModelConfig(
        attn_dim=64, ffn_dim=128, num_heads=4, num_kv_heads=2, num_layers=12,
        vocab_size=1024, maxlen=256, rope_theta=1000000.0, num_experts=8,
        moe_top_k=2, conv_moe=ConvMoEConfig(
            layer_types=("conv", "conv")
            + ("full_attention", "conv", "conv") * 2
            + ("full_attention", "conv") * 2,
            moe_intermediate_size=32)),
    # the `bd_moe` family at a CPU size: block-diffusion training in blocks
    # of 4 positions; 4 query heads over 2 key-value heads of 32 (heads x
    # width = 128, not the model's 64), q/k norms; 8 routed experts
    # (softmax top-2, no shared expert); token 1 is the mask token
    "tiny-bd-moe": ModelConfig(
        attn_dim=64, ffn_dim=128, num_heads=4, num_kv_heads=2, num_layers=2,
        vocab_size=1024, maxlen=256, rope_theta=1000000.0, num_experts=8,
        moe_top_k=2, bd_moe=BdMoEConfig(
            head_dim=32, moe_intermediate_size=32, block_length=4,
            mask_token_id=1)),
    # the `swa_moe` family at a CPU size: Trinity's pattern in small, two
    # leading dense layers, then (window, window, window, full) twice; a
    # window of 16 rows, shorter than any test's sequence; 4 query heads
    # over 2 key-value heads of 32 (heads x width = 128, not the model's
    # 64), q/k norms, an output gate, four norms a layer; 8 routed experts
    # (sigmoid top-2, a selection bias updated at 0.001 a step, one shared
    # expert)
    "tiny-swa-moe": ModelConfig(
        attn_dim=64, ffn_dim=128, num_heads=4, num_kv_heads=2, num_layers=10,
        vocab_size=1024, maxlen=256, rope_theta=10000.0, num_experts=8,
        moe_top_k=2, swa_moe=SwaMoEConfig(
            layer_types=("sliding_attention",) * 2
            + ("sliding_attention",) * 3 + ("full_attention",)
            + ("sliding_attention",) * 3 + ("full_attention",),
            head_dim=32, moe_intermediate_size=32, sliding_window=16,
            route_scale=2.826, load_balance_coeff=0.001)),
    # the `early_moe` family at a CPU size: SmallThinker's pattern in small,
    # (full, window, window, window) twice with no leading dense layer; a
    # window of 16 rows, shorter than any test's sequence; 6 query heads
    # over 2 key-value heads of 32 (a group of 3: heads x width = 192, not
    # the model's 64), two norms a layer; 8 routed ReLU-gated experts
    # (softmax top-2 from the layer's input, no shared expert, no bias)
    "tiny-early-moe": ModelConfig(
        attn_dim=64, ffn_dim=0, num_heads=6, num_kv_heads=2, num_layers=8,
        vocab_size=1024, maxlen=256, rope_theta=1.5e6, num_experts=8,
        moe_top_k=2, early_moe=EarlyMoEConfig(
            sliding_window_layout=(0, 1, 1, 1) * 2,
            rope_layout=(0, 1, 1, 1) * 2, head_dim=32,
            moe_ffn_hidden_size=32, sliding_window_size=16)),
    # the `dsa_moe` family at a CPU size: every layer scores its earlier
    # tokens with 2 index heads of 16 and keeps a row's 16 best, fewer than
    # any test's sequence; 4 query heads over 2 key-value heads of 32 (heads
    # x width = 128, not the model's 64), q/k norms; 8 routed experts
    # (softmax top-2, no shared expert)
    "tiny-dsa-moe": ModelConfig(
        attn_dim=64, ffn_dim=0, num_heads=4, num_kv_heads=2, num_layers=2,
        vocab_size=1024, maxlen=256, rope_theta=1e7, num_experts=8,
        moe_top_k=2, dsa_moe=DsaMoEConfig(
            head_dim=32, moe_intermediate_size=32, indexer_num_heads=2,
            indexer_head_dim=16, topk=16)),
    # the `kda_mla_moe` family at a CPU size: Ling-3.0's pattern in small,
    # groups of three layers (two Kimi Delta Attention layers, 4 heads 16
    # wide, then one head-gated latent-attention layer, q/k 24 wide over a
    # 16-wide latent with no q latent), the first layer dense; 16 routed
    # experts in 4 groups of which a token keeps 2 (sigmoid top-2, a shared
    # expert); one multi-token-prediction module
    "tiny-kda-mla-moe": ModelConfig(
        attn_dim=64, ffn_dim=128, num_heads=4, num_layers=6,
        vocab_size=1024, maxlen=256, rope_theta=10000.0, num_experts=16,
        moe_top_k=2, kda_mla_moe=KdaMlaMoEConfig(
            head_dim=16, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, moe_intermediate_size=32,
            layer_group_size=3, first_k_dense_replace=1, n_group=4,
            topk_group=2, num_nextn_predict_layers=1, mtp_loss_weight=0.3)),
    # the `ssm_moe` family at a CPU size: Nemotron-H's pattern in small,
    # layers of one sublayer each, (expert, Mamba-2) three times and one
    # attention layer; 4 Mamba heads 16 wide over 2 groups of B and C, a
    # state 8 wide; 4 query heads over 2 key-value heads of 16, no
    # positions; 16 routed two-matrix relu2 experts in a 32-wide latent
    # (sigmoid top-3, scaling 2.5) and a shared expert 96 wide; one
    # multi-token-prediction module (an attention and an expert layer)
    "tiny-ssm-moe": ModelConfig(
        attn_dim=64, ffn_dim=96, num_heads=4, num_kv_heads=2, num_layers=7,
        vocab_size=1024, maxlen=256, num_experts=16, moe_top_k=3,
        ssm_moe=SsmMoEConfig(
            hybrid_override_pattern="EMEMEM*", mamba_num_heads=4,
            mamba_head_dim=16, ssm_state_size=8, n_groups=2, head_dim=16,
            moe_intermediate_size=48, moe_latent_size=32,
            moe_shared_expert_intermediate_size=96, chunk_size=32,
            routed_scaling_factor=2.5, num_nextn_predict_layers=1)),
    # the `loop_llama` family at a CPU size: two llama layers with four
    # norms each (4 heads of 16, SwiGLU 128 wide), passed three times a step
    # (three: a pass count of 1 or 2 cannot agree by accident), an exit
    # after every pass and the exit gate
    "tiny-loop-llama": ModelConfig(
        attn_dim=64, ffn_dim=128, num_heads=4, num_layers=2,
        vocab_size=1024, maxlen=256, rope_theta=1e6,
        loop_llama=LoopLlamaConfig(loop_steps=3)),
    # the `ssm_dense` family at a CPU size: Granite 4.0-H's layer in small,
    # a mixer and a SwiGLU in every layer, (Mamba-2 x 2, attention) twice
    # (a period that repeats) and one Mamba-2 layer more; 8 Mamba heads 16 wide over ONE group of B and C, a state 8
    # wide, chunks of 32; 4 query heads over 2 key-value heads of 16, no
    # positions; the four scalars at values no tolerance hides (none a
    # power of two but the softmax's, which is not 1 / sqrt(16))
    "tiny-ssm-dense": ModelConfig(
        attn_dim=64, ffn_dim=96, num_heads=4, num_kv_heads=2, num_layers=7,
        vocab_size=1024, maxlen=256,
        ssm_dense=SsmDenseConfig(
            layer_types=("mamba", "mamba", "attention") * 2 + ("mamba",),
            mamba_n_heads=8, mamba_d_head=16, mamba_d_state=8,
            mamba_chunk_size=32, embedding_multiplier=12.0,
            residual_multiplier=0.22, attention_multiplier=0.125,
            logits_scaling=8.0)),
    # the `sambay` family at a CPU size: all five kinds of layer in 8
    # published layers, (Mamba-1, window) twice, the Mamba-1 layer that
    # leaves the memory, the full layer that leaves its keys and values, one
    # gated memory unit and one cross-attention; scans 128 channels wide
    # over a state of 4 with a dt rank of 4; 4 query heads over 2 key heads
    # of 16 (2 differential heads over ONE pair, its value 32 wide); a
    # window of 16 rows, shorter than any test's sequence
    "tiny-sambay": ModelConfig(
        attn_dim=64, ffn_dim=96, num_heads=4, num_kv_heads=2, num_layers=8,
        vocab_size=1024, maxlen=256,
        sambay=SambaYConfig(num_hidden_layers=8, sliding_window=16,
                            mamba_d_state=4)),
}


def model_preset(name: str, **overrides) -> ModelConfig:
    if name not in MODEL_PRESETS:
        raise ValueError(
            f"unknown model preset {name!r}; expected one of "
            f"{sorted(MODEL_PRESETS)}")
    return dataclasses.replace(MODEL_PRESETS[name], **overrides)


@dataclass(frozen=True)
class MeshConfig:
    """5-D device mesh: ('dp', 'pp', 'cp', 'ep', 'tp').

    The reference supports exactly one axis (TP == world size, asserted at
    `/root/reference/process_manager.py:13`). We design for >=2 axes from day
    one per BASELINE.json config 5 (TPxDP 4x2), plus a context-parallel axis
    'cp' for long sequences (ring attention / Ulysses), a pipeline axis 'pp'
    (stage-sharded layer stack), and an expert axis 'ep' (MoE expert
    sharding; a pure extra data axis for dense compute) — all absent from
    the reference (SURVEY §2.4) and all defaulting to size 1, in which case
    the mesh degenerates to the reference-parity ('dp', 'tp') shape.
    """

    dp: int = 1
    tp: int = 1
    cp: int = 1
    ep: int = 1
    pp: int = 1

    @property
    def world_size(self) -> int:
        return self.dp * self.pp * self.cp * self.ep * self.tp


@dataclass(frozen=True)
class OptimizerConfig:
    """Adam + OneCycle, matching the reference's
    `optim.Adam` + `OneCycleLR` setup (`/root/reference/train.py:83-84`),
    including torch's OneCycle defaults (div_factor=25, final_div_factor=1e4,
    cosine annealing, and beta1 cycling between 0.85 and 0.95)."""

    lr: float = 3e-4
    warmup_steps: int = 2000
    max_steps: int = 20000
    betas: tuple = (0.9, 0.999)
    eps: float = 1e-8
    # OneCycle details (torch defaults)
    div_factor: float = 25.0
    final_div_factor: float = 1e4
    cycle_momentum: bool = True
    base_momentum: float = 0.85
    max_momentum: float = 0.95
    # Global-norm gradient clipping (torch clip_grad_norm_ semantics: one
    # norm over ALL grads, scale = max_norm / (norm + 1e-6) when exceeded).
    # None = off — the reference has no clipping (SURVEY non-goals), so off
    # stays the parity default.
    clip_grad_norm: "float | None" = None
    # Decoupled weight decay (torch.optim.AdamW semantics: params shrink by
    # lr*wd BEFORE the Adam step). 0.0 = plain Adam, the reference's setup.
    weight_decay: float = 0.0
    # 'onecycle' (reference parity) or 'cosine' (linear warmup over
    # warmup_steps -> cosine decay to cosine_min_ratio * lr; beta1 fixed —
    # the standard pretraining schedule the reference lacks).
    lr_schedule: str = "onecycle"
    cosine_min_ratio: float = 0.1


@dataclass(frozen=True)
class TrainConfig:
    data_path: str = ""
    save_dir: str = "./checkpoints"
    batch_size: int = 32
    max_steps: int = 20000
    log_interval: int = 100
    save_interval: int = 1000
    reserve_last_n_ckpts: int = -1
    bf16: bool = False
    seed: int = 0
    # Fixed-shape padding length for XLA (reference pads to per-batch max,
    # `/root/reference/dataset.py:41` — dynamic shapes would recompile under
    # jit, so we pad to model maxlen; CE ignore-index masking keeps the loss
    # identical).
    pad_to: Optional[int] = None
    # 'vocab_parallel' computes the CE loss on sharded logits (no all-gather
    # of the (b, t, vocab) tensor); 'gather' materialises full logits first,
    # matching the reference's lm_head gather_output=True data path
    # (`/root/reference/models/model.py:137`). Both are numerically equal.
    loss_mode: str = "vocab_parallel"
    # Resume from the latest checkpoint in save_dir (the reference cannot
    # resume training at all — save-only, `/root/reference/train.py:121-133`).
    resume: bool = False


@dataclass(frozen=True)
class EvalConfig:
    data_path: str = ""
    tokenizer_path: str = ""
    ckpt_dir: str = ""
    max_decode_len: int = 128
    batch_size: int = 1
    seed: int = 0
    bf16: bool = True


def replace(cfg, **kw):
    return dataclasses.replace(cfg, **kw)
