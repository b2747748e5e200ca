"""Configuration system for the DeFTA reproduction framework.

Frozen dataclasses so configs are hashable (usable as jit static args) and
immutable. Every assigned architecture is expressed as a ``ModelConfig``;
input shapes are ``ShapeConfig`` presets; distribution is ``MeshConfig``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------

# Block kinds used by blocks.py to assemble a layer stack.
ATTN_DENSE = "attn_dense"      # attention + dense MLP
ATTN_MOE = "attn_moe"          # attention + MoE FFN
MAMBA = "mamba"                # Mamba2 SSD block (no attention)
MAMBA_MOE = "mamba_moe"        # Mamba2 block + MoE FFN (Jamba MoE layers)


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts FFN configuration."""
    num_experts: int
    top_k: int
    num_shared_experts: int = 0      # always-on experts (DeepSeekMoE)
    d_expert: int = 0                # per-expert FFN hidden size
    router_aux_weight: float = 0.01  # load-balance loss weight (softmax:
                                     # Switch loss; sigmoid: DeepSeek-V3's
                                     # sequence-wise balance loss, alpha)
    # "softmax": top-k of softmax probabilities (Switch aux loss);
    # "sigmoid": DeepSeek-V3's noaux_tc router (arXiv:2412.19437 §2.1.2):
    # top-k on sigmoid scores + a selection-only bias, gates from the
    # unbiased scores normalised over the k and scaled by routed_scaling,
    # the bias moved after each step by bias_update_rate * sign(mean load
    # - load). Sigmoid layers dispatch dropless (models/moe.moe_share).
    router: str = "softmax"
    routed_scaling: float = 1.0
    bias_update_rate: float = 0.0
    # the experts this chip holds of each layer under expert parallelism:
    # experts [held_start, held_start + num_held); 0 = all. The router
    # keeps all num_experts outputs; the layer returns the held experts'
    # part of the result (sigmoid router only).
    num_held: int = 0
    held_start: int = 0

    @property
    def held(self) -> int:
        return self.num_held or self.num_experts


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2/V3): a compressed KV latent
    with its own RMSNorm and a decoupled rotary key shared by all heads.
    Queries are a plain projection (q_lora_rank null, as Moonlight)."""
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 (SSD) block configuration."""
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64               # SSD head dim (d_inner / n_heads)
    chunk_size: int = 256            # SSD chunked-scan block length


@dataclass(frozen=True)
class ModelConfig:
    """A decoder-style transformer/SSM/hybrid/enc-dec model."""
    name: str
    family: str                      # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // num_heads
    # attention options
    qkv_bias: bool = False           # Qwen2.5-style QKV bias
    mlp_gelu: bool = False           # 2-matrix GELU MLP (gpt-bigcode style)
    qk_norm: bool = False            # Qwen3-style per-head RMSNorm on q,k
    rope_theta: float = 10_000.0
    sliding_window: int = 0          # 0 = full causal; >0 = window size
    # FFN / block structure
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    mla: Optional[MLAConfig] = None  # latent attention in place of GQA
    attn_period: int = 1             # hybrid: 1 attention layer every N layers
                                     # (jamba: 8 -> layers i%8==attn_offset attn)
    attn_offset: int = 0
    moe_period: int = 1              # MoE FFN every N layers (jamba: 2)
    moe_offset: int = 1
    first_dense: int = 0             # leading dense-FFN layers (deepseek/kimi: 1)
    # encoder-decoder (whisper)
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0
    encoder_seq_len: int = 0         # fixed encoder positions (whisper: 1500)
    # vlm
    num_vision_tokens: int = 0       # stub patch embeddings prepended
    # numerics
    dtype: str = "bfloat16"
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # remat/scan
    scan_layers: bool = True
    remat: bool = True

    def __post_init__(self):
        if self.head_dim == 0 and self.num_heads > 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)

    # -- derived block schedule -------------------------------------------
    def block_kind(self, layer_idx: int) -> str:
        """Which block kind layer ``layer_idx`` is."""
        is_attn = True
        if self.ssm is not None and self.family in ("ssm", "hybrid"):
            if self.family == "ssm":
                is_attn = False
            else:  # hybrid: attention every attn_period layers
                is_attn = (layer_idx % self.attn_period) == self.attn_offset
        is_moe = self.moe is not None and (
            (layer_idx % self.moe_period) == self.moe_offset
            if self.moe_period > 1 else True)
        if layer_idx < self.first_dense:
            is_moe = False
        if is_attn and is_moe:
            return ATTN_MOE
        if is_attn:
            return ATTN_DENSE
        if is_moe:
            return MAMBA_MOE
        return MAMBA

    def block_schedule(self) -> Tuple[str, ...]:
        return tuple(self.block_kind(i) for i in range(self.num_layers))

    # -- parameter counting (for roofline MODEL_FLOPS) ---------------------
    def param_count(self, active_only: bool = False) -> int:
        d, hd = self.d_model, self.head_dim
        n_q, n_kv = self.num_heads, self.num_kv_heads
        total = self.vocab_size * d                      # embedding
        if not self.tie_embeddings:
            total += self.vocab_size * d                 # lm head
        for i in range(self.num_layers):
            kind = self.block_kind(i)
            if kind in (ATTN_DENSE, ATTN_MOE) and self.mla is not None:
                a = self.mla
                total += (d * n_q * a.qk_head_dim
                          + d * (a.kv_lora_rank + a.qk_rope_head_dim)
                          + a.kv_lora_rank
                          + a.kv_lora_rank * n_q * (a.qk_nope_head_dim
                                                    + a.v_head_dim)
                          + n_q * a.v_head_dim * d)
            elif kind in (ATTN_DENSE, ATTN_MOE):
                attn = d * (n_q * hd) + 2 * d * (n_kv * hd) + (n_q * hd) * d
                total += attn
            else:  # mamba block (matches models/ssm.init_ssm exactly)
                s = self.ssm
                d_in = s.expand * d
                nh = d_in // s.head_dim
                d_proj = 2 * d_in + 2 * s.d_state + nh
                conv_dim = d_in + 2 * s.d_state
                total += d * d_proj + d_in * d + s.d_conv * conv_dim \
                    + conv_dim + 3 * nh + d_in
            if kind in (ATTN_MOE, MAMBA_MOE):
                m = self.moe
                n_e = m.held
                if active_only:         # experts a token reaches here
                    n_e = m.top_k if m.held == m.num_experts \
                        else m.top_k * m.held / m.num_experts
                per_expert = 3 * d * m.d_expert
                total += n_e * per_expert + m.num_shared_experts * per_expert
                total += d * m.num_experts                # router
                if m.router == "sigmoid":
                    total += m.num_experts                # selection bias
            else:
                mats = 2 if self.mlp_gelu else 3
                total += mats * d * self.d_ff             # dense FFN
            total += 2 * d                                # norms
        if self.is_encoder_decoder:
            # encoder layers: self-attn + GELU FFN; decoder adds cross-attn
            enc = self.num_encoder_layers * (
                4 * d * (n_q * hd) + 2 * d * self.d_ff + 2 * d)
            xattn = self.num_layers * (d * (n_q * hd) + 2 * d * (n_kv * hd)
                                       + (n_q * hd) * d + d)
            total += enc + xattn
        return int(round(total))


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    mode: str                        # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Mesh / distribution
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MeshConfig:
    multi_pod: bool = False
    data: int = 16
    model: int = 16
    pods: int = 2

    @property
    def shape(self):
        return (self.pods, self.data, self.model) if self.multi_pod \
            else (self.data, self.model)

    @property
    def axis_names(self):
        return ("pod", "data", "model") if self.multi_pod \
            else ("data", "model")

    @property
    def num_devices(self):
        n = self.data * self.model
        return n * self.pods if self.multi_pod else n


# ---------------------------------------------------------------------------
# DeFTA / federated run configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DeFTAConfig:
    """The paper's algorithm knobs (§3)."""
    num_workers: int = 20
    avg_peers: int = 4               # average outdegree (paper: 4)
    num_sampled: int = 2             # |S_i| sampled peers per round (paper: 2)
    topology: str = "random_kout"    # ring | random_kout | erdos | dense
    aggregation: str = "defta"       # weighted: defta | defl | uniform;
                                     # Byzantine-robust baselines (see
                                     # scenarios/robust_agg.py):
                                     # trimmed_mean | median | krum
    robust_trim: float = 0.25        # trim/f fraction for the robust rules
    use_dts: bool = True
    dts_signal: str = "loss"         # trust signal for the DTS confidence
                                     # update (core/dts.py, the engine's
                                     # trust_update stage):
                                     # "loss" — the paper's loss-delta
                                     #   (Algorithm 3 line 12, bit-exact
                                     #   legacy behaviour);
                                     # "geom" — update-geometry scores
                                     #   (cosine to the trust-weighted
                                     #   median direction, norm-ratio
                                     #   outlier, sign-agreement), per-peer
                                     #   resolution the loss delta lacks;
                                     # "both" — loss_trust + λ·geom_trust
                                     #   fused (λ = dts_geom_weight);
                                     # "corr" — cross-round collusion
                                     #   suspicion from sign-sketch
                                     #   correlation clustering (DTS v3,
                                     #   the anti-ALIE signal);
                                     # "all"  — loss + λg·geom + λc·corr,
                                     #   the full fusion
    dts_geom_weight: float = 1.0     # λg scaling the geometric trust term
    dts_corr_weight: float = 4.0     # λc scaling the correlation trust
                                     # term (suspicion scores are O(1)
                                     # cluster masses, smaller than loss
                                     # deltas under attack — the default
                                     # rebalances them)
    dts_sketch_rounds: int = 8       # R: sketch ring-buffer depth (rounds
                                     # of update history the correlation
                                     # signal sees)
    dts_sketch_dim: int = 64         # S: count-sketch width per round
                                     # (sketch state is [W, R, S] — tiny
                                     # next to the model params)
    dts_conf_decay: float = 1.0      # per-round multiplicative decay of a
                                     # worker's confidence row toward the
                                     # uninformative prior (0). 1.0 = off
                                     # (dense-participation default, keeps
                                     # the "loss" goldens bit-identical);
                                     # cross-device worlds default it on so
                                     # a peer last seen 400 rounds ago is
                                     # not trusted on stale evidence —
                                     # applied lazily at gather time as
                                     # decay ** (rounds since last fired)
    dts_min_obs: int = 2             # minimum stamp-matched sketch-slot
                                     # pairs before a (i, j) correlation
                                     # entry feeds the colluder suspicion
                                     # score (cross-device sparse
                                     # observation: peers seen together in
                                     # fewer than this many common rounds
                                     # contribute neither suspicion nor
                                     # baseline — colluders can't hide in
                                     # sampling noise, singletons can't be
                                     # framed by it)
    max_staleness: int = 0           # drop a peer's contribution from the
                                     # merge when its model is more than
                                     # this many rounds older than the
                                     # receiver's (0 = off). Sync engines
                                     # compare per-worker epoch counters
                                     # (stragglers/churn open gaps); the
                                     # cross-device path compares global
                                     # rounds since the peer last fired.
                                     # Build-time gated: 0 adds no ops
    time_machine: bool = True        # §3.3 damage check + backup rollback.
                                     # Off for the classical robust-agg
                                     # baselines: those algorithms have no
                                     # rollback — leaving DeFTA's time
                                     # machine under them would credit the
                                     # baseline with DeFTA's own defense
    crelu_slope: float = 0.2         # paper Eq. 13
    local_epochs: int = 10           # paper: 10 local epochs per round
    gossip_every: int = 1            # production: gossip every K steps
    gossip_dtype: str = "float32"    # wire format for the gossip payload:
                                     # "float32" | "bfloat16" | "int8"
                                     # (bf16 halves gossip bytes, int8
                                     # quarters them; kernels accumulate
                                     # in fp32 — see core/gossip.py)
    gossip_error_feedback: bool = True
                                     # EF21 residual compensation for lossy
                                     # wire formats (no-op at float32):
                                     # quantization error is fed back into
                                     # next round's payload instead of
                                     # compounding
    gossip_wire_round: str = "nearest"
                                     # int8 wire rounding: "nearest" |
                                     # "stochastic" (unbiased per round —
                                     # E[dequant] == payload; see
                                     # core/gossip.quantize_rows_int8).
                                     # Consumed by the simulation engines;
                                     # the --fl pods trainer takes it via
                                     # train.py --gossip-wire-round
                                     # (build_gossip_step(wire_round=))
    # differential privacy (the paper's FedAvg-algorithm-compatibility
    # claim: DP-SGD slots into local training unchanged).
    # dp_clip > 0 selects in-training DP-SGD (clip + noise every
    # minibatch gradient); dp_clip == 0 with dp_sigma > 0 selects the
    # per-ROUND update-DP stage instead: the local-update delta is
    # clipped to dp_update_clip and gets one N(0, σ·clip) draw per round
    # (engine stage ``dp_noise``, build-time gated — σ=0 traces nothing)
    dp_clip: float = 0.0             # per-example L2 clip (0 = off)
    dp_sigma: float = 0.0            # gaussian noise multiplier
    dp_update_clip: float = 1.0      # L2 clip of the per-round update
                                     # delta on the dp_noise stage
    # secure aggregation wire (core/secagg.py): None = plaintext wire,
    # "pairwise" = per-directed-edge one-time pads in the wire format's
    # integer ring — receiver-side unmask, exact by construction,
    # composes with int8/bf16 + EF21 and every transport
    secagg: Optional[str] = None
    secagg_mode: str = "edge"        # "edge": receiver unmasks per edge,
                                     # DTS sees per-peer updates unchanged;
                                     # "masked_geom": trust limited to the
                                     # aggregate-minus-own-contribution
                                     # signal (dts.masked_geom_trust) —
                                     # the honest secagg-vs-DTS tension
    seed: int = 0


@dataclass(frozen=True)
class TrainConfig:
    optimizer: str = "adam"          # sgd | adam | adafactor | fedadam
    learning_rate: float = 0.01      # paper default
    weight_decay: float = 0.0
    momentum: float = 0.0
    batch_size: int = 64             # paper default
    epochs: int = 100                # paper: global epochs E
    grad_clip: float = 0.0
    microbatches: int = 1            # grad-accumulation steps
    seed: int = 0


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    shape: ShapeConfig
    mesh: MeshConfig = MeshConfig()
    defta: DeFTAConfig = DeFTAConfig()
    train: TrainConfig = TrainConfig()


def reduced(cfg: ModelConfig, *, num_layers: int = 2, d_model: int = 256,
            max_experts: int = 4) -> ModelConfig:
    """Reduced same-family variant for CPU smoke tests (spec: 2 layers,
    d_model<=512, <=4 experts)."""
    hd = max(32, d_model // max(cfg.num_heads, 1))
    n_heads = max(2, min(cfg.num_heads, d_model // hd))
    n_kv = max(1, min(cfg.num_kv_heads, n_heads))
    while n_heads % n_kv:
        n_kv -= 1
    moe = cfg.moe
    if moe is not None:
        moe = dataclasses.replace(
            moe, num_experts=min(moe.num_experts, max_experts),
            num_held=min(moe.num_held, max_experts), held_start=0,
            top_k=min(moe.top_k, 2),
            num_shared_experts=min(moe.num_shared_experts, 1),
            d_expert=min(moe.d_expert, d_model))
    ssm = cfg.ssm
    if ssm is not None:
        ssm = dataclasses.replace(ssm, d_state=16, head_dim=32, chunk_size=32)
    mla = cfg.mla
    if mla is not None:
        mla = MLAConfig(kv_lora_rank=64, qk_nope_head_dim=32,
                        qk_rope_head_dim=16, v_head_dim=32)
    # keep the hybrid interleave meaningful at 2 layers
    attn_period = min(cfg.attn_period, num_layers) if cfg.attn_period > 1 else 1
    return dataclasses.replace(
        cfg, name=cfg.name + "-smoke", num_layers=num_layers,
        d_model=d_model, num_heads=n_heads, num_kv_heads=n_kv,
        d_ff=min(cfg.d_ff, 2 * d_model) or 2 * d_model,
        vocab_size=min(cfg.vocab_size, 1024), head_dim=hd,
        moe=moe, ssm=ssm, mla=mla, attn_period=attn_period,
        attn_offset=min(cfg.attn_offset, max(0, attn_period - 1)),
        num_encoder_layers=min(cfg.num_encoder_layers, 2),
        encoder_seq_len=min(cfg.encoder_seq_len, 64),
        num_vision_tokens=min(cfg.num_vision_tokens, 16),
        sliding_window=min(cfg.sliding_window, 64) if cfg.sliding_window else 0,
        dtype="float32", scan_layers=False, remat=False)
