"""Config system.

Every assigned architecture is described by a :class:`ModelConfig`
(architecture) + :class:`TrainConfig` (optimizer/schedule) +
:class:`CompressionConfig` (the paper's technique).  Architectures register
themselves into :data:`ARCH_REGISTRY` so launchers can resolve ``--arch
<id>``.

Heterogeneous layer stacks (Jamba's 1:7 attn/mamba interleave, the VLM's
cross-attention insertion) are expressed as a repeated *superblock*: a short
pattern of layer kinds that is scanned ``n_blocks`` times with stacked
parameters.  This keeps the HLO size O(pattern) instead of O(layers), which
is what makes 61–100-layer configs compile quickly on a 512-device dry-run.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from typing import Callable, Dict, Optional, Sequence, Tuple

# Layer kinds that can appear in a superblock pattern.
ATTN = "attn"          # self-attention (GQA; sliding-window if window set)
MLA = "mla"            # DeepSeek-V3 multi-head latent attention
MAMBA = "mamba"        # Mamba2 SSD block
CROSS = "cross"        # cross-attention over encoder/patch embeddings (VLM)


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int                 # hidden size of each expert MLP
    num_shared_experts: int = 0      # DeepSeek-style always-on shared experts
    dense_residual_d_ff: int = 0     # Arctic-style parallel dense MLP (0 = off)
    aux_loss_coef: float = 0.001     # router load-balance loss
    every_n_layers: int = 1          # MoE on every n-th block position
    capacity_factor: float = 1.25    # per-expert capacity (train/prefill)


@dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V3 latent attention geometry [arXiv:2412.19437]."""
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 SSD geometry [arXiv:2405.21060]."""
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    chunk_size: int = 256


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int                     # 0 for attention-free archs
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // n_heads
    # Superblock pattern. Default: ("attn",) repeated n_layers times.
    block_pattern: Tuple[str, ...] = (ATTN,)
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    qkv_bias: bool = False
    tie_embeddings: bool = False
    # Sliding-window attention (0 = full causal). Used (a) natively by archs
    # that have it and (b) as the long_500k sub-quadratic variant for dense.
    sliding_window: int = 0
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    # VLM: number of image/patch (or audio frame) embeddings consumed by
    # cross-attention; the frontend producing them is stubbed per spec.
    num_encoder_tokens: int = 0
    encoder_dim: int = 0
    # DeepSeek multi-token prediction aux head depth (0 = off).
    mtp_depth: int = 0
    dtype: str = "bfloat16"
    source: str = ""                 # citation

    def __post_init__(self):
        if self.head_dim == 0 and self.n_heads > 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)

    @property
    def n_blocks(self) -> int:
        assert self.n_layers % len(self.block_pattern) == 0, (
            f"{self.name}: n_layers={self.n_layers} not divisible by "
            f"pattern {self.block_pattern}")
        return self.n_layers // len(self.block_pattern)

    def reduced(self, **overrides) -> "ModelConfig":
        """A smoke-test variant of the same family (<=2 blocks, d_model<=512,
        <=4 experts), per the assignment spec."""
        pat = self.block_pattern
        small: Dict = dict(
            n_layers=2 * len(pat),
            d_model=256,
            n_heads=min(self.n_heads, 8) if self.n_heads else 0,
            n_kv_heads=min(self.n_kv_heads, 4) if self.n_kv_heads else 0,
            d_ff=512 if self.d_ff else 0,
            vocab_size=512,
            head_dim=32 if self.n_heads else 0,
            num_encoder_tokens=16 if self.num_encoder_tokens else 0,
            encoder_dim=128 if self.encoder_dim else 0,
            name=self.name + "-smoke",
            dtype="float32",
        )
        if self.moe is not None:
            small["moe"] = replace(
                self.moe, num_experts=4, top_k=min(self.moe.top_k, 2),
                d_ff_expert=256,
                num_shared_experts=min(self.moe.num_shared_experts, 1),
                dense_residual_d_ff=256 if self.moe.dense_residual_d_ff else 0,
                capacity_factor=8.0)   # dropless at smoke scale
        if self.mla is not None:
            small["mla"] = MLAConfig(q_lora_rank=64, kv_lora_rank=32,
                                     qk_nope_head_dim=32, qk_rope_head_dim=16,
                                     v_head_dim=32)
        if self.ssm is not None:
            small["ssm"] = replace(self.ssm, d_state=16, head_dim=32,
                                   chunk_size=32)
        if self.sliding_window:
            small["sliding_window"] = 64
        small.update(overrides)
        return replace(self, **small)


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                        # "train" | "prefill" | "decode"


INPUT_SHAPES: Dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


@dataclass(frozen=True)
class CompressionConfig:
    """The paper's technique as a first-class config block."""
    method: str = "none"             # none|sparse_gd|dgc|lgc_ps|lgc_rar|lgc_rar_q8
    sparsity: float = 0.001          # alpha = 0.1% top-k (paper Section V-A)
    innovation_sparsity: float = 1e-5  # 0.001% coarse innovation (LGC-PS)
    warmup_steps: int = 200          # phase-1 raw-gradient updates
    ae_train_steps: int = 300        # phase-2 (AE online training) length
    ae_lr: float = 1e-3              # paper Section VI-A
    lambda_rec: float = 1.0
    lambda_sim: float = 0.5          # paper Fig 14: lambda2 = 0.5
    momentum_correction: float = 0.9 # DGC-style momentum correction factor
    bottleneck_channels: int = 4     # Table I conv5 filter count
    encode_quant_bits: int = 0       # beyond-paper: quantize encodings (0=off)
    exempt_first_last: bool = True   # paper Section VI-A layer exemption
    # communication substrate for the distributed step: "mesh" (lax
    # collectives, XLA picks the allreduce algorithm), "ring" (the
    # paper's explicit chunked ring schedule, wire bytes measured by
    # repro.dist.collectives), "ring_q8" (ring whose compressed-payload
    # reductions ship int8 values + per-block f32 scales — the transport
    # that makes lgc_rar_q8's 1-byte/value rate claim real), "ring_hier"
    # (hierarchical intra-pod/inter-pod rings on multi-axis dp meshes;
    # last mesh axis = intra-pod) or "ring_packed" (the packed sparse
    # wire: sparse_gd/dgc/lgc_ps top-k exchanges ship bit-packed indices
    # + int8 values + per-block f32 scales, ~0.33x of the raw f32+int32
    # exchange at 1M params).  The single-host emulation transport
    # ("sim") is selected via GradientCompressor.sim_step, not here.
    transport: str = "mesh"
    # int8-wire scale granularity: one f32 scale per this many values
    # (0 = repro.dist.quantize.SCALE_BLOCK).  Shared by the real wires
    # (ring_q8's reductions, ring_packed's sparse values) and the
    # fake-quant paths, so their numerics are comparable and rate.py's
    # byte accounting matches the measured tally.
    q8_scale_block: int = 0
    # hierarchical-ring per-level message chunking, in elements
    # (0 = one message per hop; bytes are unchanged either way)
    ring_intra_chunk: int = 0
    ring_inter_chunk: int = 0
    # bucketed, double-buffered exchange schedule: split every ring
    # exchange into this many buckets and software-pipeline them —
    # bucket b's ppermute hops issue while bucket b+1 encodes
    # (quantize/pack), so compression compute overlaps network time
    # instead of adding to it (see DESIGN.md "The overlapped
    # exchange").  1 = the historical unbucketed schedule.  Float
    # wires are bit-identical at any bucket count; the int8 wires
    # re-block their scale groups per bucket (documented q8 bound).
    wire_buckets: int = 1
    # residual top-k selection backend: "jnp" (lax.top_k reference),
    # "pallas" (kernels/ops.global_topk, one launch per leaf) or "fused"
    # (the single-sweep segmented kernel: EF accumulate + per-leaf
    # selection of every exempt+compressed leaf in ONE launch — see
    # DESIGN.md "The fused sparsification sweep").  Every Pallas kernel
    # compiles on a TPU backend and is interpreted elsewhere
    # (repro.kernels.interpret_mode).
    topk_backend: str = "jnp"
    # fused sweep's per-block candidate extraction: "loop" (sequential
    # max->record->mask, O(k) reductions per block — cheapest at small
    # k), "bitonic" (the lanes-parallel sorting network in
    # kernels/bitonic.py, O(log^2 block) stages independent of k) or
    # "auto" (bitonic once k_max crosses the loop's economic threshold
    # — see core.sparsify.EXTRACT_BACKENDS).  Both are exact and
    # tie-identical; ignored unless topk_backend="fused".
    extract_backend: str = "auto"
    # phase-3 encoder backend: "jnp" (conv_general_dilated reference) or
    # "pallas" (ops.lgc_encode_fast — im2col + fused MXU matmul kernel)
    ae_backend: str = "jnp"
    # exchange guard policy (repro.dist.chaos.GUARD_POLICIES): "off"
    # (the historical executor, zero added trace), "scrub" (zero
    # non-finite/out-of-range op results and structurally-invalid packed
    # contributions — the masked gradient stays in the EF residual and
    # re-ships next round), "skip_round" (scrub AND drop the whole
    # round's global gradient when any fault is seen) or "fail_fast"
    # (scrub at trace level; the driver raises WireFaultError naming
    # the faulting op labels from the recorded per-op counts)
    guard: str = "off"
    # append one int32 checksum word to every packed payload so the
    # guard catches arbitrary finite bit-flips; +4 bytes per payload,
    # priced honestly in both pricers (packed.index_nbytes/wire_nbytes)
    guard_checksum: bool = False
    # seeded fault injection (repro.dist.chaos.FaultSpec) — when any
    # count/node is set, the transport stack auto-wraps in
    # chaos:<base>.  Counts are per targeted op per step trace; fault
    # positions derive from (fault_seed, op label), identical on every
    # transport.  fault_ops: comma-separated plan-op labels to target
    # ("" = all ops).
    fault_seed: int = 0
    fault_bitflips: int = 0
    fault_nans: int = 0
    fault_infs: int = 0
    fault_drop_node: int = -1
    fault_stale_node: int = -1
    fault_ops: str = ""


@dataclass(frozen=True)
class TrainConfig:
    optimizer: str = "sgd_momentum"  # paper trains with momentum SGD
    learning_rate: float = 0.1
    momentum: float = 0.9
    weight_decay: float = 1e-4
    adam_b1: float = 0.9
    adam_b2: float = 0.95
    grad_clip_norm: float = 0.0
    steps: int = 100
    seed: int = 0
    microbatch: int = 0              # 0 = no gradient accumulation
    remat: bool = True               # activation checkpointing per block
    compression: CompressionConfig = field(default_factory=CompressionConfig)


ARCH_REGISTRY: Dict[str, Callable[[], ModelConfig]] = {}


def register_arch(name: str):
    def deco(fn: Callable[[], ModelConfig]):
        ARCH_REGISTRY[name] = fn
        return fn
    return deco


def get_arch(name: str) -> ModelConfig:
    import repro.configs  # noqa: F401  (triggers registration)
    if name not in ARCH_REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ARCH_REGISTRY)}")
    return ARCH_REGISTRY[name]()


def list_archs() -> Sequence[str]:
    import repro.configs  # noqa: F401
    return sorted(ARCH_REGISTRY)
