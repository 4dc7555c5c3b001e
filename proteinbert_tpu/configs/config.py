"""Config system for the TPU build.

The reference has no config system at all — every hyperparameter is a
constant in the smoke driver (reference dummy_tests.py:16-19,102-141) or a
kwarg default (reference utils.py:220-231, modules.py:243-245). Here the
whole framework is driven by one frozen dataclass tree so configs hash, are
jit-static-friendly, and carry the tiny/base/long/large presets from
BASELINE.json.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture of the dual-track ProteinBERT model.

    Defaults mirror the reference smoke config (reference dummy_tests.py:
    110-118: seq_len 256, local 128, global 512, key 64, 4 heads, 6 blocks)
    but the model here is shape-parametric in seq_len (the reference's
    LayerNorm hard-codes L at construction, modules.py:148-151 — fixed).
    """

    vocab_size: int = 26                # 22 AA chars + 4 specials (data/vocab.py)
    num_annotations: int = 8943         # GO terms with >=100 records (SURVEY C3)
    local_dim: int = 128                # local (per-residue) channel dim C
    global_dim: int = 512               # global (per-protein) dim G
    key_dim: int = 64                   # attention key dim per head
    num_heads: int = 4                  # global-attention heads
    num_blocks: int = 6                 # dual-track blocks
    narrow_kernel: int = 9              # narrow Conv1d kernel (modules.py:126)
    wide_kernel: int = 9                # wide Conv1d kernel (modules.py:137)
    wide_dilation: int = 5              # wide Conv1d dilation (modules.py:141)
    dtype: str = "bfloat16"             # activation dtype (MXU-native)
    param_dtype: str = "float32"        # parameter dtype
    remat: bool = False                 # jax.checkpoint each block
    remat_policy: str = "full"          # "full" (recompute everything) |
                                        # "convs" (save the two conv outputs
                                        # per block — ~85% of block FLOPs —
                                        # and recompute only the cheap tail)
    scan_blocks: bool = True            # lax.scan over stacked block params
    scan_unroll: int = 1                # lax.scan unroll factor: XLA sees k
                                        # block bodies per iteration and can
                                        # keep activation layouts across
                                        # them (the scan's saves are a
                                        # measured cost, PERF.md section
                                        # 5); full unroll
                                        # (scan_blocks=False) is compile-
                                        # prohibitive at real sizes
    scan_split_transpose: bool = False  # lax.scan(_split_transpose=True):
                                        # transpose the block scan as two
                                        # passes (recompute-forward, then
                                        # grad sweep) so XLA can schedule
                                        # the saves' layout traffic
                                        # separately from the grad math —
                                        # an experimental alternative lever
                                        # on the same measured scan-
                                        # boundary cost scan_unroll targets
    use_pallas: bool = False            # Pallas fused local-track kernel

    @property
    def value_dim(self) -> int:
        # reference modules.py:119: value_dim = global_dim // num_heads
        assert self.global_dim % self.num_heads == 0
        return self.global_dim // self.num_heads


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    """Architecture of the causal expert decoder (`models/glm_moe.py`;
    GLM-4.7-Flash, `model_type: glm4_moe_lite`): latent attention, one
    leading dense layer then expert layers (routed + one shared expert,
    sigmoid router balanced by a bias that no gradient trains), and one
    multi-token-prediction module. Key names follow the published
    config.json; the defaults are its values.

    With `layer_group_size` > 0 the same decoder is a HYBRID stack
    (Ling-3.0-flash, `model_type: bailing_hybrid`): the layer with the
    published index i has the latent mixer where (i + 1) %
    layer_group_size == 0 and the delta-rule linear-attention mixer (KDA,
    `ops/kda.py`) elsewhere; the router chooses among `topk_group` of
    `n_group` groups of experts; both mixers end in a head-wise sigmoid
    gate. `first_layer_index` is the published index of the first layer
    held here (the stack's phase in the period).

    With `mixer` "cca" the same decoder is ZAYA1 (`model_type: zaya`),
    built on the serving path: every layer has the compressed
    convolutional attention mixer (`models/glm_moe.cca_mixer`:
    `num_attention_heads` query heads read `num_key_value_heads` key and
    value heads of `cca_head_dim`, q and k mixed by two short causal
    convolutions of `cca_time0` / `cca_time1` taps, rotary over
    `partial_rotary_factor` of a head) and routed experts alone, chosen
    by an MLP router (`ops/moe.route_mlp`) whose `router_hidden_size`-wide
    state is carried from layer to layer, and both sublayers scale and
    shift the stream and their own result by learned vectors. The router's
    kind and the residual scaling come with the mixer and cannot be asked
    for apart from it (`router`, `residual_scaling` below).

    With `hybrid_override_pattern` the same decoder is Nemotron-H / Nemotron
    3 (`model_type: nemotron_h`), built on the serving path: the pattern is
    the published STRING, one character a layer, and a layer is ONE
    sublayer (one norm, one mixer or one feed-forward part, one add):
    `M` a Mamba-2 mixer (`ops/ssd.py`: `mamba_num_heads` heads of
    `mamba_head_dim` on a state of `ssm_state_size`, B and C shared by the
    heads of each of `n_groups` groups, a causal convolution of
    `conv_kernel` taps in front, chunks of `chunk_size`), `*` attention
    (`num_attention_heads` query heads on `num_key_value_heads` key heads
    of `cca_head_dim`, no position term), `E` LatentMoE: the routed
    experts `moe_latent_size` wide between one product down and one up,
    two matrices an expert and squared ReLU (`mlp_hidden_act` "relu2"),
    beside a shared expert of its own width
    (`moe_shared_expert_intermediate_size`) on the stream. The layers held
    are characters `first_layer_index` .. + `num_hidden_layers` of it.

    Three fields describe THIS CHIP'S SHARE of an expert-parallel group
    rather than the model: `experts_held` of the `n_routed_experts` the
    router scores (ids `expert_offset` ..), and `vocab_size` rows of the
    embedding and the head (ids are drawn from the slice). The router
    keeps its published width; what the absent experts would add is
    left out (models/glm_moe.py)."""

    vocab_size: int = 154_880
    hidden_size: int = 2048
    num_hidden_layers: int = 47         # leading dense layers included
    first_k_dense_replace: int = 1
    intermediate_size: int = 10_240     # the dense layers' SwiGLU width
    moe_intermediate_size: int = 1536   # each expert's SwiGLU width
    n_routed_experts: int = 64          # the router's width
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    experts_held: int = 64              # routed experts this chip holds
    expert_offset: int = 0              # id of the first one held
    n_group: int = 1                    # groups of experts the router
    topk_group: int = 1                 # chooses among, and how many it keeps
    num_attention_heads: int = 20
    q_lora_rank: Optional[int] = 768    # None: q straight from x (no low rank)
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    rope_interleave: bool = False       # rotary pairs (2j, 2j + 1), not half-split
    partial_rotary_factor: float = 1.0  # the share of a CCA head that turns
    mixer: str = "latent"               # "latent" | "cca": the token-to-token mixer
    num_key_value_heads: Optional[int] = None   # CCA: key / value heads (None: as queries)
    cca_head_dim: int = 128
    cca_time0: int = 2                  # taps of CCA's depthwise convolution
    cca_time1: int = 2                  # taps of its grouped (head-wise) one
    router_hidden_size: int = 256       # the MLP router's width and carried state
    rms_norm_eps: float = 1e-5
    num_nextn_predict_layers: int = 1
    layer_group_size: int = 0           # 0: every mixer is latent attention
    first_layer_index: int = 0          # published index of the first layer held
    mixer_output_gate: bool = False     # y = W_o[heads * sigmoid(x W_g)], head-wise
    kda_head_dim: int = 128             # d_k = d_v of a KDA head (`head_dim`)
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0       # the log decay lies in (this, 0)
    kda_chunk: int = 64                 # tokens per chunk of the KDA scan
    hybrid_override_pattern: str = ""   # one character a published layer: M | * | E
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8                   # groups of Mamba heads sharing one B and C
    conv_kernel: int = 4                # taps of the Mamba mixer's convolution
    chunk_size: int = 128               # tokens per chunk of the state-space scan
    time_step_min: float = 0.001        # dt_bias starts as the inverse softplus of
    time_step_max: float = 0.1          # a log-uniform draw between these,
    time_step_floor: float = 1e-4       # floored (`glm_moe.init_served`)
    moe_latent_size: Optional[int] = None   # the routed experts' own width (None: the stream's)
    moe_shared_expert_intermediate_size: Optional[int] = None   # None: n_shared_experts
                                        # x moe_intermediate_size
    mlp_hidden_act: str = "silu"        # "silu": SwiGLU experts | "relu2": two matrices
    mtp_loss_weight: float = 0.3        # lambda (assumed; not in config.json)
    bias_update_speed: float = 1e-3     # gamma (assumed)
    init_std: float = 0.02              # (assumed)
    # The served hybrid tree's recipe (`glm_moe.init_served`; assumed):
    # the embedding's rows, and the products that write into the
    # residual stream (a mixer's `o`, an FFN's `down`). None: `init_std`.
    embed_init_std: Optional[float] = None
    out_init_std: Optional[float] = None
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    attention_block: int = 512          # queries (and keys, on a TPU: the flash
                                        # kernel's tile) per block of attention
    expert_block: int = 512             # rows per block of the grouped products
    loss_chunk: int = 1024              # positions per chunk of the head loss

    @property
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def hybrid(self) -> bool:
        return self.layer_group_size > 0

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def pattern_held(self) -> str:
        """The pattern's characters of the layers held here ("" where
        the stack has no pattern string)."""
        return self.hybrid_override_pattern[
            self.first_layer_index:self.first_layer_index + self.num_hidden_layers]

    @property
    def expert_kind(self) -> str:
        # `ops/moe.expert_ffn`'s kind: by the published activation's name
        return {"silu": "swiglu", "relu2": "relu2"}[self.mlp_hidden_act]

    @property
    def shared_expert_width(self) -> int:
        own = self.moe_shared_expert_intermediate_size
        return self.n_shared_experts * self.moe_intermediate_size if own is None else own

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def router(self) -> str:
        # "sigmoid" (one matrix, `ops/moe.route`) | "mlp" (ZAYA1's, which
        # carries its state: `ops/moe.route_mlp`): the CCA stack's alone
        return "mlp" if self.mixer == "cca" else "sigmoid"

    @property
    def residual_scaling(self) -> bool:
        # learned scale and bias on stream and result: the CCA stack's alone
        return self.mixer == "cca"


@dataclasses.dataclass(frozen=True)
class DataConfig:
    """Online pipeline: tokenization + denoising corruption.

    Probabilities follow the reference corruption pipeline (reference
    data_processing.py:86-142), with the hide-all-annotations branch kept as
    an explicit knob (SURVEY ledger #5).
    """

    seq_len: int = 256                      # fixed padded length fed to the model
    buckets: Optional[Tuple[int, ...]] = None  # length buckets (last == seq_len);
                                            # None = single padded length
    packing: bool = False                   # segment-aware sequence packing
                                            # (data/packing.py): several
                                            # proteins per fixed-shape row
                                            # with segment ids — ONE compiled
                                            # shape, ~zero pad FLOPs; mutually
                                            # exclusive with buckets
    pack_max_segments: int = 8              # max proteins per packed row (the
                                            # S axis of the per-segment
                                            # annotation tensor)
    pack_open_bins: int = 0                 # packer look-back: open rows the
                                            # first-fit planner keeps before
                                            # closing the oldest (0 = auto,
                                            # 2 x global batch)
    token_randomize_prob: float = 0.05      # data_processing.py:90
    annotation_corrupt_prob: float = 0.5    # P(keep-and-noise); else hide all
                                            # (data_processing.py:127-128)
    annotation_drop_prob: float = 0.25      # drop positives (data_processing.py:116)
    annotation_add_prob: float = 1e-4       # add false positives (:117)
    batch_size: int = 32
    prefetch_depth: int = 2                 # host batches produced ahead on a
                                            # background thread (0 = off)
    num_epochs: Optional[int] = None        # bound the data stream; None =
                                            # loop forever (iteration-based,
                                            # like the reference)


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """Adam + warmup schedule (reference dummy_tests.py:127-130, utils.py:257-264).

    The reference chains LambdaLR warmup into ReduceLROnPlateau via
    SequentialLR, which crashes after warmup (SURVEY ledger #7). Here both a
    correct warmup+plateau and warmup+cosine are provided.
    """

    learning_rate: float = 2e-4             # dummy_tests.py:128
    warmup_steps: int = 10_000              # utils.py:233 warmup_duration
    schedule: str = "warmup_plateau"        # "warmup_plateau" | "warmup_cosine" | "constant"
    total_steps: int = 100_000              # cosine horizon
    plateau_window: int = 100               # steps averaged into ONE plateau
                                            # observation (set ≈ eval_every so
                                            # the signal tracks eval cadence,
                                            # not per-step batch noise)
    plateau_patience: int = 10              # windowed observations without
                                            # improvement before LR is cut
    plateau_factor: float = 0.1             # plateau: LR multiplier on trigger
    plateau_cooldown: int = 10              # observations to ignore after a cut
                                            # (lets the loss re-baseline before
                                            # another reduction can chain)
    plateau_metric: str = "train_loss"      # "train_loss" | "eval_loss" — what
                                            # reduce_on_plateau observes. The
                                            # reference intended a METRIC-driven
                                            # ReduceLROnPlateau (utils.py:257-264
                                            # — it crashed); "eval_loss" feeds
                                            # the latest cadenced held-out loss
                                            # to the transform every step, so an
                                            # eval-only regime shift (train loss
                                            # falling while eval rises — the
                                            # r3 sustained run) CAN cut the LR.
                                            # Set plateau_window ≈ eval_every so
                                            # one windowed observation covers one
                                            # eval interval; requires eval_every
                                            # > 0 and an eval split. The trainer
                                            # seeds the stream with an up-front
                                            # eval bracket so the plateau window
                                            # never mixes train-scale values
                                            # (ADVICE r4).
    grad_clip_norm: float = 1.0             # reference clips grads (utils.py:136)
    b1: float = 0.9
    b2: float = 0.999
    weight_decay: float = 0.0


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Device mesh axes — entirely new vs the reference (SURVEY C18: absent).

    Axes: data (DP), fsdp (param/optimizer sharding over data axis), model
    (TP over global/annotation dims), seq (sequence parallelism for the
    local conv track with halo exchange).
    """

    data: int = 1
    fsdp: int = 1
    model: int = 1
    seq: int = 1

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return ("data", "fsdp", "model", "seq")

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.data, self.fsdp, self.model, self.seq)

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """Cross-replica execution strategy knobs (beyond the mesh SHAPE,
    which stays in MeshConfig).

    zero_update: ZeRO-1 sharded weight update (Xu et al.,
      arXiv:2004.13336). The pure `data` axis normally replicates fp32
      params and Adam mu/nu on every replica and pays a full gradient
      all-reduce per step; with zero_update the train step
      reduce-scatters gradients over ('data','fsdp'), applies the
      optimizer to a 1/(data*fsdp) shard, and all-gathers the updated
      params — Adam state HBM drops by ~(1 - 1/data_extent) on top of
      fsdp, for near-equal total collective bytes (reduce-scatter +
      all-gather ≈ all-reduce). Sharded-optimizer storage lives in
      parallel/sharding.py (zero-aware state_sharding); the update
      itself in parallel/zero.py. No-op without a mesh or when
      data*fsdp == 1.
    grad_reduce_dtype: payload dtype of the ZeRO-1 gradient reduction
      — "fp32" (exact, the implicit-SPMD reduce-scatter), or "bf16" /
      "int8": the QUANTIZED reduce-scatter (parallel/quant.py,
      EQuARX-style, arXiv:2506.17615). The quantized step computes
      per-replica partial gradients inside an explicit data-parallel
      shard_map and reduces them over quantized payloads — bf16
      (stochastic rounding, 2x fewer wire bytes) or int8 (per-chunk
      symmetric scale + stochastic rounding seeded from the step key:
      deterministic and multi-host lockstep, ~4x fewer wire bytes) —
      with the optimizer math fp32 on the dequantized shards and the
      clip norm measured on the dequantized sum. Wire bytes are
      verified from compiled HLO (tests/test_zero.py,
      zero.collective_wire_bytes_from_hlo); parity bounds are measured
      in tests/test_quant.py and documented in docs/distributed.md.
      Quantized payloads need a data/fsdp-only mesh (model>1 or seq>1
      raises the typed QuantConfigError — the explicit replica
      shard_map cannot shard those axes), a global batch divisible by
      data*fsdp, and are rejected by the explicit seq-parallel Pallas
      step (int8; its bf16 stays the PR-2 cast-only numerics-only
      reduction). Only consulted by the zero_update path.
    """

    zero_update: bool = False
    grad_reduce_dtype: str = "fp32"         # "fp32" | "bf16" | "int8"


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Online-serving knobs that belong to the MODEL's run config (the
    CLI owns transport knobs like ports and queue depths; these ride
    config.json so `pbt serve --pretrained RUN_DIR` picks them up).

    quant: which executable arm the dispatcher builds (parallel/
      quant.py) — "fp32" (ordinary), "int8" (symmetric per-channel
      int8 WEIGHTS quantized at load time, dequantized inside the
      executable: ~4x smaller resident trunk — the HBM headroom two
      resident trunks need), or "int8_act" (int8 weights + opt-in
      dynamic int8 fake-quant of the trunk's output activations).
      Overridable per serve process via `pbt serve --quant`.
    quant_parity_every: with a quantized arm, every Nth dispatched
      batch ALSO runs the fp32 executables on the same inputs and
      records the per-request max-abs output deviation
      (`serve_quant_parity_max` gauge, stats()["quant"], serve_batch
      events) — live parity evidence at 1/N the cost. 0 disables.
    pipeline_depth: bounded in-flight window for pipelined dispatch
      (ISSUE 19): the scheduler submits up to this many batches before
      blocking, and a completer thread resolves device results while
      the next batch forms — device compute overlaps host fetch +
      fan-out. 1 disables the completer and restores the serial
      submit-then-finalize path bit-for-bit. Overridable per serve
      process via `pbt serve --pipeline-depth`.
    """

    quant: str = "fp32"                     # "fp32" | "int8" | "int8_act"
    quant_parity_every: int = 0
    pipeline_depth: int = 2


@dataclasses.dataclass(frozen=True)
class CheckpointConfig:
    """Checkpoint cadence (reference utils.py:227 nb_iterations_checkpoint=1000)."""

    directory: str = "checkpoints"
    every_steps: int = 1000
    max_to_keep: int = 3
    async_save: bool = True
    overlap: bool = True                    # overlapped boundary: snapshot
                                            # the state on device and run
                                            # the device→host fetch + save
                                            # on a stager thread while the
                                            # train stream keeps
                                            # dispatching — the boundary
                                            # costs ~zero wall time instead
                                            # of drain→fetch→save
                                            # (single-process runs only;
                                            # multi-host falls back to the
                                            # synchronous collective save)
    warm_start: bool = False                # save once at the start step,
                                            # BEFORE the perf timer anchors:
                                            # pays orbax setup + the first
                                            # full device->host fetch up
                                            # front, so the first cadenced
                                            # save's one-time cost cannot
                                            # land in the timed stream (the
                                            # r3 collapse's 650-800 stretch,
                                            # BASELINE.md round-5
                                            # attribution)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Iteration-based pretraining loop config (reference utils.py:220-231)."""

    max_steps: int = 250                    # dummy_tests.py:141 smoke default
    log_every: int = 10
    eval_every: int = 0                     # 0 = no eval
    on_nan: str = "halt"                    # "halt" | "warn" | "off" — NaN/Inf
                                            # watch on logged loss/grad_norm
                                            # (train/resilience.py)
    early_stop_patience: int = 0            # consecutive cadenced evals without
                                            # eval_loss improvement before the
                                            # run checkpoints and stops; 0 = off.
                                            # The best/stalled counters (and the
                                            # latest eval loss the eval-keyed
                                            # plateau observes) are CHECKPOINTED
                                            # with the data position, so a
                                            # preempt/requeue loop cannot reset
                                            # the patience baseline.
    early_stop_min_delta: float = 0.0       # improvement smaller than this
                                            # still counts as a stall
    overlap_eval: bool = True               # dispatch the periodic eval
                                            # bracket asynchronously and
                                            # resolve its metrics after the
                                            # next train step has been
                                            # dispatched, instead of a
                                            # synchronous fetch-per-batch
                                            # bracket. Applied only where
                                            # legal: an eval-keyed plateau
                                            # or early stopping needs the
                                            # eval value BEFORE the next
                                            # step and keeps the
                                            # synchronous bracket.
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class TaskConfig:
    """A supervised fine-tuning task on the pretrained trunk (SURVEY C14 —
    the reference's fine-tune harness exists only as commented-out code,
    reference utils.py:348-493; completed here).

    Kinds (the ProteinBERT paper's benchmark shapes):
      token_classification  — per-residue labels (secondary structure);
      sequence_classification — per-protein label (remote homology);
      sequence_regression   — per-protein scalar (stability, fluorescence).
    """

    kind: str = "token_classification"
    num_outputs: int = 8                # classes, or 1 for regression
    freeze_trunk: bool = False          # train head only
    head_hidden_dim: int = 0            # 0 = linear head, else one MLP layer
    epochs: int = 10
    eval_every_epochs: int = 1


@dataclasses.dataclass(frozen=True)
class FinetuneConfig:
    model: "ModelConfig" = dataclasses.field(default_factory=lambda: ModelConfig())
    task: TaskConfig = dataclasses.field(default_factory=TaskConfig)
    data: "DataConfig" = dataclasses.field(default_factory=lambda: DataConfig())
    optimizer: "OptimizerConfig" = dataclasses.field(
        default_factory=lambda: OptimizerConfig(
            learning_rate=1e-4, warmup_steps=100, schedule="warmup_cosine",
            total_steps=10_000,
        )
    )
    checkpoint: "CheckpointConfig" = dataclasses.field(
        default_factory=lambda: CheckpointConfig(directory="finetune_checkpoints")
    )
    train: "TrainConfig" = dataclasses.field(default_factory=lambda: TrainConfig())

    def replace(self, **kw) -> "FinetuneConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class PretrainConfig:
    model: Union[ModelConfig, DecoderConfig] = dataclasses.field(
        default_factory=ModelConfig)
    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    optimizer: OptimizerConfig = dataclasses.field(default_factory=OptimizerConfig)
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    parallel: ParallelConfig = dataclasses.field(default_factory=ParallelConfig)
    checkpoint: CheckpointConfig = dataclasses.field(default_factory=CheckpointConfig)
    train: TrainConfig = dataclasses.field(default_factory=TrainConfig)
    serve: ServeConfig = dataclasses.field(default_factory=ServeConfig)

    def replace(self, **kw) -> "PretrainConfig":
        return dataclasses.replace(self, **kw)


def _tiny() -> PretrainConfig:
    # BASELINE.json configs[0]: 2 blocks, d=128, seq_len=128 — CPU smoke.
    return PretrainConfig(
        model=ModelConfig(local_dim=32, global_dim=128, key_dim=32, num_heads=4,
                          num_blocks=2, num_annotations=512, dtype="float32"),
        data=DataConfig(seq_len=128, batch_size=8),
        optimizer=OptimizerConfig(warmup_steps=50, total_steps=250),
        train=TrainConfig(max_steps=250),
    )


def _base() -> PretrainConfig:
    # BASELINE.json configs[1]: 6 blocks, d=512, seq_len=512 — v5e-16 DP.
    # remat on: the scan otherwise saves fp32 LN intermediates for all 6
    # blocks (~12G at batch 128 on a 16G chip) and is HBM-bound; measured
    # on v5e-1 remat is BOTH smaller and faster (MFU 0.52 vs 0.39), and
    # the "convs" policy (save conv outputs, recompute the cheap tail)
    # another +8% over full remat (MFU 0.56, BASELINE.md).
    return PretrainConfig(
        model=ModelConfig(local_dim=512, global_dim=512, key_dim=64, num_heads=8,
                          num_blocks=6, remat=True, remat_policy="convs"),
        data=DataConfig(seq_len=512, batch_size=128),
        optimizer=OptimizerConfig(warmup_steps=10_000, total_steps=1_000_000),
        train=TrainConfig(max_steps=1_000_000),
        mesh=MeshConfig(data=16),
    )


def _long() -> PretrainConfig:
    # BASELINE.json configs[2]: seq_len=2048 long-context, sequence-parallel,
    # length-bucketed (most UniRef sequences are far shorter than 2048).
    return PretrainConfig(
        model=ModelConfig(local_dim=512, global_dim=512, key_dim=64, num_heads=8,
                          num_blocks=6, remat=True, remat_policy="convs"),
        data=DataConfig(seq_len=2048, batch_size=64,
                        buckets=(512, 1024, 2048)),
        optimizer=OptimizerConfig(warmup_steps=10_000, total_steps=1_000_000),
        train=TrainConfig(max_steps=1_000_000),
        mesh=MeshConfig(data=4, seq=4),
    )


def _large() -> PretrainConfig:
    # BASELINE.json configs[4]: 12 blocks, d=1024, full 8943-dim GO head.
    return PretrainConfig(
        model=ModelConfig(local_dim=1024, global_dim=1024, key_dim=64,
                          num_heads=16, num_blocks=12, remat=True,
                          remat_policy="convs"),
        data=DataConfig(seq_len=1024, batch_size=256),
        optimizer=OptimizerConfig(warmup_steps=10_000, total_steps=2_000_000),
        train=TrainConfig(max_steps=2_000_000),
        mesh=MeshConfig(data=64, model=4),
    )


def _glm47flash_ep8() -> PretrainConfig:
    # GLM-4.7-Flash (huggingface.co/zai-org/GLM-4.7-Flash config.json) as
    # ONE chip of an eight-chip expert-parallel layer group holds it:
    # every width as published; 8 of the 64 routed experts and 19,360 of
    # the 154,880 vocabulary rows; the dense layer, 4 expert layers and
    # the prediction module (further layers lie on further chips as
    # pipeline stages). 706.5 M parameters, 10.53 GiB of state here.
    return PretrainConfig(
        model=DecoderConfig(vocab_size=19_360, num_hidden_layers=5,
                            experts_held=8),
        data=DataConfig(seq_len=8192, batch_size=2, packing=True,
                        pack_max_segments=16),
        optimizer=OptimizerConfig(warmup_steps=10_000, total_steps=2_000_000),
        train=TrainConfig(max_steps=2_000_000),
    )


def _glm_tiny() -> PretrainConfig:
    # The decoder at CPU-test size: 2 dense-or-expert layers + the
    # prediction module, 8 experts top-2 (all held), vocabulary 512.
    return PretrainConfig(
        model=DecoderConfig(
            vocab_size=512, hidden_size=64, num_hidden_layers=2,
            intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=8, experts_held=8, num_experts_per_tok=2,
            num_attention_heads=4, q_lora_rank=32, kv_lora_rank=24,
            qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
            dtype="float32", attention_block=16, expert_block=8,
            loss_chunk=32),
        data=DataConfig(seq_len=64, batch_size=2, packing=True,
                        pack_max_segments=4),
        optimizer=OptimizerConfig(warmup_steps=50, total_steps=250),
        train=TrainConfig(max_steps=250),
    )


# The served decoder's span ladder: a document takes the smallest span
# that holds it; what is left of the span is padding the model skips.
def _span_ladder(seq_len: int, step: int) -> Tuple[int, ...]:
    return tuple(range(step, seq_len + 1, step))


def _ling3flash_ep4() -> PretrainConfig:
    # Ling-3.0-flash (huggingface.co/inclusionAI/Ling-3.0-flash
    # config.json, bailing_hybrid) on the SERVING path as ONE chip of a
    # four-chip expert-parallel layer group holds it: every width as
    # published; 128 of the 512 routed experts (ids 0-127) and 39,296 of
    # the 157,184 embedding rows; published layers 1-7: one leading dense
    # layer, then KDA, KDA, KDA, latent, KDA, KDA (a whole period of six
    # at the phase of layer 2). 5,068.7 M parameters, bfloat16 in HBM;
    # the output head and the prediction module are not on this path.
    # `expert_block` 384 holds one held expert's tokens of a 16,384-token
    # batch (256 +- 16) in one block.
    return PretrainConfig(
        model=DecoderConfig(
            vocab_size=39_296, hidden_size=2560, num_hidden_layers=7,
            first_k_dense_replace=1, first_layer_index=1, layer_group_size=6,
            intermediate_size=6144, moe_intermediate_size=768,
            n_routed_experts=512, experts_held=128, num_experts_per_tok=8,
            n_group=8, topk_group=4, routed_scaling_factor=2.5,
            num_attention_heads=32, q_lora_rank=None, kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            rope_theta=6e6, rope_interleave=True, rms_norm_eps=1e-6,
            mixer_output_gate=True, num_nextn_predict_layers=0,
            embed_init_std=1.0, out_init_std=0.02 / (2 * 42) ** 0.5,
            param_dtype="bfloat16", expert_block=384),
        data=DataConfig(seq_len=8192, batch_size=2, packing=True,
                        pack_max_segments=16,
                        buckets=_span_ladder(8192, 128)),
    )


def _ling_tiny() -> PretrainConfig:
    # The hybrid decoder at CPU-test size: published layers 1-7 as in
    # `ling3flash_ep4` (dense, 3 KDA, latent, 2 KDA; period 6), 16
    # experts in 4 groups (2 kept, top 4), all held, float32 throughout.
    return PretrainConfig(
        model=DecoderConfig(
            vocab_size=512, hidden_size=64, num_hidden_layers=7,
            first_k_dense_replace=1, first_layer_index=1, layer_group_size=6,
            intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=16, experts_held=16, num_experts_per_tok=4,
            n_group=4, topk_group=2, routed_scaling_factor=2.5,
            num_attention_heads=4, q_lora_rank=None, kv_lora_rank=24,
            qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
            rope_theta=6e6, rope_interleave=True, rms_norm_eps=1e-6,
            mixer_output_gate=True, num_nextn_predict_layers=0,
            embed_init_std=1.0, out_init_std=0.02 / (2 * 42) ** 0.5,
            kda_head_dim=16, kda_chunk=16, dtype="float32",
            attention_block=16, expert_block=8, loss_chunk=32),
        data=DataConfig(seq_len=64, batch_size=2, packing=True,
                        pack_max_segments=4, buckets=_span_ladder(64, 8)),
    )


def _zaya(**sizes) -> DecoderConfig:
    # What names ZAYA1 whatever its widths: CCA in every layer (the MLP
    # router and residual scaling come with it), routed experts alone (no
    # shared expert, no leading dense layer), top 1 of a softmax, float32
    # stream.
    return DecoderConfig(**{**dict(
        mixer="cca", first_k_dense_replace=0, n_shared_experts=0, num_experts_per_tok=1,
        norm_topk_prob=False, routed_scaling_factor=1.0,
        partial_rotary_factor=0.5, rope_theta=5e6, rms_norm_eps=1e-5,
        num_nextn_predict_layers=0, q_lora_rank=None,
        embed_init_std=1.0, out_init_std=0.02 / (2 * 40) ** 0.5), **sizes})


def _zaya1_8b_pp2() -> PretrainConfig:
    # ZAYA1-8B (huggingface.co/Zyphra/ZAYA1-8B config.json, `zaya`) on
    # the SERVING path as the first of two pipeline stages holds it:
    # every width, all 16 experts and the whole vocabulary as published;
    # published layers 0-23 of 40 (the router's carried state starts as
    # published). 5,519.2 M parameters, bfloat16 in HBM; the tied output
    # head is not on this path.
    return PretrainConfig(
        model=_zaya(
            vocab_size=262_272, hidden_size=2048, num_hidden_layers=24,
            moe_intermediate_size=2048, n_routed_experts=16, experts_held=16,
            num_attention_heads=8, num_key_value_heads=2, cca_head_dim=128,
            router_hidden_size=256, param_dtype="bfloat16"),
        data=DataConfig(seq_len=8192, batch_size=2, packing=True,
                        pack_max_segments=16,
                        buckets=_span_ladder(8192, 128)),
    )


def _zaya_tiny() -> PretrainConfig:
    # ZAYA1 at CPU-test size: 8 query heads on 2 key heads, 16 experts
    # top 1, four layers (the router's state is carried three times),
    # float32 throughout; weights large enough at this width for a
    # sublayer's result to be a third of the stream it is added to.
    return PretrainConfig(
        model=_zaya(
            vocab_size=512, hidden_size=64, num_hidden_layers=4,
            moe_intermediate_size=32, n_routed_experts=16, experts_held=16,
            num_attention_heads=8, num_key_value_heads=2, cca_head_dim=16,
            router_hidden_size=24, init_std=0.1, out_init_std=0.05,
            dtype="float32", attention_block=16, expert_block=8,
            loss_chunk=32),
        data=DataConfig(seq_len=64, batch_size=2, packing=True,
                        pack_max_segments=4, buckets=_span_ladder(64, 8)),
    )


NEMOTRON_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                    "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


def _nemotron(**sizes) -> DecoderConfig:
    # What names Nemotron 3 whatever its widths: the published pattern of
    # single-sublayer layers, squared-ReLU experts in a latent behind a
    # sigmoid router with no groups, no leading dense layer, no rotary,
    # float32 stream; `init_served`'s recipe at the published depth of 88.
    return DecoderConfig(**{**dict(
        hybrid_override_pattern=NEMOTRON_PATTERN, first_k_dense_replace=0,
        first_layer_index=0, mlp_hidden_act="relu2", n_shared_experts=1,
        norm_topk_prob=True, routed_scaling_factor=5.0, n_group=1, topk_group=1,
        rms_norm_eps=1e-5, num_nextn_predict_layers=0, q_lora_rank=None,
        embed_init_std=1.0, out_init_std=0.02 / (2 * 88) ** 0.5), **sizes})


def _nemotron3super_ep4() -> PretrainConfig:
    # Nemotron-3-Super-120B-A12B (huggingface.co/nvidia/NVIDIA-Nemotron-3-
    # Super-120B-A12B-BF16 config.json, `nemotron_h`) on the SERVING path as
    # ONE chip of the four that share the first of seven pipeline stages
    # holds it: every width as published; published layers 0-12
    # (`MEMEMEM*EMEME`: 6 Mamba-2, 6 LatentMoE, 1 attention layer), 128 of
    # the 512 routed experts a layer (ids 0-127) and 32,768 of the 131,072
    # embedding rows. 5,382.9 M parameters, bfloat16 in HBM; the output
    # head and the prediction module are not on this path. `expert_block`
    # 384: a held expert's ~700 tokens of a 16,384-token batch in two blocks.
    return PretrainConfig(
        model=_nemotron(
            vocab_size=32_768, hidden_size=4096, num_hidden_layers=13,
            mamba_num_heads=128, mamba_head_dim=64, ssm_state_size=128,
            n_groups=8, conv_kernel=4, chunk_size=128,
            num_attention_heads=32, num_key_value_heads=2, cca_head_dim=128,
            n_routed_experts=512, experts_held=128, num_experts_per_tok=22,
            moe_latent_size=1024, moe_intermediate_size=2688,
            moe_shared_expert_intermediate_size=5376,
            param_dtype="bfloat16", expert_block=384),
        data=DataConfig(seq_len=8192, batch_size=2, packing=True,
                        pack_max_segments=16,
                        buckets=_span_ladder(8192, 128)),
    )


def _nemotron_tiny() -> PretrainConfig:
    # Nemotron 3 at CPU-test size: the published pattern's first 13
    # layers, 8 Mamba heads of 8 in 2 groups on a state of 16, 4 query
    # heads on 2 key heads, 16 experts top 4 (all held) in a latent of
    # 32, float32 throughout; weights large enough at this width for a
    # sublayer's result to be a third of the stream it is added to.
    return PretrainConfig(
        model=_nemotron(
            vocab_size=512, hidden_size=64, num_hidden_layers=13,
            mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16,
            n_groups=2, conv_kernel=4, chunk_size=16,
            num_attention_heads=4, num_key_value_heads=2, cca_head_dim=16,
            n_routed_experts=16, experts_held=16, num_experts_per_tok=4,
            moe_latent_size=32, moe_intermediate_size=48,
            moe_shared_expert_intermediate_size=96,
            init_std=0.1, out_init_std=0.05, dtype="float32",
            attention_block=16, expert_block=8, loss_chunk=32),
        data=DataConfig(seq_len=64, batch_size=2, packing=True,
                        pack_max_segments=4, buckets=_span_ladder(64, 8)),
    )


PRESETS = {
    "tiny": _tiny,
    "base": _base,
    "long": _long,
    "large": _large,
    "glm47flash_ep8": _glm47flash_ep8,
    "glm_tiny": _glm_tiny,
    "ling3flash_ep4": _ling3flash_ep4,
    "ling_tiny": _ling_tiny,
    "zaya1_8b_pp2": _zaya1_8b_pp2,
    "zaya_tiny": _zaya_tiny,
    "nemotron3super_ep4": _nemotron3super_ep4,
    "nemotron_tiny": _nemotron_tiny,
}


def get_preset(name: str) -> PretrainConfig:
    try:
        return PRESETS[name]()
    except KeyError:
        raise ValueError(f"unknown preset {name!r}; have {sorted(PRESETS)}") from None


def config_to_dict(cfg) -> dict:
    """Frozen config tree → plain JSON-serializable dict (tuples become
    lists; from_dict restores them)."""
    return dataclasses.asdict(cfg)


def _build(cls, data: dict):
    kwargs = {}
    for f in dataclasses.fields(cls):
        if f.name not in data:
            continue
        v = data[f.name]
        if isinstance(v, dict):
            # Nested config: resolve the node class from the field's
            # default (f.type is a string under PEP 563 annotations).
            default = (f.default_factory() if f.default_factory
                       is not dataclasses.MISSING else f.default)
            node = type(default)
            if node is ModelConfig and "n_routed_experts" in v:
                node = DecoderConfig    # `model` has two types: told by keys
            kwargs[f.name] = _build(node, v)
        elif isinstance(v, list):
            kwargs[f.name] = tuple(v)  # configs must stay hashable
        else:
            kwargs[f.name] = v
    return cls(**kwargs)


def config_from_dict(data: dict, cls=None):
    """Inverse of config_to_dict. `cls` defaults to PretrainConfig."""
    return _build(cls or PretrainConfig, data)


def save_config(cfg, path: str) -> None:
    """Write the config as JSON (pretrain drops one into the run dir so
    downstream commands need no repeated --pretrained-set flags).

    Atomic (temp file + rename): a crash mid-write must not leave a
    truncated config.json that poisons every later --pretrained consumer
    of an otherwise-valid run dir."""
    import json
    import os
    import tempfile

    fd, tmp = tempfile.mkstemp(prefix=os.path.basename(path) + ".tmp",
                               dir=os.path.dirname(os.path.abspath(path)))
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(config_to_dict(cfg), f, indent=2, sort_keys=True)
        os.chmod(tmp, 0o644)  # mkstemp is 0600
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def load_config(path: str, cls=None):
    import json

    with open(path) as f:
        return config_from_dict(json.load(f), cls)
