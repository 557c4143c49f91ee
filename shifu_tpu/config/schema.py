"""Typed configuration schema for shifu_tpu.

The reference spreads configuration across three places: Hadoop XML key/value
layers (reference: yarn/util/GlobalConfigurationKeys.java:22-155), Shifu's
ModelConfig.json hyperparameters (reference: resources/ssgd_monitor.py:91-107,
177-183) and a Java->Python env-var bridge (reference:
yarn/container/TensorflowTaskExecutor.java:200-238).  Here everything collapses
into one typed, serializable tree of dataclasses; `shifu_compat` fills it from
the unchanged Shifu JSON files.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence


class ConfigError(ValueError):
    """Raised when a config is structurally invalid."""


# ---------------------------------------------------------------------------
# Columns / dataset
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ColumnSpec:
    """One column of the normalized tabular input.

    Mirrors what the reference extracts from ColumnConfig.json into the
    SELECTED_COLUMN_NUMS / TARGET_COLUMN_NUM / WEIGHT_COLUMN_NUM env vars
    (reference: yarn/client/TensorflowClient.java + TensorflowTaskExecutor.java:200-238).
    """

    index: int
    name: str
    is_target: bool = False
    is_weight: bool = False
    is_selected: bool = False
    # categorical metadata (used by Wide&Deep / DeepFM embedding paths; the
    # reference MLP treats everything as pre-normalized floats)
    is_categorical: bool = False
    vocab_size: int = 0


@dataclass(frozen=True)
class DataSchema:
    """Column layout of one pipe-delimited normalized row."""

    columns: tuple[ColumnSpec, ...] = ()
    target_index: int = -1
    weight_index: int = -1          # -1 => implicit weight 1.0 (reference: ssgd_monitor.py:417-421)
    selected_indices: tuple[int, ...] = ()
    # Shifu multi-target mode (multitask models): ordered target columns.
    # Empty => single-target via target_index.
    target_indices: tuple[int, ...] = ()

    @property
    def feature_count(self) -> int:
        return len(self.selected_indices)

    @property
    def categorical_indices(self) -> tuple[int, ...]:
        by_index = {c.index: c for c in self.columns}
        return tuple(i for i in self.selected_indices
                     if i in by_index and by_index[i].is_categorical)

    @property
    def all_target_indices(self) -> tuple[int, ...]:
        return self.target_indices if self.target_indices else (self.target_index,)

    def validate(self) -> None:
        if self.target_index < 0 and not self.target_indices:
            raise ConfigError("DataSchema.target_index must be set (>= 0)")
        if not self.selected_indices:
            raise ConfigError("DataSchema.selected_indices must be non-empty")
        for t in self.all_target_indices:
            if t in self.selected_indices:
                raise ConfigError("target column cannot also be a selected feature")
        if self.weight_index >= 0 and self.weight_index in self.selected_indices:
            raise ConfigError("weight column cannot also be a selected feature")


@dataclass(frozen=True)
class DataConfig:
    """Input pipeline configuration.

    The reference round-robins gzip files across workers
    (yarn/appmaster/TrainingDataSet.java:65-82) and re-draws a random row-level
    train/valid split every run (ssgd_monitor.py:395 `random.random()`); here
    the split is a deterministic per-row hash so resume/restart sees the same
    partition.
    """

    paths: tuple[str, ...] = ()
    delimiter: str = "|"
    valid_ratio: float = 0.1        # reference default VALID_TRAINING_DATA_RATIO (ssgd_monitor.py:27)
    split_seed: int = 0
    batch_size: int = 100           # reference default BATCH_SIZE (ssgd_monitor.py:33)
    shuffle_seed: int = 0
    shuffle: bool = True
    drop_remainder: bool = True     # static shapes for XLA
    prefetch: int = 2
    # host-side queue depth of the input feeders: the streamed first
    # epoch's parse-result queue and the overlap engine's host staging
    # queue (data/pipeline.EpochFeeder) both run this many items ahead.
    # Distinct from `prefetch`, which bounds DEVICE-resident blocks (HBM);
    # this knob bounds host RAM held by assembled-but-unstaged chunks.
    # 0 = auto: the feeder instead resizes its DEVICE staging gate per
    # epoch from the goodput ledger's exposed-input measurement
    # (data/pipeline.next_prefetch_depth — HBM-side run-ahead between 2
    # and 8 chunks, superseding `prefetch`; the host queue stays at 4).
    prefetch_depth: int = 4
    # cross-epoch overlap engine (train/loop.py + data/pipeline.EpochFeeder):
    # a persistent feeder shuffles and assembles epoch N+1's batches on host
    # threads while epoch N still executes on device, and next-epoch work
    # overlaps the eval dispatch tail — batch order stays a pure function of
    # (seed, epoch), byte-identical to the non-overlapped order.  On the
    # resident tiers (an epoch is one dispatch, the valid rows live on the
    # device) epoch N+1's scan is dispatched before the host accumulates
    # epoch N's eval scores, where that accumulation is a sizeable share
    # of the epoch (docs/DATA.md "Overlap engine").  False
    # restores the per-epoch producer thread and the sequential order
    # (stop-the-world boundaries).
    overlap_epochs: bool = True
    # staged epochs: device-put (block_batches, B, F) blocks once and
    # lax.scan the train step on device — one H2D transfer per block instead
    # of per batch; the 10M+ samples/sec input path (SURVEY.md section 7.3)
    staged: bool = True
    block_batches: int = 32
    # device-resident tier: when the training partition fits in this many
    # bytes of HBM, transfer it once and reorder batches on device each epoch
    # (zero steady-state H2D).  0 disables.  The budget covers the valid
    # rows too: where their feature blocks fit what the train rows leave of
    # it (single process), they are placed once as well and every epoch's
    # eval is one dispatch over them (the journal's `eval_tier`:
    # "resident"); where they do not fit, the train rows stay resident and
    # eval streams its batches from the host every epoch ("streamed").
    device_resident_bytes: int = 2 << 30
    # parse-once columnar cache directory (data/cache.py); None defers to the
    # SHIFU_TPU_DATA_CACHE env var, empty-or-unset means no cache.
    cache_dir: str | None = None
    # cache entry format generation (data/cache.py CACHE_FORMAT_VERSION):
    # 0 = latest (v2: wire-format projected entries with compact
    # target/weight storage and an entry.json manifest — ¼ the disk bytes
    # of raw float32, zero re-quantize on warm starts); 1 pins the legacy
    # v1 layout for interop with pre-v2 readers sharing the cache dir.
    # Both formats reconstruct bit-identical arrays on load.
    cache_format: int = 0
    # file-level read parallelism for load_datasets; 0 = one thread per file
    # capped at cpu_count.
    read_threads: int = 0
    # cold-ingest parse pool width: how many part-files inflate+parse
    # concurrently (native parser per file; v2 cache writes overlap on a
    # separate writer thread).  0 = auto (read_threads when set, else one
    # worker per file capped at cpu_count).  Takes precedence over
    # read_threads when both are set; intra-file parser threads scale down
    # as the pool widens so total parallelism stays ~cores, not cores².
    ingest_workers: int = 0
    # out-of-core mode: consolidate the host shard into on-disk projected
    # arrays once (requires cache_dir) and train from read-only memmaps —
    # host shards larger than RAM stream through the staged tier
    # (data/outofcore.py).
    out_of_core: bool = False
    # stream the FIRST trained epoch: start training on parsed blocks while
    # the remaining files still parse (single-host staged path; parse, H2D,
    # and device compute overlap instead of running serially — the fix for
    # the reference's parse-everything-then-train anti-pattern,
    # ssgd_monitor.py:348-454).  Later epochs train from the fully loaded,
    # globally shuffled dataset as usual.
    stream_first_epoch: bool = True
    # host->device wire dtype for the FEATURES array: "auto" sends bfloat16
    # when the model computes in bfloat16 anyway (the model casts inputs
    # first — models/base.py) and no categorical id columns ride in features
    # (ids > 256 are not bf16-exact); halves H2D bytes and the resident
    # tier's HBM footprint.  "float32"/"bfloat16" force a choice.  "int8"
    # quantizes features to a per-column affine grid on the host and
    # dequantizes on device (train/step.py make_wire_decode): 1 byte/value
    # on the wire — 2x the effective H2D roofline of bf16 — at a max
    # rounding error of wire_int8_clip/254 per value, which ZSCALE-
    # normalized data tolerates (AUC parity pinned by
    # tests/test_wire_int8.py).  int8 requires a categorical-free feature
    # matrix (ids cannot ride an affine grid; JobConfig.validate enforces).
    wire_dtype: str = "auto"
    # symmetric per-column clip for the int8 wire grid, in (normalized)
    # feature units: values quantize to round(x * 127/clip) in [-127, 127],
    # so anything beyond +-clip saturates.  Shifu ZSCALE clamps at 4-6
    # sigma, so the default 8.0 never clips in-contract data.
    wire_int8_clip: float = 8.0
    # compact wire for the TARGET column: "auto" sends uint8 (1 B instead of
    # 4) exactly when every value in the block is an integer in [0, 255] —
    # always true for Shifu's binary labels — decoded back to f32 on device
    # (train/step.py); lossless by construction, falls back to f32 per block
    # otherwise.  "uint8" forces (non-representable targets raise);
    # "float32" disables.
    wire_label_dtype: str = "auto"
    # compact wire for the WEIGHT column: "auto" elides the column entirely
    # (0 B on the wire) when every weight in the block is exactly 1.0 — the
    # common case for Shifu jobs without a weightColumnName — with the
    # device step synthesizing ones (bit-identical losses).  "elide" forces
    # (non-unit weights raise); "float32" disables.
    wire_weight_mode: str = "auto"
    # pod-scale host shard assignment (data/pipeline.host_shard_assignment):
    # how source files map onto hosts as a pure function of
    # (process_index, process_count, seed, epoch).  "auto"/"static" = the
    # fixed round-robin (i % num_hosts, the legacy scheme — stable across
    # epochs, so per-host caches and out-of-core entries stay hot).
    # "rotate" rotates the round-robin by a deterministic per-epoch offset
    # (shard_rotation): across epochs every host visits every slice, and a
    # host rejoining after an elastic reshape re-derives its slice from
    # the same formula.  Epoch 0 is identical in all modes.
    host_shard: str = "auto"
    # in-HBM format for the device-resident tier's feature blocks: "auto"
    # keeps the wire format (no silent precision change), "wire" says the
    # same explicitly, "int8" forces int8 residency — features quantize to
    # the wire_params grid at tier build even when the per-batch wire is
    # f32/bf16, quartering resident HBM vs f32 staging, with dequantization
    # fused into the first-layer matmul where ops/pallas_int8_matmul is
    # available (XLA decode otherwise).  Same categorical-free requirement
    # as wire_dtype="int8" (JobConfig.validate enforces).
    resident_format: str = "auto"

    def validate(self) -> None:
        if not (0.0 <= self.valid_ratio < 1.0):
            raise ConfigError(f"valid_ratio must be in [0,1): {self.valid_ratio}")
        if self.batch_size <= 0:
            raise ConfigError("batch_size must be positive")
        if self.prefetch_depth < 0:
            raise ConfigError(
                f"prefetch_depth must be >= 0 (0 = auto): "
                f"{self.prefetch_depth}")
        if self.cache_format not in (0, 1, 2):
            raise ConfigError(
                f"cache_format must be 0 (latest), 1, or 2: "
                f"{self.cache_format}")
        if self.ingest_workers < 0:
            raise ConfigError(
                f"ingest_workers must be >= 0 (0 = auto): "
                f"{self.ingest_workers}")
        if self.wire_dtype not in ("auto", "float32", "bfloat16", "int8"):
            raise ConfigError(
                f"wire_dtype must be auto/float32/bfloat16/int8: "
                f"{self.wire_dtype!r}")
        if self.wire_int8_clip <= 0:
            raise ConfigError(
                f"wire_int8_clip must be positive: {self.wire_int8_clip}")
        if self.wire_label_dtype not in ("auto", "uint8", "float32"):
            raise ConfigError(
                f"wire_label_dtype must be auto/uint8/float32: "
                f"{self.wire_label_dtype!r}")
        if self.wire_weight_mode not in ("auto", "elide", "float32"):
            raise ConfigError(
                f"wire_weight_mode must be auto/elide/float32: "
                f"{self.wire_weight_mode!r}")
        if self.resident_format not in ("auto", "wire", "int8"):
            raise ConfigError(
                f"resident_format must be auto/wire/int8: "
                f"{self.resident_format!r}")
        if self.host_shard not in ("auto", "static", "rotate"):
            raise ConfigError(
                f"host_shard must be auto/static/rotate: "
                f"{self.host_shard!r}")


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

VALID_MODEL_TYPES = ("mlp", "wide_deep", "deepfm", "multitask",
                     "ft_transformer", "moe_mlp", "block_stack")
VALID_ACTIVATIONS = ("sigmoid", "tanh", "relu", "leakyrelu")
#: model types the training path runs and nothing downstream of it does
TRAINING_ONLY_MODEL_TYPES = ("block_stack",)

#: the letters of `BlockStackSpec.pattern`, one a kind of block
BLOCK_KINDS = "M*ELAFCDGVQ"


def refuse_training_only(model_type: Any, what: str) -> None:
    """Export and serving refuse a training-only model by name, with what
    is missing, instead of failing deep inside a lowering."""
    if model_type in TRAINING_ONLY_MODEL_TYPES:
        raise ConfigError(
            f"{what} does not support model_type {model_type!r}: it is "
            "trained only (train() and evaluate()); the op-list program has "
            "no scan, causal-attention or routed-expert opcode, and "
            "runtime/serve.py keeps no recurrent state between requests")


@dataclass(frozen=True)
class BlockStackSpec:
    """The `block_stack` model's one configuration group: a causal sequence
    scorer over fixed-width rows of token ids (every selected column a
    categorical column of one vocabulary, one column a position).

    `pattern` has one letter a block, and a letter names a kind of block:

    - `M` a Mamba-2 mixer;
    - `*` causal grouped-query attention with no positional term;
    - `E` sigmoid-routed relu^2 experts beside one shared expert;
    - `L` a gated-DeltaNet linear-attention mixer (the gated delta rule);
    - `A` gated causal grouped-query attention: a per-head RMSNorm on q and
      k, a rotary term on the first `partial_rotary_factor` of a head's
      dims, a sigmoid output gate;
    - `F` softmax-routed gated experts (`silu(W_g x) * W_u x`) beside one
      shared expert behind a sigmoid gate;
    - `C` causal multi-head latent attention: q and the keys and values
      through low-rank projections with an RMSNorm inside each, a head's
      key of `qk_nope_head_dim` dims of its own and `qk_rope_head_dim`
      rotary dims (pairs of neighbours turn together) that all heads
      share, values of `v_head_dim`;
    - `D` a dense gated MLP at `intermediate_size`;
    - `G` sigmoid-routed gated experts (`E`'s router, `F`'s experts) beside
      `n_shared_experts` shared experts' width with no gate; with
      `n_shared_experts` 0 beside none, and a token whose choices all fall
      on experts not held here leaves the layer unchanged;
    - `V` a gated short convolution over `hidden_size` channels:
      `[B | C | X] = x W_in`, `(C * conv(B * X)) W_out`, the convolution
      depthwise, causal, of 3 taps and no bias;
    - `Q` causal grouped-query attention with a plain RMSNorm over each
      head's dims of q and k and the rotary term on all of a head's dims,
      no gate.

    Each block is `x <- x + mixer(RMSNorm(x))`; a final RMSNorm and the
    last position's vector feed the shared `shifu_output_0` head.  The
    norms of `L`, `A` and `F` blocks are zero-centred (`x_hat * (1 + w)`,
    `w` from zero), and the final norm is of the last block's kind.  The
    keys are the ones a published `config.json` of the hybrid families
    carries, so a configuration is copied, not translated.

    `experts_held` / `first_expert_held` say which routed experts this
    program holds (expert parallelism's share): the router scores all
    `n_routed_experts` and picks `num_experts_per_tok` of them a token; the
    layer adds the part of the result its own experts give.
    """

    pattern: str = ""
    hidden_size: int = 0
    norm_eps: float = 1e-5
    # M: Mamba-2 (d_inner = mamba_num_heads * mamba_head_dim)
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    n_groups: int = 1
    ssm_state_size: int = 0
    conv_kernel: int = 4
    # *, A and Q: causal grouped-query attention
    num_attention_heads: int = 0
    num_key_value_heads: int = 0
    head_dim: int = 0
    # A: the rotary term, on the first partial_rotary_factor of a head
    partial_rotary_factor: float = 1.0
    rope_theta: float = 10000.0                      # A, C and Q
    # C: latent attention over num_attention_heads heads
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # D: the dense gated MLP
    intermediate_size: int = 0
    # L: the gated delta rule (value head h reads key head
    # h // (linear_num_value_heads // linear_num_key_heads))
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    # E, F and G: routed experts beside one shared expert
    n_routed_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    moe_shared_expert_intermediate_size: int = 0     # E
    routed_scaling_factor: float = 1.0               # E and G
    shared_expert_intermediate_size: int = 0         # F
    # G: of moe_intermediate_size each, 0 for none; -1 = not given
    n_shared_experts: int = -1
    experts_held: int = 0           # 0 = all of them
    first_expert_held: int = 0

    @property
    def held(self) -> int:
        return self.experts_held or self.n_routed_experts

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def _need(self, kind: str, *names: str) -> None:
        if min(getattr(self, n) for n in names) <= 0:
            raise ConfigError(f"block_stack: {kind} block needs "
                              + ", ".join(names))

    def validate(self) -> None:
        if not self.pattern or set(self.pattern) - set(BLOCK_KINDS):
            raise ConfigError(
                f"block_stack.pattern must be letters of {BLOCK_KINDS!r}: "
                f"{self.pattern!r}")
        if self.hidden_size < 1:
            raise ConfigError("block_stack.hidden_size must be positive")
        if "M" in self.pattern:
            self._need("an 'M'", "mamba_num_heads", "mamba_head_dim",
                       "n_groups", "ssm_state_size", "conv_kernel")
            if self.mamba_num_heads % self.n_groups:
                raise ConfigError("block_stack.mamba_num_heads must be a "
                                  "multiple of n_groups")
        if set(self.pattern) & set("*AQ"):
            self._need("a '*', 'A' or 'Q'", "num_attention_heads",
                       "num_key_value_heads", "head_dim")
            if self.num_attention_heads % self.num_key_value_heads:
                raise ConfigError("block_stack.num_attention_heads must be "
                                  "a multiple of num_key_value_heads")
        if "A" in self.pattern:
            self._need("an 'A'", "partial_rotary_factor", "rope_theta")
            if (self.partial_rotary_factor > 1.0 or self.rotary_dim < 2
                    or self.rotary_dim % 2):
                raise ConfigError(
                    "block_stack: head_dim * partial_rotary_factor must be "
                    f"an even count of a head's dims: {self.rotary_dim}")
        if "Q" in self.pattern:
            self._need("a 'Q'", "rope_theta")
            if self.head_dim % 2:
                raise ConfigError("block_stack: a 'Q' block turns all of a "
                                  "head's dims in pairs: head_dim must be "
                                  f"even, not {self.head_dim}")
        if "L" in self.pattern:
            self._need("an 'L'", "linear_num_key_heads",
                       "linear_num_value_heads", "linear_key_head_dim",
                       "linear_value_head_dim", "linear_conv_kernel_dim")
            if self.linear_num_value_heads % self.linear_num_key_heads:
                raise ConfigError("block_stack.linear_num_value_heads must "
                                  "be a multiple of linear_num_key_heads")
        if "E" in self.pattern:
            self._need("an 'E'", "n_routed_experts", "num_experts_per_tok",
                       "moe_intermediate_size",
                       "moe_shared_expert_intermediate_size")
        if "F" in self.pattern:
            self._need("an 'F'", "n_routed_experts", "num_experts_per_tok",
                       "moe_intermediate_size",
                       "shared_expert_intermediate_size")
        if "C" in self.pattern:
            self._need("a 'C'", "num_attention_heads", "q_lora_rank",
                       "kv_lora_rank", "qk_nope_head_dim",
                       "qk_rope_head_dim", "v_head_dim", "rope_theta")
            if self.qk_rope_head_dim % 2:
                raise ConfigError("block_stack.qk_rope_head_dim must be "
                                  "even: its dims turn in pairs")
        if "D" in self.pattern:
            self._need("a 'D'", "intermediate_size")
        if "G" in self.pattern:
            self._need("a 'G'", "n_routed_experts", "num_experts_per_tok",
                       "moe_intermediate_size")
            if self.n_shared_experts < 0:
                raise ConfigError("block_stack: a 'G' block needs "
                                  "n_shared_experts (0 for none)")
        if set(self.pattern) & set("EFG"):
            if self.num_experts_per_tok > self.n_routed_experts:
                raise ConfigError("block_stack.num_experts_per_tok exceeds "
                                  "n_routed_experts")
            if (self.first_expert_held < 0 or self.experts_held < 0
                    or self.first_expert_held + self.held
                    > self.n_routed_experts):
                raise ConfigError(
                    "block_stack: experts first_expert_held.."
                    "first_expert_held+experts_held must lie within "
                    "n_routed_experts")


@dataclass(frozen=True)
class ModelSpec:
    """Model topology.

    For `mlp` this mirrors ModelConfig.json train params NumHiddenLayers /
    NumHiddenNodes / ActivationFunc (reference: ssgd_monitor.py:93-106) with a
    sigmoid scoring head named `shifu_output_0` (ssgd_monitor.py:121).
    """

    model_type: str = "mlp"
    hidden_nodes: tuple[int, ...] = (20,)     # reference fallback HIDDEN_NODES_COUNT=20 (ssgd_monitor.py:26)
    activations: tuple[str, ...] = ("leakyrelu",)  # reference default (ssgd_monitor.py:77-90)
    # Reference quirk, kept as explicit options: xavier init on *biases* too
    # (ssgd_monitor.py:66-70) and an L2 regularizer that is declared but never
    # added to the optimized loss (ssgd_monitor.py:59, loss at :129).
    xavier_bias_init: bool = True
    l2_scale: float = 0.0
    # embedding path (wide_deep / deepfm / ft_transformer)
    embedding_dim: int = 16
    # multitask: number of output heads (Shifu multi-target mode)
    num_heads: int = 1
    head_names: tuple[str, ...] = ("shifu_output_0",)
    # ft_transformer
    num_layers: int = 3
    num_attention_heads: int = 8
    token_dim: int = 64
    mlp_ratio: int = 4
    dropout_rate: float = 0.0
    # attention implementation for the transformer blocks: "local" (every
    # device holds the full token axis), "ring" (ppermute K/V rotation —
    # ops/attention.ring_attention), "ulysses" (all-to-all head scatter —
    # ops/attention.ulysses_attention), or "flash" (blockwise Pallas kernel,
    # O(S) memory — ops/pallas_attention.flash_attention).  ring/ulysses take
    # effect when the training mesh has a `seq` axis of size > 1; flash is a
    # per-device kernel choice; scoring/export always runs local.
    attention_impl: str = "local"
    # fused transformer block (ft_transformer): run each TransformerBlock's
    # attention + FFN as one Pallas pass (ops/pallas_ft_block) when the
    # feature-token count fits the kernel's shape class.  "auto" engages on
    # TPU backends (or under SHIFU_TPU_PALLAS interpret opt-in), "on"
    # forces (interpret mode off-TPU — the CI exactness path), "off"
    # keeps the unfused module math.  Inapplicable shapes, train-time
    # dropout, and ring/ulysses sequence parallelism always fall back.
    fused_block: str = "auto"
    # pipeline parallelism (ft_transformer): split the transformer blocks
    # into this many stages over the mesh's `pipe` axis, GPipe-style
    # microbatch schedule (parallel/pipeline.py).  1 = off.  Training-time
    # knob only: export always canonicalizes to the single-device graph.
    pipeline_stages: int = 1
    # microbatches per global batch when pipelined; 0 = pipeline_stages
    # (the minimum that keeps every stage busy at steady state)
    pipeline_microbatches: int = 0
    # moe_mlp: dense-gated mixture of expert MLP trunks; the expert axis
    # shards over the `model` mesh axis (true expert parallelism)
    num_experts: int = 4
    # rematerialization (gradient checkpointing): recompute each transformer
    # block's activations in the backward pass instead of storing them —
    # trades FLOPs for HBM on deep stacks / long token axes (jax.checkpoint)
    remat: bool = False
    # block_stack: the layer pattern and the published widths, one group
    block_stack: BlockStackSpec = field(default_factory=BlockStackSpec)
    # numerics
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"

    def validate(self) -> None:
        if self.model_type not in VALID_MODEL_TYPES:
            raise ConfigError(f"unknown model_type {self.model_type!r}; "
                              f"expected one of {VALID_MODEL_TYPES}")
        if len(self.hidden_nodes) != len(self.activations):
            raise ConfigError("hidden_nodes and activations must have equal length")
        for a in self.activations:
            if a not in VALID_ACTIVATIONS:
                raise ConfigError(f"unknown activation {a!r}")
        if self.num_heads != len(self.head_names):
            raise ConfigError("num_heads must match len(head_names)")
        if self.attention_impl not in ("local", "ring", "ulysses", "flash"):
            raise ConfigError(
                f"unknown attention_impl {self.attention_impl!r}; "
                "expected local|ring|ulysses|flash")
        if self.fused_block not in ("auto", "on", "off"):
            raise ConfigError(
                f"fused_block must be auto/on/off: {self.fused_block!r}")
        if self.model_type == "moe_mlp" and self.num_experts < 2:
            raise ConfigError("moe_mlp requires num_experts >= 2")
        if self.model_type == "block_stack":
            self.block_stack.validate()
            if self.attention_impl in ("ring", "ulysses"):
                raise ConfigError(
                    "block_stack runs its causal attention on one device: "
                    f"attention_impl {self.attention_impl!r} has no causal "
                    "mask (ops/attention.py)")
            if self.dropout_rate > 0:
                raise ConfigError("block_stack has no dropout")
        if self.pipeline_stages < 1 or self.pipeline_microbatches < 0:
            raise ConfigError("pipeline_stages must be >= 1 and "
                              "pipeline_microbatches >= 0")
        if self.pipeline_stages > 1:
            if self.model_type != "ft_transformer":
                raise ConfigError("pipeline_stages > 1 requires "
                                  "model_type='ft_transformer'")
            if self.num_layers % self.pipeline_stages != 0:
                raise ConfigError(
                    f"num_layers ({self.num_layers}) must be divisible by "
                    f"pipeline_stages ({self.pipeline_stages})")
            if self.attention_impl in ("ring", "ulysses"):
                raise ConfigError(
                    "pipeline_stages > 1 composes with local/flash attention "
                    "only (sequence parallelism uses its own mesh axis)")
            if self.dropout_rate > 0:
                raise ConfigError("pipeline_stages > 1 requires dropout_rate=0")


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class OptimizerConfig:
    """Optimizer selection.

    Reference default is Adadelta (ssgd_monitor.py:140) at LearningRate from
    ModelConfig.json, falling back to 0.003 (ssgd_monitor.py:134-137).
    """

    name: str = "adadelta"
    learning_rate: float = 0.003
    momentum: float = 0.9
    weight_decay: float = 0.0
    grad_clip_norm: float = 0.0     # 0 disables
    # gradient accumulation: the TPU analog of SAGN's 5-step local window
    # (reference: resources/SAGN.py:110-142) — accumulate k microbatch grads
    # before applying one update.
    accumulate_steps: int = 1
    # learning-rate schedule over optimizer steps (the reference only had a
    # constant LR): constant | cosine | exponential | warmup_cosine
    schedule: str = "constant"
    warmup_steps: int = 0           # linear warmup from 0 (warmup_cosine)
    decay_steps: int = 0            # horizon for cosine/exponential (required)
    decay_rate: float = 0.96        # per-decay_steps factor (exponential)
    end_lr_factor: float = 0.0      # final lr = learning_rate * this (cosine)

    def validate(self) -> None:
        if self.learning_rate <= 0:
            raise ConfigError("learning_rate must be positive")
        if self.accumulate_steps < 1:
            raise ConfigError("accumulate_steps must be >= 1")
        if self.schedule not in ("constant", "cosine", "exponential",
                                 "warmup_cosine"):
            raise ConfigError(f"unknown schedule {self.schedule!r}; expected "
                              "constant|cosine|exponential|warmup_cosine")
        if self.schedule != "constant" and self.decay_steps <= 0:
            raise ConfigError(
                f"schedule {self.schedule!r} requires decay_steps > 0")
        if self.warmup_steps < 0:
            raise ConfigError("warmup_steps must be >= 0")
        if (self.schedule == "warmup_cosine"
                and self.decay_steps <= self.warmup_steps):
            raise ConfigError(
                f"warmup_cosine requires decay_steps ({self.decay_steps}) > "
                f"warmup_steps ({self.warmup_steps})")


@dataclass(frozen=True)
class TrainConfig:
    epochs: int = 100               # reference: ModelConfig train.numTrainEpochs
    loss: str = "weighted_mse"      # reference semantics: tf.losses.mean_squared_error on sigmoid (ssgd_monitor.py:129)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    seed: int = 42
    eval_every_epochs: int = 1      # reference evaluates the valid set every epoch (ssgd_monitor.py:281-284)
    log_every_steps: int = 0        # 0: epoch-level logging only, like the reference
    bagging_sample_rate: float = 1.0
    # early stopping on the valid error (no reference analog — it always ran
    # all numTrainEpochs): stop after this many evaluated epochs without an
    # improvement of at least early_stop_min_delta.  0 disables.
    early_stop_patience: int = 0
    early_stop_min_delta: float = 0.0
    # True local SGD (the reference's SAGN trainer, resources/SAGN.py:110-196):
    # each data shard runs `local_sgd_window` plain-SGD updates on its OWN
    # parameter replica between global syncs (parameter all-mean).  0 = off
    # (every step is globally synchronous, the ssgd_monitor semantics).
    # Parameter averaging after K local lr-steps equals the reference's
    # "average the window's accumulated grads, apply globally, resync" with
    # an SGD apply at learning rate K*lr (it divides the window sum by K,
    # SAGN.py:137-142); shifu_compat divides a migrated SAGN config's
    # LearningRate by K to keep the effective step size.  KNOWN deviation:
    # the reference's local AND global applies use Adam (SAGN.py:107-108,
    # 158-159 — GradientDescent is commented out); this tier is plain SGD
    # (see validate() below and PARITY.md "Local SGD").
    local_sgd_window: int = 0
    # rows-touched-only optimizer updates for gather-path embedding tables
    # (train/sparse_embed.py — the SPMD successor of TF's IndexedSlices
    # sparse applies the reference relied on, ssgd_monitor.py:203-206).
    # "auto": engage when the optimizer has a sparse rule (adadelta/sgd),
    # the table is not model-axis sharded, and the vocab is large enough
    # that dense optimizer traffic dominates; "on": require it (raise with
    # the specific blocker otherwise); "off": always dense.
    sparse_embedding_update: str = "auto"

    def validate(self) -> None:
        if self.epochs <= 0:
            raise ConfigError("epochs must be positive")
        if self.sparse_embedding_update not in ("auto", "on", "off"):
            raise ConfigError(
                f"sparse_embedding_update must be auto/on/off: "
                f"{self.sparse_embedding_update!r}")
        if self.early_stop_patience < 0 or self.early_stop_min_delta < 0:
            raise ConfigError("early_stop_patience and early_stop_min_delta "
                              "must be >= 0")
        if not (0.0 < self.bagging_sample_rate <= 1.0):
            raise ConfigError("bagging_sample_rate must be in (0, 1]: "
                              f"{self.bagging_sample_rate}")
        if self.loss not in ("weighted_mse", "bce", "weighted_bce"):
            raise ConfigError(f"unknown loss {self.loss!r}")
        if self.local_sgd_window < 0:
            raise ConfigError("local_sgd_window must be >= 0")
        if self.local_sgd_window > 0:
            # this tier's local updates are plain p - lr*g; the reference
            # SAGN ran Adam locally AND globally (SAGN.py:107-108,158-159),
            # but momentum/adaptive state on diverged local replicas has no
            # sound averaging semantic here — reject rather than guess, and
            # document the optimizer-family deviation (PARITY.md)
            if self.optimizer.name != "sgd":
                raise ConfigError(
                    "local_sgd_window requires optimizer 'sgd' (this tier "
                    "implements plain-SGD local updates; the reference "
                    "SAGN's Adam family is a documented deviation), "
                    f"got {self.optimizer.name!r}")
            if self.optimizer.accumulate_steps > 1:
                raise ConfigError("local_sgd_window and accumulate_steps "
                                  "are mutually exclusive")
            if self.optimizer.schedule != "constant":
                raise ConfigError("local_sgd_window supports only the "
                                  "constant learning-rate schedule (local "
                                  "updates use the static lr)")
            if self.optimizer.grad_clip_norm > 0 or self.optimizer.weight_decay > 0:
                raise ConfigError(
                    "local_sgd_window applies plain p - lr*g local updates; "
                    "grad_clip_norm/weight_decay would be silently ignored "
                    "— unset them (the reference SAGN has neither)")
        self.optimizer.validate()


# ---------------------------------------------------------------------------
# Observability (device flight recorder — obs/devprof.py)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ObsConfig:
    """Device-profiling plane knobs (docs/OBSERVABILITY.md "Device flight
    recorder").  The reference's only profiling hook was a dead
    start_tensorboard (ssgd_monitor.py:493-502); here trace capture is a
    scheduled, bounded, journaled part of the train loop."""

    # which epochs capture a jax.profiler trace window, parsed into a
    # per-kernel `device_profile` journal event: "off" (default — the
    # flight recorder ring/watermarks stay on, only the profiler is
    # idle), "first" (the first trained epoch only), "every:N", or a
    # comma list of epoch numbers ("0,2,5").
    trace_epochs: str = "off"
    # where trace windows land; "" anchors a trace/ dir beside the
    # telemetry sinks (local job dirs; remote telemetry disables capture
    # — jax.profiler writes real files).
    trace_dir: str = ""
    # per-kernel rollup rows kept in the device_profile event (the tail
    # folds into other_us) — bounds journal bytes and label cardinality.
    trace_top_k: int = 16
    # poll device.memory_stats() at epoch boundaries into hbm_* gauges +
    # an hbm_watermark event (XLA memory-analysis estimate on backends
    # without allocator stats).
    hbm_watermarks: bool = True
    # flight recorder: ring size (last K per-chunk timings), the robust
    # z-score an anomalous chunk must exceed, how many prior chunks the
    # detector needs before judging, and the minimum slowdown ratio over
    # the ring median (the guard that keeps near-constant quiet series
    # from flagging scheduler jitter).
    anomaly_window: int = 32
    anomaly_zscore: float = 6.0
    anomaly_min_chunks: int = 8
    anomaly_min_ratio: float = 0.5

    def validate(self) -> None:
        from ..obs import devprof  # parse, don't duplicate the grammar
        try:
            devprof.parse_trace_epochs(self.trace_epochs)
        except ValueError as e:
            raise ConfigError(str(e))
        if self.trace_top_k < 1:
            raise ConfigError(
                f"obs.trace_top_k must be >= 1: {self.trace_top_k}")
        if self.anomaly_window < 4:
            raise ConfigError(
                f"obs.anomaly_window must be >= 4: {self.anomaly_window}")
        if self.anomaly_zscore <= 0 or self.anomaly_min_ratio < 0:
            raise ConfigError(
                "obs.anomaly_zscore must be > 0 and anomaly_min_ratio >= 0")
        if self.anomaly_min_chunks < 2:
            raise ConfigError(
                f"obs.anomaly_min_chunks must be >= 2: "
                f"{self.anomaly_min_chunks}")


# ---------------------------------------------------------------------------
# Sparse embedding engine (shifu_tpu/embed/ — docs/EMBEDDING.md)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class EmbedConfig:
    """Sparse embedding engine knobs (docs/EMBEDDING.md).  Rides on top of
    train.sparse_embedding_update: dedup and sharding shape HOW the
    rows-touched update runs; tiering governs where 10M+-vocab tables
    live (hot rows in HBM, cold tail on a host memmap)."""

    # per-batch unique-id compaction in the feeder placement stage:
    # "auto" (default — engages whenever a sparse plan engages), "off".
    # Ships (unique_ids, inverse) over H2D alongside features, so the
    # update touches each row once; exact under duplicates by
    # construction (tests/test_embed_engine.py pins bit-identity).
    dedup: str = "auto"
    # frequency-tiered table placement: "off" (default — the whole table
    # is device-resident) or "host" (cold tail served from a host
    # memmap; see embed/tiering.py).  Training-step residency swap is
    # future work (ROADMAP); "host" today serves feeder lookups.
    tiering: str = "off"
    # cold-tier storage dtype: "float32" (exact) or "int8" (4x smaller,
    # rides the cache-v2 wire quantization grid — lossy).
    tier_dtype: str = "float32"
    # hot-tier size: explicit row count, or 0 to derive from
    # hot_fraction of the vocab.
    hot_rows: int = 0
    hot_fraction: float = 0.05
    # where the cold-tier memmap + manifest land ("" = beside the job's
    # cache dir).
    cold_dir: str = ""
    # overlap next-batch cold-row fetches with the device step
    # (feeder-style background thread).
    prefetch: bool = True

    def validate(self) -> None:
        if self.dedup not in ("auto", "off"):
            raise ConfigError(
                f"embed.dedup must be auto|off: {self.dedup!r}")
        if self.tiering not in ("off", "host"):
            raise ConfigError(
                f"embed.tiering must be off|host: {self.tiering!r}")
        if self.tier_dtype not in ("float32", "int8"):
            raise ConfigError(
                f"embed.tier_dtype must be float32|int8: "
                f"{self.tier_dtype!r}")
        if self.hot_rows < 0:
            raise ConfigError(f"embed.hot_rows must be >= 0: "
                              f"{self.hot_rows}")
        if not (0.0 < self.hot_fraction <= 1.0):
            raise ConfigError(
                f"embed.hot_fraction must be in (0, 1]: "
                f"{self.hot_fraction}")


# ---------------------------------------------------------------------------
# Serving plane (runtime/serve.py — docs/SERVING.md)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DriftConfig:
    """Model-quality / data-drift observatory knobs (`shifu.drift.*` XML
    keys, obs/drift.py — docs/OBSERVABILITY.md "Drift observatory").

    Nested under ServingConfig so it threads unchanged through the
    daemon, fleet members and the loadtest probe.  Drift only engages
    when the served artifact actually carries a `baseline_profile.json`;
    `enabled` is the operator kill switch on top of that."""

    # kill switch: False silences the whole drift plane — no sketch
    # accumulation, no tick thread, zero drift events (the overhead
    # guard's contract).
    enabled: bool = True
    # fast/slow trailing windows (seconds): an alert objective must
    # violate in BOTH to fire (transient bursts don't page) and the
    # fast window alone resolves it (recovery is quick).
    fast_window_s: float = 60.0
    slow_window_s: float = 300.0
    # per-feature PSI threshold on the int8 wire grid, folded to 17
    # groups; conventional reading: < 0.1 stable, 0.1-0.25 moderate,
    # > 0.25 significant.  0 disables the feature_psi objective.
    psi_threshold: float = 0.25
    # KL(baseline || live) threshold for the score distribution;
    # 0 disables the score_kl objective.
    score_kl_threshold: float = 0.1
    # how many worst features a drift_report / drift_alert names
    top_k: int = 5
    # fast window must hold at least this many rows before any
    # judgment (quiet traffic never pages; idle unlatch below this).
    min_rows: int = 200
    # labeled-feedback path (wire FEEDBACK frame -> live AUC /
    # auc_decay); off rejects FEEDBACK frames with STATUS_ERROR.
    feedback: bool = True
    # score-bin resolution of the feedback AUC accumulator
    feedback_bins: int = 1024

    def validate(self) -> None:
        if self.fast_window_s <= 0 \
                or self.slow_window_s < self.fast_window_s:
            raise ConfigError(
                "drift windows need 0 < fast_window_s <= slow_window_s: "
                f"{self.fast_window_s}/{self.slow_window_s}")
        if self.psi_threshold < 0:
            raise ConfigError(
                f"drift.psi-threshold must be >= 0: {self.psi_threshold}")
        if self.score_kl_threshold < 0:
            raise ConfigError("drift.score-kl-threshold must be >= 0: "
                              f"{self.score_kl_threshold}")
        if self.top_k < 1:
            raise ConfigError(f"drift.top-k must be >= 1: {self.top_k}")
        if self.min_rows < 1:
            raise ConfigError(
                f"drift.min-rows must be >= 1: {self.min_rows}")
        if self.feedback_bins < 2:
            raise ConfigError("drift.feedback-bins must be >= 2: "
                              f"{self.feedback_bins}")


@dataclass(frozen=True)
class ServingConfig:
    """Knobs for the persistent scoring daemon (`shifu-tpu serve`).

    Standalone, not a JobConfig member: serving is driven from an export
    ARTIFACT, not a training job — the XML spelling (`shifu.serving.*`,
    utils/xmlconfig.serving_config_from_conf) layers the same way train
    keys do, with CLI flags as the top override."""

    # scoring engine tier: auto / native / numpy / stablehlo / jax / aot
    # (same ladder as `shifu-tpu score --engine`; `aot` forces the
    # artifact's pre-compiled executable pack, degrading to jax when the
    # pack is absent or fingerprint-incompatible)
    engine: str = "auto"
    # adaptive micro-batcher: a LONE request is dispatched after at most
    # this budget (ms); under load batches fill to max_batch and dispatch
    # immediately — the deadline only ever binds when traffic is sparse.
    latency_budget_ms: float = 2.0
    # largest coalesced batch (queue-depth-driven: everything waiting is
    # taken up to this, so batch size tracks load)
    max_batch: int = 4096
    # smallest padded-bucket shape for static-shape engines (jax /
    # stablehlo): batches pad up the power-of-two ladder
    # min_batch_bucket, 2x, 4x ... max_batch so the jit cache holds at
    # most log2(max_batch/min_batch_bucket)+1 executables
    min_batch_bucket: int = 16
    # admission bound: requests beyond this queue depth are rejected
    # with ServeOverload (backpressure to the caller, never a silent
    # drop or an unbounded-latency queue)
    queue_limit: int = 100_000
    # scoring worker threads draining the admission queue (numpy/native
    # release the GIL in their kernels, so >1 can help on big hosts)
    workers: int = 1
    # `serving_report` journal cadence (seconds); 0 disables the reporter
    report_every_s: float = 10.0
    # TCP port for `shifu-tpu serve` (0 = ephemeral, printed at startup)
    port: int = 8571
    # bind host for the wire server
    host: str = "127.0.0.1"
    # per-request lifecycle tracing (obs/slo.py, docs/OBSERVABILITY.md
    # "Serving SLO engine"): journal one sampled `request_trace` event —
    # the admission/queue/coalesce/dispatch/device/reply span chain whose
    # stage durations sum to the end-to-end latency — for every Nth
    # admitted request (deterministic 1-in-N).  0 disables sampling; the
    # per-stage `serve_stage_seconds` histograms stay on regardless.
    trace_sample: int = 0
    # p99 exemplars: how many slowest-request trace_ids a loadtest run
    # reports in its `loadtest_report` (0 disables; only meaningful with
    # trace_sample > 0 — exemplars come from the sampled traces)
    trace_exemplars: int = 5
    # serving SLO objectives (`shifu.serving.slo.*` XML keys); 0 disables
    # each.  p99 target in ms — pick a value on the latency bucket grid
    # (1/2.5/5/10/25...) so the violation count is bucket-exact; error
    # rate and availability are fractions (e.g. 0.001 / 0.999).
    slo_p99_ms: float = 0.0
    slo_error_rate: float = 0.0
    slo_availability: float = 0.0
    # multiwindow burn-rate alerting: both the fast and the slow trailing
    # window must burn the objective's budget at >= slo_burn_threshold x
    # the sustainable rate to fire ONE `slo_alert`; the alert latches
    # until the fast window is healthy again (burn < 1), then resolves.
    slo_fast_window_s: float = 60.0
    slo_slow_window_s: float = 300.0
    slo_burn_threshold: float = 2.0
    # model-quality / data-drift observatory (`shifu.drift.*` keys,
    # obs/drift.py); engages only when the artifact carries a
    # baseline_profile.json.
    drift: DriftConfig = field(default_factory=DriftConfig)
    # export-time opt-in (`shifu.serving.aot-pack` / `--aot-pack`):
    # compile the scorer for every rung of the padded bucket ladder at
    # save_artifact time and ship the serialized executables inside the
    # artifact (export/aot.py) — a fleet member then cold-starts by
    # deserializing instead of compiling.  Load side needs no flag: a
    # pack that matches the host fingerprint is used, anything else
    # falls back to jit.
    aot_pack: bool = False
    # warm EVERY bucket of the ladder (largest-first, small thread pool)
    # before a load/swap flips the registry pointer — so a post-failover
    # burst at any batch size never compiles in the hot path.  False
    # restores the old single 1-row warm.
    prewarm_ladder: bool = True

    def validate(self) -> None:
        if self.engine not in ("auto", "native", "numpy", "stablehlo",
                               "jax", "aot"):
            raise ConfigError(f"serving.engine must be one of auto/native/"
                              f"numpy/stablehlo/jax/aot: {self.engine!r}")
        if self.latency_budget_ms <= 0:
            raise ConfigError("serving.latency_budget_ms must be > 0: "
                              f"{self.latency_budget_ms}")
        if self.max_batch < 1 or self.min_batch_bucket < 1:
            raise ConfigError("serving.max_batch and min_batch_bucket must "
                              "be >= 1")
        if self.min_batch_bucket > self.max_batch:
            raise ConfigError(
                f"serving.min_batch_bucket ({self.min_batch_bucket}) must "
                f"not exceed max_batch ({self.max_batch})")
        if self.queue_limit < 1:
            raise ConfigError("serving.queue_limit must be >= 1")
        if self.workers < 1:
            raise ConfigError("serving.workers must be >= 1")
        if self.report_every_s < 0:
            raise ConfigError("serving.report_every_s must be >= 0")
        if not (0 <= self.port <= 65535):
            raise ConfigError(f"serving.port out of range: {self.port}")
        if self.trace_sample < 0:
            raise ConfigError("serving.trace_sample must be >= 0 "
                              f"(0 = off, N = 1-in-N): {self.trace_sample}")
        if self.trace_exemplars < 0:
            raise ConfigError("serving.trace-exemplars must be >= 0: "
                              f"{self.trace_exemplars}")
        if self.slo_p99_ms < 0:
            raise ConfigError(
                f"serving.slo.p99-ms must be >= 0: {self.slo_p99_ms}")
        if not (0 <= self.slo_error_rate < 1):
            raise ConfigError("serving.slo.error-rate must be in [0, 1): "
                              f"{self.slo_error_rate}")
        if not (0 <= self.slo_availability < 1):
            raise ConfigError("serving.slo.availability must be in [0, 1): "
                              f"{self.slo_availability}")
        if self.slo_fast_window_s <= 0 \
                or self.slo_slow_window_s < self.slo_fast_window_s:
            raise ConfigError(
                "serving SLO windows need 0 < slo_fast_window_s <= "
                f"slo_slow_window_s: {self.slo_fast_window_s}/"
                f"{self.slo_slow_window_s}")
        if self.slo_burn_threshold < 1:
            raise ConfigError("serving.slo.burn-threshold must be >= 1: "
                              f"{self.slo_burn_threshold}")
        self.drift.validate()


@dataclass(frozen=True)
class FleetConfig:
    """Knobs for the serving fleet (`shifu-tpu fleet`, runtime/fleet.py —
    docs/SERVING.md "Fleet").

    XML spelling `shifu.fleet.*` (utils/xmlconfig.fleet_config_from_conf)
    layers under CLI flags exactly like ServingConfig does.  The fleet is
    the successor of the reference AM's container supervision: N scoring
    daemons + hot-standby backups, heartbeat membership, a routing
    front-end, and burn-rate-driven scale decisions."""

    # active scoring daemons the manager keeps in rotation
    n_daemons: int = 2
    # pre-warmed hot standbys (loaded on the current artifact, wire
    # server bound, OUT of rotation) promoted on a member failure
    standbys: int = 1
    # heartbeat cadence: every member writes a lease this often; a lease
    # older than heartbeat_every_s * heartbeat_misses marks the member
    # DOWN and triggers failover
    heartbeat_every_s: float = 0.5
    heartbeat_misses: int = 3
    # router: per-request round-trip timeout before the one hedged retry
    # to a healthy peer, and the connect timeout for (re)building a
    # member connection
    route_timeout_ms: float = 1000.0
    connect_timeout_ms: float = 250.0
    # overload shedding: a primary whose fast-window slo_burn_rate is at
    # or above this routes around to the least-burned member
    shed_burn: float = 1.0
    # decorrelated-jitter reconnect backoff bounds for a member the
    # router observed failing (same shape as fsio's retry ladder)
    backoff_base_ms: float = 50.0
    backoff_cap_ms: float = 2000.0
    # scale loop: 0 disables; both burn windows must agree (fast AND
    # slow >= scale_up_burn on the worst member -> spawn; fast AND slow
    # <= scale_down_burn on every member -> retire) with a cooldown
    # between decisions
    scale_every_s: float = 0.0
    scale_up_burn: float = 2.0
    scale_down_burn: float = 0.25
    scale_cooldown_s: float = 30.0
    min_daemons: int = 1
    max_daemons: int = 8
    # consistent-ring virtual nodes per member (per-model routing)
    vnodes: int = 32
    # --- host plane (cross-host fleet, docs/SERVING.md) ---
    # launcher/pod.py host grammar: "" = single-host in-proc fleet (the
    # pre-host-plane behavior), "local:N" = N simulated hosts (tier-1
    # drills), "h1,h2"/"@file" = one `shifu-tpu serve` member per slot
    # over ssh
    hosts: str = ""
    # member spawn mode: "auto" (in-proc on local transport, process on
    # ssh), or force "inproc"/"process"
    member_mode: str = "auto"
    # first wire port for process-mode members (member i binds base+i)
    member_port_base: int = 8600
    # atomic artifact sync: each host pulls the export once, verifies it
    # against the exporter's blake2b manifest, atomically renames into
    # its cache, and only then swaps (torn/corrupt pulls quarantine the
    # member; the old version keeps serving)
    sync_artifacts: bool = True
    # split-brain guard: a DOWN member whose lease resurrects (partition
    # healed) rejoins as a STANDBY — never re-promoted into its old slot
    rejoin_standby: bool = True
    # fleet timeline (obs/timeline.py): estimate per-host clock offsets
    # from lease round-trips and merge member journals in the corrected
    # order; off = raw per-journal timestamps (debugging the estimator)
    timeline_skew_correct: bool = True
    # clamp on any single host's estimated |offset| — a lease stamped by
    # a wildly wrong clock must not fling the merged timeline
    timeline_max_offset_s: float = 300.0

    @property
    def heartbeat_ttl_s(self) -> float:
        """Lease freshness bound: miss this many beats -> DOWN."""
        return self.heartbeat_every_s * self.heartbeat_misses

    def validate(self) -> None:
        if self.n_daemons < 1:
            raise ConfigError(f"fleet.n-daemons must be >= 1: "
                              f"{self.n_daemons}")
        if self.standbys < 0:
            raise ConfigError(f"fleet.standbys must be >= 0: "
                              f"{self.standbys}")
        if self.heartbeat_every_s <= 0:
            raise ConfigError("fleet.heartbeat-every-s must be > 0: "
                              f"{self.heartbeat_every_s}")
        if self.heartbeat_misses < 1:
            raise ConfigError("fleet.heartbeat-misses must be >= 1: "
                              f"{self.heartbeat_misses}")
        if self.route_timeout_ms <= 0 or self.connect_timeout_ms <= 0:
            raise ConfigError("fleet.route-timeout-ms and "
                              "connect-timeout-ms must be > 0")
        if self.shed_burn <= 0:
            raise ConfigError(f"fleet.shed-burn must be > 0: "
                              f"{self.shed_burn}")
        if self.backoff_base_ms <= 0 \
                or self.backoff_cap_ms < self.backoff_base_ms:
            raise ConfigError(
                "fleet backoff needs 0 < backoff-base-ms <= "
                f"backoff-cap-ms: {self.backoff_base_ms}/"
                f"{self.backoff_cap_ms}")
        if self.scale_every_s < 0 or self.scale_cooldown_s < 0:
            raise ConfigError("fleet.scale-every-s and scale-cooldown-s "
                              "must be >= 0")
        if self.scale_down_burn < 0 \
                or self.scale_up_burn <= self.scale_down_burn:
            raise ConfigError(
                "fleet scale thresholds need 0 <= scale-down-burn < "
                f"scale-up-burn: {self.scale_down_burn}/"
                f"{self.scale_up_burn}")
        if not (1 <= self.min_daemons <= self.max_daemons):
            raise ConfigError(
                "fleet daemon bounds need 1 <= min-daemons <= "
                f"max-daemons: {self.min_daemons}/{self.max_daemons}")
        if not (self.min_daemons <= self.n_daemons <= self.max_daemons):
            raise ConfigError(
                f"fleet.n-daemons ({self.n_daemons}) must sit within "
                f"[min-daemons, max-daemons] = [{self.min_daemons}, "
                f"{self.max_daemons}]")
        if self.vnodes < 1:
            raise ConfigError(f"fleet.vnodes must be >= 1: {self.vnodes}")
        if self.member_mode not in ("auto", "inproc", "process"):
            raise ConfigError(
                "fleet.member-mode must be auto/inproc/process: "
                f"{self.member_mode!r}")
        if not (0 < self.member_port_base < 65536):
            raise ConfigError(
                f"fleet.member-port-base out of range: "
                f"{self.member_port_base}")
        if self.timeline_max_offset_s <= 0:
            raise ConfigError(
                "fleet.timeline-max-offset-s must be > 0: "
                f"{self.timeline_max_offset_s}")
        if self.hosts:
            # fail at config time, not at fleet start: the same grammar
            # parse_hosts uses later, minus the file read for @lists
            h = self.hosts.strip()
            if h.startswith("local:"):
                try:
                    n = int(h.split(":", 1)[1])
                except ValueError:
                    n = 0
                if n < 1:
                    raise ConfigError(
                        f"fleet.hosts {self.hosts!r}: need local:N "
                        "with N >= 1")
            elif not h.startswith("@") \
                    and not [x for x in h.split(",") if x.strip()]:
                raise ConfigError(f"fleet.hosts {self.hosts!r}: no hosts")


# ---------------------------------------------------------------------------
# Runtime / parallelism
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MeshConfig:
    """Logical device mesh.

    Replaces the reference's PS/worker container topology
    (yarn/util/CommonUtils.java:336-369 parseContainerRequests): `data` is the
    batch (data-parallel) axis — the successor of N workers; `model` shards
    parameters/embedding vocab — the successor of variable placement across PS
    tasks (ssgd_monitor.py:202-206 replica_device_setter); `seq` is the
    sequence/context-parallel axis for attention over long token axes.
    """

    data: int = 1
    model: int = 1
    seq: int = 1
    # pipeline-parallel axis: transformer stages hold disjoint layer blocks,
    # activations hop stage->stage over ICI (parallel/pipeline.py)
    pipe: int = 1
    axis_order: tuple[str, ...] = ("data", "seq", "pipe", "model")

    @property
    def num_devices(self) -> int:
        return self.data * self.model * self.seq * self.pipe

    def validate(self) -> None:
        for name in ("data", "model", "seq", "pipe"):
            if getattr(self, name) < 1:
                raise ConfigError(f"mesh axis {name} must be >= 1")
        known = {"data", "seq", "pipe", "model"}
        if not set(self.axis_order) <= known or len(set(self.axis_order)) != len(self.axis_order):
            raise ConfigError(f"axis_order must be distinct axes from {sorted(known)}: "
                              f"{self.axis_order}")
        for name in known - set(self.axis_order):
            if getattr(self, name) != 1:
                raise ConfigError(f"mesh axis {name} > 1 but missing from axis_order")


@dataclass(frozen=True)
class CheckpointConfig:
    directory: str = ""
    save_every_epochs: int = 1
    # time-based cadence (reference parity: Supervisor save_model_secs=10,
    # ssgd.py:124-128): also save mid-epoch when this many seconds elapsed
    # since the last save — per batch on the per-batch tier, per chunk on
    # the staged/streamed tiers (whose long out-of-HBM epochs are exactly
    # where mid-epoch durability matters).  0 disables.  A mid-epoch save
    # records the CURRENT epoch, so resume replays the interrupted epoch
    # from its start — a bounded re-application window, the price of
    # mid-epoch durability (the reference's restore was equally coarse).
    save_every_seconds: int = 0
    max_to_keep: int = 3
    resume: bool = True             # auto-resume from newest checkpoint (reference: MonitoredTrainingSession checkpoint_dir, ssgd_monitor.py:251-257)
    # async saves overlap checkpoint IO with the next epoch's compute.  Off
    # by default: the synchronous contract ("the save is durable before the
    # epoch callback runs, so an external kill never loses a completed
    # epoch") is the stronger fault-tolerance guarantee; turn on for large
    # models where the save stall matters and losing the newest in-flight
    # checkpoint to a kill only costs one extra epoch of recompute.
    async_save: bool = False


@dataclass(frozen=True)
class RuntimeConfig:
    mesh: MeshConfig = field(default_factory=MeshConfig)
    checkpoint: CheckpointConfig = field(default_factory=CheckpointConfig)
    # job-level controls (successors of shifu.application.* keys,
    # GlobalConfigurationKeys.java:34-60)
    app_name: str = "shifu_tpu"
    timeout_seconds: int = 0        # 0: no timeout; reference client kills the YARN app on timeout (TensorflowClient.java:625-658)
    max_restarts: int = 2           # checkpoint-restart budget; successor of backup-worker promotion (TensorflowApplicationMaster.java:410-426)
    # Supervisor liveness window (`shifu.liveness.seconds`): if the console
    # board stops growing for this long the child is presumed hung, killed,
    # and restarted (charging the restart budget) — successor of the AM's
    # heartbeat-expiry monitor (TensorflowApplicationMaster.java:63-112,
    # 1s x 25 misses).  Default 0 = off: the board is written once per
    # EPOCH, so a sane window must exceed the job's epoch time — a fixed
    # 25s default would false-kill any long epoch.
    liveness_seconds: float = 0.0
    # Elastic reshape floor (`shifu.pod.min-hosts`): when a pod gang
    # exhausts its restart budget and the SAME host keeps failing, the
    # dispatcher drops that host and restarts the gang at the reduced
    # world size (file shards rebalance through the env contract, the
    # global batch re-rounds to the new mesh, training resumes from
    # checkpoint) — as long as at least this many hosts remain.  The SPMD
    # successor of the reference's degraded start, which launched with
    # >= 95% of requested workers and re-packed task indices
    # (TensorflowApplicationMaster.java:230-338, thresholds
    # Constants.java:91-94).  0 = off (same-shape restarts only).
    min_hosts: int = 0
    final_model_path: str = ""      # FINAL_MODEL_PATH env in the reference
    tmp_model_path: str = ""        # TMP_MODEL_PATH env in the reference
    # Kerberos for secured HDFS access — successor of the reference client's
    # delegation-token fetch (TensorflowClient.java:481-502); a configured
    # principal+keytab runs kinit before data access, otherwise the ambient
    # ticket cache is used (libhdfs via pyarrow.fs picks it up)
    kerberos_principal: str = ""
    kerberos_keytab: str = ""
    distributed: bool = False       # multi-host: jax.distributed.initialize
    # tensor-parallel / custom parameter sharding from config: ordered
    # (param-path regex, per-dim axis names) rules, first match wins, axes
    # from the mesh ("data"/"seq"/"pipe"/"model") or None for unsharded.
    # XML: shifu.sharding.rules = "regex=axis,axis;regex2=axis" (see
    # utils/xmlconfig.parse_sharding_rules).  Applied before the built-in
    # embedding/pipeline rules in train/loop.init_state.
    param_sharding_rules: tuple[tuple[str, tuple[Optional[str], ...]], ...] = ()


# ---------------------------------------------------------------------------
# The whole job
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class JobConfig:
    schema: DataSchema = field(default_factory=DataSchema)
    data: DataConfig = field(default_factory=DataConfig)
    model: ModelSpec = field(default_factory=ModelSpec)
    train: TrainConfig = field(default_factory=TrainConfig)
    runtime: RuntimeConfig = field(default_factory=RuntimeConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)
    embed: EmbedConfig = field(default_factory=EmbedConfig)

    def validate(self) -> "JobConfig":
        self.schema.validate()
        self.data.validate()
        self.model.validate()
        self.train.validate()
        self.runtime.mesh.validate()
        self.obs.validate()
        self.embed.validate()
        if self.train.bagging_sample_rate < 1.0 and self.data.out_of_core:
            # subsampling fancy-indexes the dataset, which would materialize
            # memmap-backed out-of-core shards into RAM
            raise ConfigError("bagging_sample_rate < 1 is not supported with "
                              "out-of-core datasets")
        if self.data.wire_dtype == "int8" and self.schema.categorical_indices:
            # integer ids cannot ride an affine quantization grid (an id of
            # 300 would saturate at the clip); embedding models keep
            # f32/bf16 wire
            raise ConfigError(
                "wire_dtype=int8 requires a categorical-free feature matrix "
                f"({len(self.schema.categorical_indices)} categorical "
                "columns selected); use auto/bfloat16/float32")
        if (self.data.resident_format == "int8"
                and self.schema.categorical_indices):
            # the resident tier shares the wire_params affine grid
            raise ConfigError(
                "resident_format=int8 requires a categorical-free feature "
                f"matrix ({len(self.schema.categorical_indices)} categorical "
                "columns selected); use auto/wire")
        if self.model.model_type == "block_stack":
            by_index = {c.index: c for c in self.schema.columns}
            cols = [by_index.get(i) for i in self.schema.selected_indices]
            vocabs = {c.vocab_size if c is not None and c.is_categorical
                      else 0 for c in cols}
            if len(vocabs) != 1 or min(vocabs) < 1:
                raise ConfigError(
                    "block_stack reads fixed-width rows of token ids: every "
                    "selected column must be categorical, all of one "
                    "vocab_size (one column a position)")
            if self.train.local_sgd_window > 0:
                raise ConfigError("block_stack does not train under "
                                  "local_sgd_window")
        return self

    # -- serialization ------------------------------------------------------

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    def to_json(self, indent: int = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "JobConfig":
        return _from_dict(cls, d)

    @classmethod
    def from_json(cls, s: str) -> "JobConfig":
        return cls.from_dict(json.loads(s))

    def replace(self, **kw: Any) -> "JobConfig":
        return dataclasses.replace(self, **kw)


def _deep_tuple(v: Any) -> Any:
    """Lists (from JSON) to tuples at every nesting level — dataclass tuple
    fields like param_sharding_rules nest two deep, and equality/hash of the
    frozen configs requires tuples all the way down."""
    if isinstance(v, list):
        return tuple(_deep_tuple(x) for x in v)
    return v


def _from_dict(cls: type, d: Any) -> Any:
    """Recursively build a (possibly nested) dataclass from plain dicts/lists."""
    if not dataclasses.is_dataclass(cls):
        return d
    kwargs: dict[str, Any] = {}
    fields = {f.name: f for f in dataclasses.fields(cls)}
    for key, value in d.items():
        if key not in fields:
            raise ConfigError(f"unknown config key {key!r} for {cls.__name__}")
        f = fields[key]
        ftype = f.type if isinstance(f.type, type) else None
        # resolve nested dataclass types by inspecting the default factory
        default = f.default_factory() if f.default_factory is not dataclasses.MISSING else f.default  # type: ignore[misc]
        if dataclasses.is_dataclass(default) and isinstance(value, dict):
            kwargs[key] = _from_dict(type(default), value)
        elif key == "columns" and isinstance(value, (list, tuple)):
            kwargs[key] = tuple(_from_dict(ColumnSpec, v) if isinstance(v, dict) else v
                                for v in value)
        elif isinstance(value, list):
            kwargs[key] = _deep_tuple(value)
        else:
            kwargs[key] = value
    return cls(**kwargs)
