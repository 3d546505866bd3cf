"""The single JSON config.

TPU-native equivalent of the reference's ``runtime/config.py:674`` (``DeepSpeedConfig``):
one JSON file/dict configures the whole engine. Key names mirror the reference so that
existing DeepSpeed configs port ~1:1; TPU-specific extensions (the ``mesh`` section) are
additive. The batch-size triangle (``train_batch_size = micro_batch * grad_accum *
dp_world``) is resolved and validated exactly as the reference does.
"""

import enum
import json
import os
import typing

from .base import ConfigModel, ConfigError
from ..utils.logging import logger


class OffloadDeviceEnum(str, enum.Enum):
    """Reference: ``runtime/zero/offload_config.py:12``."""

    none = "none"
    cpu = "cpu"
    nvme = "nvme"


class OptimizerConfig(ConfigModel):
    type: str = "adamw"
    params: dict = {}


class SchedulerConfig(ConfigModel):
    type: str = ""
    params: dict = {}


class FP16Config(ConfigModel):
    """Reference: ``runtime/config.py`` fp16 section + ``runtime/fp16/loss_scaler.py``."""

    enabled: bool = False
    auto_cast: bool = False
    loss_scale: float = 0.0  # 0 => dynamic
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    min_loss_scale: float = 1.0


class BF16Config(ConfigModel):
    enabled: bool = False


class DeepSpeedZeroOffloadParamConfig(ConfigModel):
    """Reference: ``runtime/zero/offload_config.py`` (param offload)."""

    device: OffloadDeviceEnum = OffloadDeviceEnum.none
    nvme_path: str = ""
    buffer_count: int = 5
    buffer_size: int = 100_000_000
    max_in_cpu: int = 1_000_000_000
    pin_memory: bool = False


class DeepSpeedZeroOffloadOptimizerConfig(ConfigModel):
    """Reference: ``runtime/zero/offload_config.py`` (optimizer offload)."""

    device: OffloadDeviceEnum = OffloadDeviceEnum.none
    nvme_path: str = ""
    buffer_count: int = 4
    pin_memory: bool = False
    pipeline_read: bool = False
    pipeline_write: bool = False
    fast_init: bool = False


class ZeroConfig(ConfigModel):
    """Reference: ``runtime/zero/config.py:76`` (``DeepSpeedZeroConfig``).

    On TPU, stages 1-3 are realized as sharding specs over the data-parallel mesh axis
    (opt state / gradients / parameters sharded respectively); XLA's SPMD partitioner
    places the reduce-scatter/allgather collectives the reference issues by hand. Bucket
    and prefetch knobs are accepted for config compatibility; the XLA scheduler makes
    most of them advisory.
    """

    stage: int = 0
    # "compiler": trust XLA's SPMD scheduling of the stage-3 param gathers;
    # "per_layer": force a gather per scanned block inside the layer loop
    # (explicit schedule — the fetch-coordinator role, bounded live params)
    zero3_gather_mode: str = "compiler"
    # How per_layer realizes the gather: "constraint" leaves the collective
    # to the partitioner (which gathers the fp32 master and converts after —
    # a measured 2x on gather wire, PARITY.md known gaps); "shard_map" emits
    # an explicit bf16 all_gather island after the compute-dtype cast, half
    # the bytes on the wire.
    zero3_gather_impl: str = "constraint"
    # Wire dtype of the per-layer weight gathers. "auto" keeps the impl's
    # historical behavior (fp32 masters under "constraint", the compute dtype
    # under "shard_map"); "fp32" gathers masters; "bf16" casts to the 16-bit
    # compute dtype before the wire (half the gather bytes); "int8" is the
    # ZeRO++-style (qwZ) blockwise-quantized gather (~quarter the bytes,
    # per-block fp32 scales). bf16/int8 require stage 3 +
    # zero3_gather_mode="per_layer" and imply the shard_map impl (a
    # constraint chain cannot pin the wire dtype — PERF.md "known 2x").
    # Masters stay sharded fp32 in every mode; only the wire payload changes.
    zero3_gather_dtype: str = "auto"
    # int8 gather quantization granularity: elements per fp32 scale block
    # (wire overhead ~ 4/block bytes/param; leaves whose last dim the block
    # does not divide fall back to one scale per row)
    zero3_gather_block: int = 256
    # Wire dtype of the gradient reduction (reduce-scatter at stage >= 2,
    # all-reduce below): "bf16" casts each micro-batch's grads before the
    # sharding constraint, halving reduce wire bytes; accumulation across
    # micro-batches then also runs in bf16 (the reference's
    # communication_data_type / grad_accum_dtype semantics). The optimizer
    # step always runs fp32 on the sharded masters.
    grad_reduce_dtype: str = "fp32"
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    reduce_bucket_size: int = 500_000_000
    allgather_partitions: bool = True
    allgather_bucket_size: int = 500_000_000
    overlap_comm: bool = True
    round_robin_gradients: bool = False
    offload_param: DeepSpeedZeroOffloadParamConfig = DeepSpeedZeroOffloadParamConfig
    offload_optimizer: DeepSpeedZeroOffloadOptimizerConfig = DeepSpeedZeroOffloadOptimizerConfig
    sub_group_size: int = 1_000_000_000
    prefetch_bucket_size: int = 50_000_000
    param_persistence_threshold: int = 100_000
    model_persistence_threshold: int = 2 ** 62
    max_live_parameters: int = 1_000_000_000
    max_reuse_distance: int = 1_000_000_000
    gather_16bit_weights_on_model_save: bool = False
    ignore_unused_parameters: bool = True
    legacy_stage1: bool = False
    elastic_checkpoint: bool = False

    deprecated_fields = {
        "stage3_gather_16bit_weights_on_model_save": "gather_16bit_weights_on_model_save",
        "stage3_max_live_parameters": "max_live_parameters",
        "stage3_max_reuse_distance": "max_reuse_distance",
        "stage3_prefetch_bucket_size": "prefetch_bucket_size",
        "stage3_param_persistence_threshold": "param_persistence_threshold",
        "cpu_offload": "offload_optimizer",
    }

    def _validate(self):
        if self.stage not in (0, 1, 2, 3):
            raise ConfigError(f"zero_optimization.stage must be in 0..3, got {self.stage}")
        if self.zero3_gather_mode not in ("compiler", "per_layer"):
            raise ConfigError(
                f"zero_optimization.zero3_gather_mode must be 'compiler' or "
                f"'per_layer', got {self.zero3_gather_mode!r}")
        if self.zero3_gather_dtype not in ("auto", "fp32", "bf16", "int8"):
            raise ConfigError(
                f"zero_optimization.zero3_gather_dtype must be one of "
                f"auto|fp32|bf16|int8, got {self.zero3_gather_dtype!r}")
        if self.zero3_gather_dtype in ("bf16", "int8"):
            if self.stage != 3:
                raise ConfigError(
                    f"zero_optimization.zero3_gather_dtype="
                    f"{self.zero3_gather_dtype!r} requires stage 3 (got stage "
                    f"{self.stage}); below stage 3 params are not partitioned "
                    f"and there is no weight gather to compress")
            if self.zero3_gather_mode != "per_layer":
                raise ConfigError(
                    f"zero_optimization.zero3_gather_dtype="
                    f"{self.zero3_gather_dtype!r} requires "
                    f"zero3_gather_mode='per_layer' (got "
                    f"{self.zero3_gather_mode!r}): under 'compiler' the "
                    f"partitioner owns the gathers and reshards the fp32 "
                    f"masters — the wire dtype cannot be pinned")
        if self.zero3_gather_block < 1:
            raise ConfigError(
                f"zero_optimization.zero3_gather_block must be >= 1, got "
                f"{self.zero3_gather_block}")
        if self.grad_reduce_dtype not in ("fp32", "bf16"):
            raise ConfigError(
                f"zero_optimization.grad_reduce_dtype must be 'fp32' or "
                f"'bf16', got {self.grad_reduce_dtype!r}")

    @classmethod
    def from_dict(cls, d):
        d = dict(d or {})
        # legacy bool cpu_offload -> offload_optimizer section
        if isinstance(d.get("cpu_offload"), bool):
            flag = d.pop("cpu_offload")
            if flag:
                d.setdefault("offload_optimizer", {"device": "cpu"})
        return super().from_dict(d)


class ActivationCheckpointingConfig(ConfigModel):
    """Reference: ``runtime/activation_checkpointing/checkpointing.py`` config keys.

    On TPU this maps to ``jax.checkpoint`` (remat) policies applied to the
    scan-over-layers; ``partition_activations`` maps to sequence/TP-sharded residuals.
    """

    partition_activations: bool = False
    contiguous_memory_optimization: bool = False
    cpu_checkpointing: bool = False
    number_checkpoints: int = 0
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False


class MeshConfig(ConfigModel):
    """TPU-native extension: the device mesh (no reference analogue; the reference's
    ``runtime/pipe/topology.py`` ProcessTopology axes map here).

    Axis sizes; -1 on ``data`` means "use all remaining devices". Product of all axes
    must equal the device count.
    """

    data: int = -1
    model: int = 1
    pipe: int = 1
    seq: int = 1
    expert: int = 1


class HybridEngineConfig(ConfigModel):
    """RLHF hybrid engine (reference ``runtime/hybrid_engine.py:32`` +
    ``deepspeed/__init__.py:143`` selection)."""

    enabled: bool = False
    max_out_tokens: int = 512
    # rollout prompts pad to a multiple of this so PPO batches with varying
    # prompt lengths share compiled programs (1 disables)
    prompt_bucket_size: int = 64


class CheckpointConfig(ConfigModel):
    """Checkpoint engine selection (reference ``runtime/checkpoint_engine/`` +
    ``deepspeed/checkpoint/`` universal layout). "sharded" writes per-process
    index-range-addressed shards and reshapes on load across mesh changes;
    "npz" is the legacy single-file gather-to-host engine."""

    engine: str = "sharded"  # sharded | npz
    async_save: bool = False
    # transient-I/O retry (network filesystems): total attempts per durable
    # write step, and the exponential-backoff base delay in seconds
    retries: int = 3
    retry_backoff: float = 0.05

    def _validate(self):
        if self.retries < 1:
            raise ConfigError(
                f"checkpoint.retries is the TOTAL attempts per durable write "
                f"step and must be >= 1 (1 = no retry), got {self.retries}")
        if self.retry_backoff < 0:
            raise ConfigError(
                f"checkpoint.retry_backoff must be >= 0, got "
                f"{self.retry_backoff}")


class PipelineConfig(ConfigModel):
    """Pipeline-parallel schedule selection (reference ``runtime/pipe/schedule.py``:
    ``TrainSchedule`` is 1F1B, the in-flight-bounded default; "gpipe" keeps the
    AD-through-scan path whose activation footprint grows with microbatch count)."""

    schedule: str = "1f1b"  # 1f1b | gpipe


class TensorBoardConfig(ConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"


class WandbConfig(ConfigModel):
    enabled: bool = False
    group: str = ""
    team: str = ""
    project: str = "deepspeed_tpu"


class CSVConfig(ConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"


class CommsLoggerConfig(ConfigModel):
    """Reference: ``comm/config.py`` + ``utils/comms_logging.py``."""

    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False
    prof_ops: list = []


class KVPoolConfig(ConfigModel):
    """The serving engine's KV store (``serving/kv_pool.py``): a fixed-shape
    pool of token blocks plus a per-slot block table, never one dense
    ``n_slots x max_len`` region. Blocks are allocated/freed at request
    granularity on the host; the decode program reads through the (traced)
    block table, so it still compiles exactly once. Slot count is not capped
    by worst-case sequence length — requests reserve ``ceil((prompt +
    max_new - 1) / block_size)`` blocks, their real footprint. With no key
    set the pool holds ``n_slots * max_len`` tokens. Which decode attention
    runs over it is the engine's choice from what it can observe;
    ``snapshot()["kv_pool"]`` names the path that ran (``attention_backend``
    ``kernel`` or ``view``, ``attention_reason``, ``decode_dispatches``)."""

    # tokens per KV block; serving max_len must be a multiple of it
    block_size: int = 16
    # physical blocks in the pool, INCLUDING the reserved garbage block 0
    # (freed slots' dead decode writes land there). 0 = auto: room for
    # every slot at max_len, n_slots * (max_len / block_size) + 1.
    n_blocks: int = 0
    # "" = the engine serving dtype; "int8" stores blocks as int8 payloads
    # with per-(token, head) fp32 scales (the ZeRO++ blockwise kernels from
    # comm/collectives.py), ~halving pool HBM at a pinned logits tolerance
    kv_dtype: str = ""
    # copy-on-write shared-prefix cache: full prompt blocks are content-
    # addressed; an identical prefix maps to the SAME physical blocks
    # (refcounted) and only the suffix is prefilled
    prefix_cache: bool = True
    # reserve-as-you-decode: admission reserves only the PROMPT's blocks and
    # decode blocks are allocated as cursors advance (admission stops paying
    # for tokens not yet generated — effective concurrency rises). On pool
    # exhaustion mid-decode the newest request is preempted back to the
    # queue (resuming bitwise-identical) instead of OOM/shed. False = the
    # PR 7 whole-footprint reservation.
    on_demand_growth: bool = False

    def _validate(self):
        if self.block_size < 1:
            raise ConfigError(
                f"kv_pool.block_size must be >= 1, got {self.block_size}")
        if self.n_blocks < 0:
            raise ConfigError(
                f"kv_pool.n_blocks must be >= 0, got {self.n_blocks}")
        if self.kv_dtype not in ("", "int8"):
            raise ConfigError(
                f"kv_pool.kv_dtype must be '' or 'int8', got {self.kv_dtype!r}")


class ChunkedPrefillConfig(ConfigModel):
    """Chunked prefill (``serving/engine.py``): split a long prompt's prefill
    into fixed-token chunks interleaved with decode steps, so a single long
    arrival cannot stall the co-batched decode program — a bounded-TPOT
    guarantee instead of an unbounded prefill window. Each chunk rides the
    shared-prefix suffix-prefill machinery (one compiled program per chunk
    bucket, start position traced), so chunking changes the SCHEDULE, never
    the math: greedy streams stay bitwise-equal to ``generate()``."""

    enabled: bool = False
    # tokens per prefill chunk (bucketed by the prompt-bucket policy, so all
    # full chunks share one compiled suffix program)
    chunk_size: int = 64
    # decode steps run for the co-batched slots between consecutive chunks.
    # The virtual-clock worst inter-token gap for a running decoder is
    # chunk_bucket * prefill_cost + decode_step_cost (one chunk at most
    # lands between two decode steps); raising this knob does not shrink
    # that ceiling — it slows the long prompt's prefill in exchange for
    # more decode throughput between chunks.
    decode_steps_between_chunks: int = 1

    def _validate(self):
        if self.chunk_size < 1:
            raise ConfigError(
                f"chunked_prefill.chunk_size must be >= 1, got "
                f"{self.chunk_size}")
        if self.decode_steps_between_chunks < 1:
            raise ConfigError(
                "chunked_prefill.decode_steps_between_chunks must be >= 1, "
                f"got {self.decode_steps_between_chunks}")


class RouterConfig(ConfigModel):
    """Multi-replica router (``serving/router.py``): N ServingEngine replicas
    behind a load-aware dispatcher. Scoring extends the single-replica
    shed-with-reason admission control into cross-replica balancing: replicas
    are scored on queue depth + slot/block occupancy (from
    ``ServingMetrics``), with session and prefix affinity (the paged pool's
    SHA-256 prefix chain keys as the cross-replica currency) steering
    repeated system prompts to the replica already holding their blocks."""

    # least_loaded (default) scores replicas on load; round_robin cycles
    policy: str = "least_loaded"
    # sticky sessions: requests with the same session_id land on the same
    # replica (until it drains or saturates)
    session_affinity: bool = True
    # shared prefix index: full-prompt-block chain keys -> replica, so an
    # identical system prompt routes to the replica whose paged pool already
    # caches its blocks (suffix-only prefill there)
    prefix_affinity: bool = True
    # bound on the shared prefix index (LRU past it)
    prefix_index_cap: int = 4096
    # load-score weights (normalized queue depth / slot occupancy / paged
    # block occupancy)
    queue_weight: float = 1.0
    slot_weight: float = 1.0
    block_weight: float = 1.0
    # an affinity target whose load score exceeds the best candidate's by
    # more than this margin is overridden (counted as a rebalance)
    rebalance_margin: float = 1.0

    def _validate(self):
        if self.policy not in ("least_loaded", "round_robin"):
            raise ConfigError(
                f"router.policy must be 'least_loaded' or 'round_robin', "
                f"got {self.policy!r}")
        if self.prefix_index_cap < 1:
            raise ConfigError(
                f"router.prefix_index_cap must be >= 1, got "
                f"{self.prefix_index_cap}")
        if self.rebalance_margin < 0:
            raise ConfigError(
                f"router.rebalance_margin must be >= 0, got "
                f"{self.rebalance_margin}")


class SpeculativeConfig(ConfigModel):
    """Speculative decoding on the serving stack (``serving/speculative.py``
    + the verify program in ``models/decoding.py``): a drafter proposes up
    to ``k`` tokens per greedy slot, ONE target forward over k+1 positions
    verifies them against the paged cache, and the longest agreeing prefix
    is accepted (greedy acceptance, arXiv:2211.17192 — bitwise-checkable
    against ``generate()``). Rejected candidates roll back by cursor
    decrement; blocks left entirely past the cursor are released/scrubbed
    at block granularity. Sampled (temperature > 0) requests never
    speculate — their per-slot rng streams advance exactly once per
    dispatched step either way, so enabling/disabling speculation cannot
    perturb a seeded stream."""

    enabled: bool = False
    # "ngram" = prompt-lookup drafting, zero extra weights: match the last
    # ``ngram`` tokens against the request's own prompt+generated history
    # and propose the continuation of the most recent earlier occurrence.
    # "model" = a small draft model sharing the mesh (separate params, its
    # own tiny dense KV cache; see ``draft_model``).
    drafter: str = "ngram"
    # max draft tokens per verify step; the verify program is shaped by k
    # (drafts pad to k), so it compiles exactly once per k
    k: int = 4
    # match length for the ngram drafter
    ngram: int = 2
    # TransformerConfig overrides for the draft model (vocab_size and
    # max_seq_len are pinned to the target's); default = a 1-layer copy of
    # the target config
    draft_model: dict = {}
    # draft-model init seed (the drafter only shapes PROPOSALS — accepted
    # output is provably the target's own greedy stream either way)
    draft_seed: int = 0
    # virtual-clock cost per PROPOSED token for the model drafter (the
    # ngram drafter is free); the verify itself costs one decode step —
    # it is one target forward, which is the whole latency play
    virtual_draft_cost_per_token: float = 0.0

    def _validate(self):
        if self.drafter not in ("ngram", "model"):
            raise ConfigError(
                f"speculative.drafter must be 'ngram' or 'model', got "
                f"{self.drafter!r}")
        if self.k < 1:
            raise ConfigError(
                f"speculative.k must be >= 1, got {self.k}")
        if self.ngram < 1:
            raise ConfigError(
                f"speculative.ngram must be >= 1, got {self.ngram}")
        if self.virtual_draft_cost_per_token < 0:
            raise ConfigError(
                f"speculative.virtual_draft_cost_per_token must be >= 0, "
                f"got {self.virtual_draft_cost_per_token}")


class SLOConfig(ConfigModel):
    """Serving latency objectives (``serving.slo``): P99 targets graded
    against the streaming latency digests (``telemetry/digest.py``) that
    ``ServingMetrics`` and the Router maintain per replica and
    fleet-aggregated. A target of 0 disables that objective. When any
    target is set, the metrics cadence emits ``Serving/ttft_p99_ms``-style
    scalars plus a structured ``slo/violation`` trace event with the
    burn rate (fraction of requests over target / the 1% error budget a
    P99 objective grants) whenever the observed P99 exceeds its target;
    ``tools/fleet_report.py --fail-on slo`` turns the same grade into an
    exit code."""

    # P99 targets in milliseconds (virtual-clock units x1e3 under a
    # VirtualClock); 0 = objective off
    ttft_p99_ms: float = 0.0
    tpot_p99_ms: float = 0.0
    queue_wait_p99_ms: float = 0.0

    def _validate(self):
        for field in ("ttft_p99_ms", "tpot_p99_ms", "queue_wait_p99_ms"):
            if getattr(self, field) < 0:
                raise ConfigError(
                    f"slo.{field} must be >= 0 (0 disables), got "
                    f"{getattr(self, field)}")

    def targets_ms(self):
        """The evaluate_slo() input dict (keys carry the _p99_ms suffix)."""
        return {"ttft_p99_ms": self.ttft_p99_ms,
                "tpot_p99_ms": self.tpot_p99_ms,
                "queue_wait_p99_ms": self.queue_wait_p99_ms}

    @property
    def armed(self):
        return any(v > 0 for v in self.targets_ms().values())


class MigrationConfig(ConfigModel):
    """Live KV migration (``serving/migration.py``): serialize a running
    request's physical state — pool blocks (raw pool-dtype bytes + int8
    scales where applicable), block-table row, cursor, per-slot rng key,
    sampling knobs, prefix chain keys — into a portable snapshot and splice
    it into a peer replica through the compiled insert path. The Router
    uses it three ways: failover after a replica kill, ``drain(idx,
    migrate=True)``, and cross-replica retry. Migrated streams are bitwise
    vs stay-put (greedy and seeded sampled)."""

    enabled: bool = True
    # capture a periodic snapshot every N committed tokens per request
    # (0 = off): bounds kill-recovery replay to tokens since last snapshot
    snapshot_interval_tokens: int = 0
    # virtual-clock cost per migrated block on the TARGET replica (models
    # the splice DMA; keeps drain-vs-wait comparisons honest)
    virtual_cost_per_block: float = 0.002

    def _validate(self):
        if self.snapshot_interval_tokens < 0:
            raise ConfigError(
                f"migration.snapshot_interval_tokens must be >= 0, got "
                f"{self.snapshot_interval_tokens}")
        if self.virtual_cost_per_block < 0:
            raise ConfigError(
                f"migration.virtual_cost_per_block must be >= 0, got "
                f"{self.virtual_cost_per_block}")


class PoolsConfig(ConfigModel):
    """Disaggregated prefill/decode fleet (``serving/router.py``): partition
    the Router's replicas into a PREFILL pool (first ``prefill_replicas``
    indices) and a DECODE pool (the rest). Prefill replicas run prompts to
    the first token, capture a FRESH live-migration snapshot (partial tail
    block included — the PR 16 zero-recompute contract) and hand the stream
    off to a decode replica through the compiled insert path; decode
    replicas only ever decode. Long prompts stop interfering with in-flight
    decode latency — disaggregation ELIMINATES the interference chunked
    prefill only amortizes (DeepSpeed-Inference, arXiv:2207.00032).
    Disabled (the default) keeps every replica mixed."""

    enabled: bool = False
    # pool sizes; together they must equal the Router's replica count
    # (checked at Router construction — the config cannot see the fleet)
    prefill_replicas: int = 1
    decode_replicas: int = 1
    # per-pool chunked-prefill chunk-size overrides (0 = inherit the shared
    # serving.chunked_prefill.chunk_size): prefill replicas typically want
    # LARGER chunks (no co-resident decodes to protect), decode replicas
    # smaller ones (they only ever prefill on failover/rebalance splices)
    prefill_chunk_size: int = 0
    decode_chunk_size: int = 0
    # per-pool speculative-decoding overrides ("" = inherit serving.
    # speculative.enabled, "on"/"off" = force): speculation only pays on
    # the decode pool — a prefill replica holds each stream for one token
    prefill_speculation: str = ""
    decode_speculation: str = ""

    def _validate(self):
        if self.prefill_replicas < 1:
            raise ConfigError(
                f"pools.prefill_replicas must be >= 1, got "
                f"{self.prefill_replicas}")
        if self.decode_replicas < 1:
            raise ConfigError(
                f"pools.decode_replicas must be >= 1, got "
                f"{self.decode_replicas}")
        for field in ("prefill_chunk_size", "decode_chunk_size"):
            if getattr(self, field) < 0:
                raise ConfigError(
                    f"pools.{field} must be >= 0 (0 inherits), got "
                    f"{getattr(self, field)}")
        for field in ("prefill_speculation", "decode_speculation"):
            if getattr(self, field) not in ("", "on", "off"):
                raise ConfigError(
                    f"pools.{field} must be '', 'on' or 'off', got "
                    f"{getattr(self, field)!r}")


class RebalanceConfig(ConfigModel):
    """Live decode rebalancing (``serving/router.py``): the actuator over
    the live-migration mechanism — the Router watches per-replica load
    scores (occupancy, queue depth, the same signals routing uses) and
    migrates long-tail decode streams off hot replicas mid-flight. The
    trigger is hysteresis-guarded so it provably never thrashes: a move
    fires only when the hot/cold load gap exceeds ``min_gain`` (and a move
    of one stream cannot invert a gap that large back past the threshold),
    at most ``max_concurrent`` streams move per trigger, and the trigger
    then cools down for ``cooldown`` seconds. Voluntary moves never burn
    the ``serving.retry_limit`` budget."""

    enabled: bool = False
    # minimum hot-minus-cold load-score gap before any stream moves; also
    # the hysteresis band — below it the fleet is "balanced enough"
    min_gain: float = 0.25
    # seconds (virtual under a VirtualClock) between triggers
    cooldown: float = 0.5
    # streams moved per trigger (bounded blast radius)
    max_concurrent: int = 1
    # router loop iterations between load evaluations (the check is cheap
    # but per-step evaluation would just hit the cooldown gate anyway)
    interval: int = 8

    def _validate(self):
        if self.min_gain <= 0:
            raise ConfigError(
                f"rebalance.min_gain must be > 0 (the hysteresis band), "
                f"got {self.min_gain}")
        if self.cooldown < 0:
            raise ConfigError(
                f"rebalance.cooldown must be >= 0, got {self.cooldown}")
        if self.max_concurrent < 1:
            raise ConfigError(
                f"rebalance.max_concurrent must be >= 1, got "
                f"{self.max_concurrent}")
        if self.interval < 1:
            raise ConfigError(
                f"rebalance.interval must be >= 1, got {self.interval}")


class AutoscalerConfig(ConfigModel):
    """SLO-driven replica autoscaling (``serving/control.py``): the Router
    watches the windowed ``slo_burn_rate`` + queue depth of each replica
    group (the whole fleet, or each prefill/decode pool independently under
    ``serving.pools``) and scales the ACTIVE replica set through the
    existing drain(migrate=True)/rejoin lifecycle — scale up on sustained
    burn, drain down on sustained idle. The fleet the Router was built
    with is the ceiling; ``min_replicas`` is the floor (per pool when
    pools are enabled). Hysteresis follows the rebalance overshoot-guard
    discipline: a dead band between the up and down thresholds, N
    consecutive evaluations before any action, a cooldown between actions,
    and a capacity guard that refuses a drain-down unless the surviving
    replicas can absorb every in-flight stream — so the controller
    provably never thrashes (a down can only fire when it cannot
    re-create the up signal from the load present at decision time)."""

    enabled: bool = False
    # floor of ACTIVE replicas (per pool under serving.pools); the replica
    # list the Router was constructed with is the ceiling
    min_replicas: int = 1
    # windowed burn rate (samples since the previous evaluation) at/above
    # which an evaluation counts toward scale-up
    scale_up_burn: float = 1.0
    # windowed burn rate at/below which (with an empty queue) an
    # evaluation counts toward drain-down; must sit strictly below
    # scale_up_burn — this gap IS the hysteresis dead band
    scale_down_burn: float = 0.25
    # mean queue depth per active replica that also arms scale-up
    # (0 disables the queue trigger; burn alone then drives it)
    scale_up_queue_depth: float = 0.0
    # consecutive armed evaluations before an action fires
    sustain_evals: int = 2
    # seconds (virtual under a VirtualClock) between scale actions
    cooldown: float = 4.0
    # router loop iterations between evaluations (cf. rebalance.interval)
    interval: int = 8

    def _validate(self):
        if self.min_replicas < 1:
            raise ConfigError(
                f"autoscaler.min_replicas must be >= 1, got "
                f"{self.min_replicas}")
        if self.scale_up_burn <= 0:
            raise ConfigError(
                f"autoscaler.scale_up_burn must be > 0, got "
                f"{self.scale_up_burn}")
        if not 0 <= self.scale_down_burn < self.scale_up_burn:
            raise ConfigError(
                "autoscaler.scale_down_burn must sit in [0, scale_up_burn) "
                f"— the hysteresis dead band — got {self.scale_down_burn} "
                f"vs scale_up_burn={self.scale_up_burn}")
        if self.scale_up_queue_depth < 0:
            raise ConfigError(
                f"autoscaler.scale_up_queue_depth must be >= 0 (0 "
                f"disables), got {self.scale_up_queue_depth}")
        if self.sustain_evals < 1:
            raise ConfigError(
                f"autoscaler.sustain_evals must be >= 1, got "
                f"{self.sustain_evals}")
        if self.cooldown < 0:
            raise ConfigError(
                f"autoscaler.cooldown must be >= 0, got {self.cooldown}")
        if self.interval < 1:
            raise ConfigError(
                f"autoscaler.interval must be >= 1, got {self.interval}")


class TenantClassConfig(ConfigModel):
    """One tenant class (``serving.tenants.interactive`` / ``.batch``):
    the weighted-fair share, the per-tenant token-bucket admission budget,
    and an optional per-class TTFT objective for per-tenant SLO grading."""

    # weighted-fair admission share (start-time fair queuing over tenants:
    # a tenant's virtual time advances by admitted_tokens / weight)
    weight: float = 1.0
    # per-TENANT token-bucket budget: sustained admitted tokens
    # (prompt + max_new_tokens) per second (virtual under a VirtualClock);
    # 0 = unlimited. Over-budget requests WAIT in the queue (deferral,
    # not shedding) until the bucket refills — enforcement is exact under
    # the virtual clock.
    token_budget_per_s: float = 0.0
    # bucket capacity (burst); 0 = one second's refill (token_budget_per_s)
    token_budget_burst: float = 0.0
    # per-class TTFT P99 target for per-tenant SLO grades (ms; 0 inherits
    # serving.slo.ttft_p99_ms)
    ttft_p99_ms: float = 0.0

    def _validate(self):
        if self.weight <= 0:
            raise ConfigError(
                f"tenants class weight must be > 0, got {self.weight}")
        for field in ("token_budget_per_s", "token_budget_burst",
                      "ttft_p99_ms"):
            if getattr(self, field) < 0:
                raise ConfigError(
                    f"tenants class {field} must be >= 0, got "
                    f"{getattr(self, field)}")


class TenantsConfig(ConfigModel):
    """Multi-tenant QoS (``serving.tenants``): requests carry a
    ``tenant_id`` + a class (``interactive`` | ``batch``); admission
    becomes weighted-fair across tenants (``serving.policy:
    "weighted_fair"``) with per-tenant token budgets, and a latency-class
    arrival may evict a batch-class stream mid-flight through the
    rollback-safe preemption machinery (the evicted stream resumes
    bitwise-identically — the PR 12/14 contract)."""

    enabled: bool = False
    interactive: TenantClassConfig = None   # default weight 4.0
    batch: TenantClassConfig = None         # default weight 1.0
    # priority preemption: when no slot is free and an arrived interactive
    # request waits, preempt the NEWEST-admitted batch-class stream
    # (preemption rides the block-release machinery)
    preempt: bool = True

    def _validate(self):
        if self.interactive is None:
            self.interactive = TenantClassConfig(weight=4.0)
        if self.batch is None:
            self.batch = TenantClassConfig(weight=1.0)

    def class_config(self, tenant_class):
        return self.batch if tenant_class == "batch" else self.interactive


class DegradedConfig(ConfigModel):
    """Degraded modes as first-class policy (``serving.degraded``): an
    ordered ladder the engine climbs under sustained SLO burn and descends
    when the burn clears, with entry/exit hysteresis so the ladder never
    oscillates. Rungs, in order: (1) shed new batch-class requests,
    (2) also cap ``max_new_tokens`` on new admissions, (3) also drop
    speculation (the compiled verify stays warm; seeded streams are
    unaffected — the PR 14 pin), (4) shed interactive too — the last
    resort. Interactive traffic is never shed before rung 4."""

    enabled: bool = False
    # windowed burn rate at/above which an evaluation counts toward
    # climbing one rung
    enter_burn: float = 1.0
    # windowed burn rate at/below which an evaluation counts toward
    # descending one rung; must sit strictly below enter_burn
    exit_burn: float = 0.25
    # consecutive armed evaluations before a rung change
    enter_evals: int = 2
    exit_evals: int = 2
    # rung 2+: max_new_tokens cap applied to NEW admissions
    max_new_tokens_cap: int = 8
    # scheduler steps between evaluations
    interval: int = 8

    def _validate(self):
        if self.enter_burn <= 0:
            raise ConfigError(
                f"degraded.enter_burn must be > 0, got {self.enter_burn}")
        if not 0 <= self.exit_burn < self.enter_burn:
            raise ConfigError(
                "degraded.exit_burn must sit in [0, enter_burn) — the "
                f"hysteresis dead band — got {self.exit_burn} vs "
                f"enter_burn={self.enter_burn}")
        for field in ("enter_evals", "exit_evals", "interval"):
            if getattr(self, field) < 1:
                raise ConfigError(
                    f"degraded.{field} must be >= 1, got "
                    f"{getattr(self, field)}")
        if self.max_new_tokens_cap < 1:
            raise ConfigError(
                f"degraded.max_new_tokens_cap must be >= 1, got "
                f"{self.max_new_tokens_cap}")


class ServingConfig(ConfigModel):
    """Continuous-batching serving (Orca-style slot scheduler over ONE jitted
    decode program; DeepSpeed-Inference's serving-side batching layer,
    TPU-native). Consumed by ``serving/engine.py`` via the inference config's
    ``serving`` block."""

    # fixed decode batch-slot pool: static shapes, compiled once; finished
    # requests free their slot mid-flight and queued ones are spliced in
    n_slots: int = 8
    # per-slot KV window (prompt + generation); 0 = inference max_tokens
    max_len: int = 0
    # admission control: requests beyond this queue depth are shed with a
    # reason instead of growing until OOM
    max_queue_depth: int = 64
    # prefill/decode interleaving: at most this many prefills per scheduler
    # step, so a burst of arrivals can't starve running decodes (TPOT)
    max_prefills_per_step: int = 1
    # admission policy: "fcfs" (strict arrival order + bounded HOL bypass)
    # or "weighted_fair" (start-time fair queuing across tenants with
    # per-tenant token budgets; serving.tenants configures the classes)
    policy: str = "fcfs"
    # deterministic virtual-clock mode (tests/simulation): scheduler time
    # advances by the cost model below instead of the wall clock
    virtual_clock: bool = False
    virtual_decode_step_cost: float = 1.0
    virtual_prefill_cost_per_token: float = 0.0625  # ~flash prefill vs decode
    # zero freed KV memory when a request finishes (the causal mask and
    # whole-block insert already prevent stale-KV leaks; hygiene/debug
    # knob): each physical block is zeroed as its refcount hits zero.
    scrub_freed_slots: bool = False
    # emit Serving/* monitor events every N scheduler steps (0 disables)
    monitor_interval: int = 32
    # the KV store: block pool geometry, int8 blocks, shared-prefix reuse
    kv_pool: KVPoolConfig = None
    # chunked prefill: interleave fixed-token prefill chunks with decode
    # steps for a bounded co-batched TPOT (chunked_prefill.enabled)
    chunked_prefill: ChunkedPrefillConfig = None
    # multi-replica router policy (serving/router.py reads this block off
    # its first replica's config unless given one explicitly)
    router: RouterConfig = None
    # latency SLO targets graded against the streaming digests (per replica
    # and fleet-aggregated); 0 targets = no objective
    slo: SLOConfig = None
    # head-of-line bypass under block-aware admission: when the queue head's
    # KV footprint cannot fit, up to this many later requests that DO fit may
    # be admitted past it before admissions stop until the head clears
    # (bounded starvation). 0 = strict FCFS, nothing ever overtakes the head.
    hol_bypass_limit: int = 0
    # speculative decoding: drafter + one-forward verify + rollback-safe
    # greedy acceptance over the paged pool (speculative.enabled)
    speculative: SpeculativeConfig = None
    # live KV migration: portable request snapshots spliced between
    # replicas (failover, drain-by-migration, cross-replica retry)
    migration: MigrationConfig = None
    # disaggregated prefill/decode pools over the Router's replicas
    # (pools.enabled): prefill replicas hand streams off at first-token
    # time through the migration machinery
    pools: PoolsConfig = None
    # live decode rebalancing: hysteresis-guarded migration of long-tail
    # streams off hot replicas (rebalance.enabled)
    rebalance: RebalanceConfig = None
    # SLO-driven replica autoscaling over the Router's fleet
    # (autoscaler.enabled): drain/rejoin actuation on windowed burn rate
    autoscaler: AutoscalerConfig = None
    # tenant/priority classes: weighted-fair admission shares, per-tenant
    # token budgets, priority preemption (tenants.enabled)
    tenants: TenantsConfig = None
    # degraded-mode ladder under SLO burn: shed batch -> cap tokens ->
    # drop speculation -> shed interactive, hysteresis-guarded
    degraded: DegradedConfig = None
    # cross-replica retry budget: a request that hits a recoverable
    # per-replica failure (unhealthy_slot, replica crash) is re-dispatched
    # to a different replica up to this many times before the terminal shed
    retry_limit: int = 1

    def _validate(self):
        if self.kv_pool is None:
            self.kv_pool = KVPoolConfig()
        if self.chunked_prefill is None:
            self.chunked_prefill = ChunkedPrefillConfig()
        if self.router is None:
            self.router = RouterConfig()
        if self.slo is None:
            self.slo = SLOConfig()
        if self.speculative is None:
            self.speculative = SpeculativeConfig()
        if self.migration is None:
            self.migration = MigrationConfig()
        if self.pools is None:
            self.pools = PoolsConfig()
        if self.rebalance is None:
            self.rebalance = RebalanceConfig()
        if self.autoscaler is None:
            self.autoscaler = AutoscalerConfig()
        if self.tenants is None:
            self.tenants = TenantsConfig()
        if self.degraded is None:
            self.degraded = DegradedConfig()
        if self.pools.enabled and not self.migration.enabled:
            raise ConfigError(
                "serving.pools.enabled requires serving.migration.enabled: "
                "the first-token handoff IS a live migration")
        if self.retry_limit < 0:
            raise ConfigError(
                f"serving.retry_limit must be >= 0, got {self.retry_limit}")
        if self.hol_bypass_limit < 0:
            raise ConfigError(
                f"serving.hol_bypass_limit must be >= 0, got "
                f"{self.hol_bypass_limit}")
        if self.n_slots < 1:
            raise ConfigError(f"serving.n_slots must be >= 1, got {self.n_slots}")
        if self.max_queue_depth < 1:
            raise ConfigError(
                f"serving.max_queue_depth must be >= 1, got {self.max_queue_depth}")
        if self.policy not in ("fcfs", "weighted_fair"):
            raise ConfigError(
                f"serving.policy must be 'fcfs' or 'weighted_fair', got "
                f"{self.policy!r}")
        if self.max_prefills_per_step < 1:
            raise ConfigError("serving.max_prefills_per_step must be >= 1")
        if self.autoscaler.enabled and not self.slo.armed \
                and self.autoscaler.scale_up_queue_depth <= 0:
            raise ConfigError(
                "serving.autoscaler.enabled needs a sensor: set a "
                "serving.slo target (burn-rate trigger) and/or "
                "autoscaler.scale_up_queue_depth (queue trigger)")
        if self.degraded.enabled and not self.slo.armed:
            raise ConfigError(
                "serving.degraded.enabled requires a serving.slo target: "
                "the ladder's only input is the windowed SLO burn rate")


class TelemetryConfig(ConfigModel):
    """Span-based step tracing (``telemetry/tracer.py``): nested host spans
    over the engine's step phases (data/fwd/bwd/step/checkpoint), serving
    request lifecycles, and checkpoint save/resume, emitted as Chrome-trace
    JSON (Perfetto-loadable) + structured JSONL under
    ``<output_path>/<job_name>/``. ``device_sync`` fences span ends (and the
    wall-clock timers) with ``block_until_ready`` so timings measure device
    execution rather than dispatch."""

    enabled: bool = False
    output_path: str = ""  # trace dir root; "" -> ./traces
    job_name: str = "DeepSpeedJobName"
    # fence sync=True spans + the fwd/bwd/step timers on the device
    device_sync: bool = False
    chrome_trace: bool = True  # write trace.json (chrome://tracing/Perfetto)
    jsonl: bool = True         # write spans.jsonl (tools/trace_summary.py)
    # in-memory event cap; past it new events are dropped (and counted)
    max_events: int = 100_000

    def _validate(self):
        if self.max_events < 1:
            raise ConfigError(
                f"telemetry.max_events must be >= 1, got {self.max_events}")


class HealthConfig(ConfigModel):
    """Numerics flight recorder (``telemetry/health.py``): per-param-group
    health stats computed inside the jitted step (always traced as a small
    side output), a host-side ring buffer + anomaly watchdog (this block
    arms it), and atomically-committed black-box dumps on detector fire /
    SIGTERM / unhandled train_batch exceptions. Detector actions:
    ``off | warn | skip_step | dump | halt`` — ``skip_step`` is realized
    in-graph (the fp16 overflow-skip generalized to any-dtype non-finite
    grads) and only applies to the nonfinite detector; ``halt`` dumps and
    raises ``HealthHalted``. On the serving side, ``enabled`` arms the
    nonfinite-logit watchdog (``Serving/health_*`` events + the
    ``unhealthy_slot`` shed)."""

    enabled: bool = False
    # ring buffer length (steps kept for the black-box dump) and the
    # observe cadence (1 = every step; observing syncs the step's stats)
    window: int = 256
    check_interval: int = 1
    # write Health/* scalar events through the monitor fan-out per observe
    emit_events: bool = True
    # detector: any non-finite grad/param element, naming the param group
    nonfinite_action: str = "dump"
    # detector: z-score spike of loss / grad_norm over a trailing window
    spike_zscore: float = 6.0
    spike_window: int = 32
    spike_min_steps: int = 8
    spike_action: str = "warn"
    # detector: per-group update/param ratio ceiling (0 disables)
    update_ratio_max: float = 0.0
    update_ratio_action: str = "warn"
    # black-box dump root ("" -> ./health_dumps), dump triggers, and the
    # per-run dump cap (a flapping detector must not fill the disk)
    dump_dir: str = ""
    max_dumps: int = 8
    dump_on_exception: bool = True
    dump_on_signal: bool = True

    def _validate(self):
        from ..telemetry.health import ACTIONS

        for field in ("nonfinite_action", "spike_action",
                      "update_ratio_action"):
            v = getattr(self, field)
            if v not in ACTIONS:
                raise ConfigError(
                    f"health.{field} must be one of {'|'.join(ACTIONS)}, "
                    f"got {v!r}")
        if self.window < 8:
            raise ConfigError(
                f"health.window must be >= 8 (detectors need history), "
                f"got {self.window}")
        if self.check_interval < 1:
            raise ConfigError(
                f"health.check_interval must be >= 1, got "
                f"{self.check_interval}")
        if self.spike_window < 1 or self.spike_min_steps < 1:
            raise ConfigError(
                f"health.spike_window and health.spike_min_steps must be "
                f">= 1, got {self.spike_window}/{self.spike_min_steps}")
        if self.max_dumps < 1:
            raise ConfigError(
                f"health.max_dumps must be >= 1, got {self.max_dumps}")


class ElasticConfig(ConfigModel):
    """Preemption-native elastic training (``checkpoint/snapshot.py`` +
    ``elasticity/agent.py``). ``enabled`` arms overlapped snapshots: the
    agent keeps a double-buffered host shadow of the full step state,
    captured every ``snapshot_interval`` steps (async device-to-host issue,
    no file I/O on the step path) and drained to a published sharded tag by
    a background writer. On SIGTERM the flush commits the freshest
    already-staged shadow — bounded by one snapshot write, never a
    from-scratch save — so a preemption loses at most ``snapshot_interval``
    steps. The grace budgeter measures real write+fsync time per snapshot
    and warns (once per run) when ``flush_time * safety_factor`` no longer
    fits ``grace_period_s``, stretching the cadence within
    ``[snapshot_interval, max_interval]`` when the writer can't keep up."""

    enabled: bool = False
    # steps between shadow captures (the max steps a preemption can lose)
    snapshot_interval: int = 1
    # the preemption grace window the SIGTERM flush must fit (seconds)
    grace_period_s: float = 30.0
    # flush must fit grace_period_s / safety_factor before the budgeter warns
    safety_factor: float = 2.0
    # cadence ceiling when the budgeter stretches a too-slow writer
    max_interval: int = 64
    # keep the newest N snapshot tags (retention; None = keep everything)
    keep_last: typing.Optional[int] = 4

    def _validate(self):
        if self.snapshot_interval < 1:
            raise ConfigError(
                f"elastic.snapshot_interval must be >= 1, got "
                f"{self.snapshot_interval}")
        if self.max_interval < self.snapshot_interval:
            raise ConfigError(
                f"elastic.max_interval must be >= snapshot_interval "
                f"({self.snapshot_interval}), got {self.max_interval}")
        if self.grace_period_s <= 0:
            raise ConfigError(
                f"elastic.grace_period_s must be > 0, got "
                f"{self.grace_period_s}")
        if self.safety_factor < 1.0:
            raise ConfigError(
                f"elastic.safety_factor must be >= 1.0, got "
                f"{self.safety_factor}")
        if self.keep_last is not None and self.keep_last < 1:
            raise ConfigError(
                f"elastic.keep_last must be >= 1 or null, got "
                f"{self.keep_last}")


class FlopsProfilerConfig(ConfigModel):
    """Reference: ``profiling/config.py``."""

    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: str = ""


class DataTypesConfig(ConfigModel):
    grad_accum_dtype: typing.Optional[str] = None


class GradientCompressionConfig(ConfigModel):
    """Quantized-collective slot (reference's 1-bit Adam / compressed allreduce,
    ``runtime/comm/nccl.py:54``; cf. EQuARX for the XLA analogue)."""

    enabled: bool = False
    bits: int = 8


class CurriculumConfig(ConfigModel):
    """Curriculum learning (reference legacy top-level ``curriculum_learning``
    section, consumed by the engine at ``engine.py:1675`` for seqlen
    scheduling). Scheduler keys pass through to ``CurriculumScheduler``."""

    enabled: bool = False
    curriculum_type: str = "seqlen"
    min_difficulty: int = 8
    max_difficulty: int = 1024
    schedule_type: str = "fixed_linear"
    schedule_config: dict = {}


class ProgressiveLayerDropConfig(ConfigModel):
    """Reference ``progressive_layer_drop`` section (``engine.py:680``,
    ``runtime/progressive_layer_drop.py``): stochastic depth with the
    theta(t) = (1-theta_bar) exp(-gamma t) + theta_bar keep schedule."""

    enabled: bool = False
    theta: float = 0.5
    gamma: float = 0.001


class DeepSpeedConfig(ConfigModel):
    """Top-level config (reference ``runtime/config.py:674``)."""

    train_batch_size: typing.Optional[int] = None
    train_micro_batch_size_per_gpu: typing.Optional[int] = None
    gradient_accumulation_steps: typing.Optional[int] = None
    steps_per_print: int = 10
    optimizer: OptimizerConfig = OptimizerConfig
    scheduler: SchedulerConfig = SchedulerConfig
    fp16: FP16Config = FP16Config
    bf16: BF16Config = BF16Config
    zero_optimization: ZeroConfig = ZeroConfig
    zero_allow_untested_optimizer: bool = False
    gradient_clipping: float = 0.0
    prescale_gradients: bool = False
    gradient_predivide_factor: float = 1.0
    sparse_gradients: bool = False
    activation_checkpointing: ActivationCheckpointingConfig = ActivationCheckpointingConfig
    mesh: MeshConfig = MeshConfig
    pipeline: PipelineConfig = PipelineConfig
    checkpoint: CheckpointConfig = CheckpointConfig
    hybrid_engine: HybridEngineConfig = HybridEngineConfig
    tensorboard: TensorBoardConfig = TensorBoardConfig
    wandb: WandbConfig = WandbConfig
    csv_monitor: CSVConfig = CSVConfig
    telemetry: TelemetryConfig = TelemetryConfig
    health: HealthConfig = HealthConfig
    elastic: ElasticConfig = ElasticConfig
    comms_logger: CommsLoggerConfig = CommsLoggerConfig
    flops_profiler: FlopsProfilerConfig = FlopsProfilerConfig
    data_types: DataTypesConfig = DataTypesConfig
    curriculum_learning: CurriculumConfig = CurriculumConfig
    progressive_layer_drop: ProgressiveLayerDropConfig = ProgressiveLayerDropConfig
    gradient_compression: GradientCompressionConfig = GradientCompressionConfig
    # compression-in-training (reference compression_training section,
    # deepspeed/compression/config.py): parsed by compression.init_compression
    # — kept as a raw dict here to avoid a config<->compression import cycle
    compression_training: dict = {}
    communication_data_type: typing.Optional[str] = None
    wall_clock_breakdown: bool = False
    memory_breakdown: bool = False
    dump_state: bool = False
    gradient_checkpointing: bool = False
    seed: int = 1234

    deprecated_fields = {"train_micro_batch_size": "train_micro_batch_size_per_gpu"}

    # -- batch triangle -------------------------------------------------------------
    def resolve_batch_size(self, dp_world_size):
        """Resolve/validate the batch-size triangle against ``dp_world_size``.

        Mirrors the reference's ``DeepSpeedConfig._configure_train_batch_size``
        (``runtime/config.py``): given any subset of {train_batch_size,
        train_micro_batch_size_per_gpu, gradient_accumulation_steps}, infer the rest,
        and check ``train = micro * grad_accum * dp_world``.
        """
        tbs = self.train_batch_size
        micro = self.train_micro_batch_size_per_gpu
        gas = self.gradient_accumulation_steps
        for name, v in (("train_batch_size", tbs),
                        ("train_micro_batch_size_per_gpu", micro),
                        ("gradient_accumulation_steps", gas),
                        ("dp_world_size", dp_world_size)):
            if v is not None and v <= 0:
                raise ConfigError(f"{name} must be positive, got {v}")

        if tbs is not None and micro is not None and gas is None:
            gas, rem = divmod(tbs, micro * dp_world_size)
            if rem:
                raise ConfigError(
                    f"train_batch_size {tbs} is not divisible by "
                    f"micro_batch {micro} * dp_world {dp_world_size}"
                )
        elif tbs is not None and micro is None and gas is not None:
            micro, rem = divmod(tbs, gas * dp_world_size)
            if rem:
                raise ConfigError(
                    f"train_batch_size {tbs} is not divisible by "
                    f"grad_accum {gas} * dp_world {dp_world_size}"
                )
        elif tbs is not None and micro is None and gas is None:
            gas = 1
            micro, rem = divmod(tbs, dp_world_size)
            if rem:
                raise ConfigError(
                    f"train_batch_size {tbs} is not divisible by dp_world {dp_world_size}"
                )
        elif tbs is None and micro is not None:
            gas = gas or 1
            tbs = micro * gas * dp_world_size
        elif tbs is None and micro is None:
            raise ConfigError(
                "At least train_batch_size or train_micro_batch_size_per_gpu must be set"
            )

        if tbs != micro * gas * dp_world_size:
            raise ConfigError(
                f"Batch-size triangle violated: train_batch_size ({tbs}) != "
                f"micro ({micro}) * grad_accum ({gas}) * dp_world ({dp_world_size})"
            )
        if tbs <= 0 or micro <= 0 or gas <= 0:
            raise ConfigError("Batch sizes must be positive")

        self.train_batch_size = tbs
        self.train_micro_batch_size_per_gpu = micro
        self.gradient_accumulation_steps = gas
        return tbs, micro, gas

    def _validate(self):
        if self.fp16.enabled and self.bf16.enabled:
            raise ConfigError("fp16 and bf16 cannot both be enabled")

    @property
    def mixed_precision_dtype(self):
        if self.fp16.enabled:
            return "float16"
        if self.bf16.enabled:
            return "bfloat16"
        return "float32"


def load_config(config) -> DeepSpeedConfig:
    """Accept a path to a JSON file or an in-memory dict (reference accepts both;
    ``deepspeed/__init__.py:54`` ``config`` / ``config_params``)."""
    if isinstance(config, DeepSpeedConfig):
        return config
    if isinstance(config, str):
        if not os.path.exists(config):
            raise ConfigError(f"DeepSpeed config file not found: {config}")
        with open(config) as f:
            config = json.load(f)
    if not isinstance(config, dict):
        raise ConfigError(f"config must be a dict or JSON path, got {type(config)}")
    return DeepSpeedConfig.from_dict(config)
