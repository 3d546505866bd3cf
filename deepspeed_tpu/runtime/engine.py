"""The training engine.

TPU-native equivalent of the reference's ``DeepSpeedEngine`` (``runtime/engine.py:183``):
a config-driven wrapper exposing ``forward`` / ``backward`` / ``step`` /
``save_checkpoint`` / ``load_checkpoint`` plus a fused ``train_batch``. The torch
version orchestrates hooks, buckets, and streams at runtime; here the whole training
step is a handful of jitted XLA programs whose sharding specs realize the configured
parallelism (see ``parallel/sharding.py`` for the ZeRO-stage -> spec mapping):

- params: fp32 master copies (reference keeps the same fp32 master in
  ``fp16/fused_optimizer.py``), sharded per ZeRO-3 / TP, donated through the step
- compute: bf16/fp16 cast at apply time (``fp16``/``bf16`` config sections)
- grads: accumulated in a persistent buffer sharded per ZeRO-2
- optimizer state: sharded per ZeRO-1
- fp16: dynamic loss scaling with in-program overflow check and step skip
  (reference ``runtime/fp16/loss_scaler.py`` + ``CheckOverflow``)

Init sequence mirrors the reference (``engine.py:186-380``): dist init -> config
parse -> mesh ("distributed model") -> optimizer -> lr scheduler -> checkpointing.
Parameter init happens *sharded*: ``model.init`` runs under jit with the ZeRO specs
as out_shardings, so a 13B model never materializes unsharded — the reference needs
the ``zero.Init`` monkey-patch context (``partition_parameters.py:601``) for this.
"""

import os

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import comm as dist
from ..config import load_config, ConfigError
from ..models.layers import Param, split_params_axes
from ..ops import (
    get_optimizer,
    get_lr_schedule,
    make_scaler_state,
    check_overflow,
    update_scale,
    clip_grads_by_global_norm,
    global_grad_norm,
)
from ..parallel import build_mesh, DATA_AXIS, EXPERT_AXIS, PIPE_AXIS
from ..parallel.sharding import (
    param_partition_specs,
    state_partition_specs,
    batch_partition_specs,
    named,
)
from ..utils.logging import log_dist, logger
from ..utils.timer import (
    SynchronizedWallClockTimer,
    ThroughputTimer,
    FORWARD_GLOBAL_TIMER,
    BACKWARD_GLOBAL_TIMER,
    STEP_GLOBAL_TIMER,
)
from .dataloader import DeepSpeedDataLoader

DTYPES = {"float16": jnp.float16, "bfloat16": jnp.bfloat16, "float32": jnp.float32}

# Compile-only construction switch (see abstract_init below).
_ABSTRACT_INIT = False


class abstract_init:
    """Context manager: engines constructed inside build with ABSTRACT params.

    ``self.params`` / ``self.optimizer_state`` become ``jax.ShapeDtypeStruct``
    trees carrying the real shardings instead of device buffers, so the engine
    can ``lower()``/``compile()`` its train step — AOT memory analysis, HLO
    inspection, collective-volume accounting — without a single byte of model
    state existing anywhere. This is the planning role the reference autotuner
    fills with model-info estimation (``autotuning/autotuner.py``
    ``_get_model_info``), made exact: the numbers come from the real compiled
    program, not a formula. ``tools/scale_projection.py`` uses it to plan
    OPT-13B ZeRO-3 on a 256-chip mesh from a CPU host (materializing the fp32
    master would need ~156 GB of host RAM).

    Execution APIs (``train_batch`` etc.) are unusable on such an engine.
    """

    def __enter__(self):
        global _ABSTRACT_INIT
        self._prev = _ABSTRACT_INIT
        _ABSTRACT_INIT = True
        return self

    def __exit__(self, *exc):
        global _ABSTRACT_INIT
        _ABSTRACT_INIT = self._prev
        return False


def _abstract_tree(shape_tree, shardings):
    return jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shape_tree, shardings)


class DeepSpeedEngine:
    def __init__(self, model, optimizer=None, model_parameters=None, training_data=None,
                 lr_scheduler=None, mesh=None, collate_fn=None, config=None):
        if model is None:
            raise ConfigError("deepspeed_tpu.initialize: model is required")
        self.module = model
        self.client_optimizer = optimizer
        self._config = load_config(config)

        # -- mesh (the reference's _configure_distributed_model + process groups) ---
        self.mesh = mesh if mesh is not None else build_mesh(self._config.mesh)
        self.dp_world_size = self.mesh.shape[DATA_AXIS] * self.mesh.shape.get(EXPERT_AXIS, 1)
        self.mp_world_size = self.mesh.shape.get("model", 1)

        # -- batch triangle ----------------------------------------------------------
        (self.train_batch_size_, self.micro_batch_size,
         self.gradient_accumulation_steps_) = self._config.resolve_batch_size(self.dp_world_size)

        # -- precision ---------------------------------------------------------------
        self.compute_dtype = DTYPES[self._config.mixed_precision_dtype]
        if hasattr(self.module, "config") and hasattr(self.module.config, "compute_dtype"):
            self.module.config.compute_dtype = self.compute_dtype
        if self._config.gradient_checkpointing and hasattr(self.module, "config") \
                and hasattr(self.module.config, "remat"):
            self.module.config.remat = True
        self.fp16_enabled = self._config.fp16.enabled

        self.zero_stage = self._config.zero_optimization.stage
        self._persist_threshold = self._config.zero_optimization.param_persistence_threshold
        # bf16 gradient reduction wire (reduce-scatter at stage >= 2,
        # all-reduce below): grads are cast BEFORE the sharding constraint so
        # the collective moves 16-bit payloads; accumulation across
        # micro-batches then also runs at the wire dtype. fp32 = exact.
        self._grad_wire_dtype = jnp.bfloat16 \
            if self._config.zero_optimization.grad_reduce_dtype == "bf16" \
            else None
        # validated regardless of gather mode: a typo'd knob must fail at
        # construction, not lie dormant until per_layer is enabled
        if self._config.zero_optimization.zero3_gather_impl not in (
                "constraint", "shard_map"):
            raise ConfigError(
                f"zero3_gather_impl must be 'constraint' or 'shard_map', got "
                f"{self._config.zero_optimization.zero3_gather_impl!r}")

        # -- pipeline parallelism ----------------------------------------------------
        # With pipe > 1 the whole accumulation window runs as ONE compiled GPipe
        # sweep (parallel/pipeline.py): pipeline microbatches = the configured
        # gradient_accumulation_steps (the reference folds grad-accum into the 1F1B
        # schedule the same way, pipe/engine.py:285 train_batch).
        self.pipe_stages = self.mesh.shape.get(PIPE_AXIS, 1)
        self._pipe_microbatches = 1

        # -- sequence parallelism (ring attention over the seq axis) -----------------
        self.seq_parallel_size = self.mesh.shape.get("seq", 1)
        if self.seq_parallel_size > 1:
            if not (hasattr(self.module, "config")
                    and hasattr(self.module.config, "sequence_parallel")):
                raise ConfigError(
                    "sequence parallelism (mesh seq > 1) requires a model whose "
                    "config supports sequence_parallel (the transformer backbone)"
                )
            self.module.config.sequence_parallel = True
        if self.pipe_stages > 1:
            if not (hasattr(self.module, "config")
                    and hasattr(self.module.config, "pipeline_stages")):
                raise ConfigError(
                    "pipeline parallelism (mesh pipe > 1) requires a model whose "
                    "config supports pipeline_stages (the transformer backbone)"
                )
            self._pipe_microbatches = self.gradient_accumulation_steps_
            self.gradient_accumulation_steps_ = 1
            self.module.config.pipeline_stages = self.pipe_stages
            self.module.config.pipeline_microbatches = self._pipe_microbatches
        # Hand the mesh to the model whenever its config can carry it: ring
        # attention (seq), the pipeline loop (pipe), and the MoE dispatch
        # constraints (expert; moe/sharded_moe.py _expert_a2a) all need it.
        if hasattr(self.module, "config") and hasattr(self.module.config, "mesh"):
            self.module.config.mesh = self.mesh
        elif self.mesh.shape.get(EXPERT_AXIS, 1) > 1:
            logger.warning(
                "mesh has expert>1 but the model config has no `mesh` field: MoE "
                "dispatch cannot be constrained to all_to_all and will compile "
                "to a degraded replicated layout")
        if self.mp_world_size > 1 and hasattr(self.module, "config") \
                and getattr(self.module.config, "fused_qkv", False):
            # the SPMD partitioner miscompiles jnp.concatenate along an axis
            # the operands are sharded on (verified wrong bytes on jaxlib
            # 0.4.x), which is exactly the fused-qkv concat under a >1 model
            # axis; the unfused projections are the Megatron column-parallel
            # form and bitwise-identical per output column
            self.module.config.fused_qkv = False
            log_dist("tensor parallelism: fused qkv disabled (sharded-concat "
                     "SPMD hazard); using per-projection matmuls", ranks=[0])

        # -- compression-in-training (reference compression_training section) --------
        self._compression = None
        self._compression_phase = None
        self._compression_step = 0
        if self._config.compression_training:
            # reject incompatible configs BEFORE touching the module config —
            # a caught ConfigError must leave the model reusable
            if self.pipe_stages > 1:
                raise ConfigError(
                    "compression_training does not compose with pipeline "
                    "parallelism (apply compression manually via "
                    "deepspeed_tpu.compression on pipe meshes)")
            if dict(self._config.compression_training).get(
                    "layer_reduction", {}).get("enabled"):
                raise ConfigError(
                    "compression_training.layer_reduction is a deploy-time "
                    "transform (redundancy_clean slices the layer stack); it "
                    "cannot run inside training — train the full depth, then "
                    "clean, or build the student model directly")
            if self._config.gradient_compression.enabled or \
                    self._config.optimizer.type.lower().replace("-", "").replace("_", "") \
                    in ("onebitadam", "zerooneadam", "onebitlamb"):
                raise ConfigError(
                    "compression_training does not compose with 1-bit/"
                    "compressed-gradient optimizers (their train path would "
                    "silently skip the quantization/pruning masks)")
            from ..compression import apply_to_model_config, init_compression

            if hasattr(self.module, "config"):
                # activation quantization is a model-config knob (QuantAct role)
                self.module.config = apply_to_model_config(
                    self.module.config, self._config.compression_training)
            self._compression = init_compression(
                self._config.compression_training,
                model_config=getattr(self.module, "config", None))

        # -- parameters (sharded at init = zero.Init) --------------------------------
        self._rng = jax.random.PRNGKey(self._config.seed)
        self._init_parameters(model_parameters)

        # -- optimizer ---------------------------------------------------------------
        self._configure_optimizer()

        if self._compression is not None and self._onebit_active:
            # authoritative guard: a client-PASSED 1-bit optimizer instance
            # bypasses the config-string check above, and the 1-bit train
            # path would silently skip the compression masks
            raise ConfigError(
                "compression_training does not compose with 1-bit/"
                "compressed-gradient optimizers (their train path would "
                "silently skip the quantization/pruning masks)")

        # -- lr scheduler ------------------------------------------------------------
        self.lr_scheduler = lr_scheduler
        if self.lr_scheduler is None and self._config.scheduler.type:
            self.lr_scheduler = get_lr_schedule(
                self._config.scheduler.type, self._config.scheduler.params
            )

        # -- fp16 loss scaler --------------------------------------------------------
        fp16 = self._config.fp16
        self._scaler_meta = make_scaler_state(
            static_scale=fp16.loss_scale,
            initial_scale_power=fp16.initial_scale_power,
            min_scale=fp16.min_loss_scale,
        ) if self.fp16_enabled else None
        if self.fp16_enabled:
            self._scale = self._scaler_meta["scale"]
            self._good_steps = self._scaler_meta["good_steps"]
        else:
            self._scale = jnp.asarray(1.0, jnp.float32)
            self._good_steps = jnp.zeros((), jnp.int32)

        # -- grad accumulation buffer (ZeRO-2 sharded) -------------------------------
        self._grad_specs = state_partition_specs(
            self._axes, self._shapes, self.mesh,
            zero_stage=self.zero_stage if self.zero_stage >= 2 else 0,
            min_data_shard_elems=self._persist_threshold if self.zero_stage >= 2 else 2 ** 62,
        )
        self._grad_shardings = named(self.mesh, self._grad_specs)
        self._acc_grads = None
        self._cached = None  # (loss, grads) from the last forward

        # -- counters / timers / monitor / telemetry ---------------------------------
        self.global_steps = 0
        self.micro_steps = 0
        self.skipped_steps = 0
        self._last_resume_rescaled = False  # set by load_checkpoint
        tel = self._config.telemetry
        from ..telemetry import SpanTracer

        # device_sync arms a block_until_ready fence on BOTH the span ends
        # and the fwd/bwd/step timers: unsynced host timers measure dispatch
        # (jax's async enqueue), not execution
        self._telemetry_sync = bool(tel.enabled and tel.device_sync)
        sync_fn = self._device_fence if tel.device_sync else None
        self.tracer = SpanTracer.from_config(
            tel, sync_fn=self._device_fence,
            meta={"process": "train", "mesh": dict(self.mesh.shape),
                  "zero_stage": self.zero_stage})
        self.timers = SynchronizedWallClockTimer(sync_fn=sync_fn)
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_batch_size_, steps_per_output=self._config.steps_per_print,
            sync_fn=sync_fn,
        )
        self._wall_clock_breakdown = self._config.wall_clock_breakdown
        from ..monitor.monitor import MonitorMaster

        self.monitor = MonitorMaster(self._config)

        # -- numerics flight recorder (telemetry/health.py) ----------------------
        # Group definitions derive from the param pytree; the in-graph stats
        # are ALWAYS a side output of the compiled step (so the sanitizer/
        # budget gates audit the real program), but the host-side monitor
        # only reads them — one sync per observed step — when enabled.
        from ..telemetry.health import HealthMonitor, derive_group_names

        self._health_groups = derive_group_names(
            self._shapes, is_leaf=lambda x: isinstance(x, tuple))
        self.health = HealthMonitor(
            self._config.health, self._health_groups, monitor=self.monitor,
            meta={"process": "train", "mesh": dict(self.mesh.shape),
                  "zero_stage": self.zero_stage})
        # skip_step is the one action realized IN-GRAPH: generalize the fp16
        # overflow-skip to any-dtype non-finite grads (pre-update, so the
        # poisoned step never touches params/optimizer state)
        self._health_skip = bool(
            self._config.health.enabled
            and self._config.health.nonfinite_action == "skip_step")
        self._health_fn = None  # lazy jitted stats for the offloaded path
        self._health_rng = None  # key that SEEDED the current step's window

        # -- explicit ZeRO-3 gather schedule (per-layer constraint in the scan) ------
        if (self.zero_stage >= 3
                and self._config.zero_optimization.zero3_gather_mode == "per_layer"
                and hasattr(self.module, "config")
                and hasattr(self.module.config, "zero3_per_layer_gather")
                and isinstance(self.param_specs, dict)
                and "blocks" in self.param_specs):
            gather_specs = jax.tree_util.tree_map(
                lambda s: P(*(None if a == DATA_AXIS else a
                              for a in tuple(s)[1:])),
                self.param_specs["blocks"],
                is_leaf=lambda x: isinstance(x, P))
            self.module.config.zero3_per_layer_gather = True
            self.module.config.zero3_gather_specs = gather_specs
            impl, wire = self._resolve_gather_wire()
            if impl == "shard_map":
                if not hasattr(self.module.config, "zero3_sharded_specs"):
                    # refuse rather than silently run fp32-sized gather wire
                    # while the operator believes the bf16/int8 path is active
                    raise ConfigError(
                        "zero3_gather_impl: 'shard_map' requires a model "
                        "config with a zero3_sharded_specs field (the "
                        "transformer backbone); this module only supports "
                        "the 'constraint' impl")
                self.module.config.zero3_gather_impl = "shard_map"
                if hasattr(self.module.config, "zero3_gather_dtype"):
                    self.module.config.zero3_gather_dtype = wire
                    self.module.config.zero3_gather_block = \
                        self._config.zero_optimization.zero3_gather_block
                elif wire != "compute":
                    # "compute" is the field-less module's historical
                    # behavior; anything EXPLICIT (fp32 included — an
                    # exact-gather baseline silently running bf16 wire is
                    # precisely the mismatch this guard exists for) needs a
                    # config that can carry it
                    raise ConfigError(
                        f"zero3_gather_dtype={wire!r} requires a model config "
                        f"with a zero3_gather_dtype field (the transformer "
                        f"backbone); this module would silently gather at "
                        f"the compute dtype")
                # sharded specs minus the layers dim: the shard_map islands'
                # in_specs (the all_gather's input layout)
                self.module.config.zero3_sharded_specs = \
                    jax.tree_util.tree_map(
                        lambda s: P(*tuple(s)[1:]),
                        self.param_specs["blocks"],
                        is_leaf=lambda x: isinstance(x, P))
            # Top-level params (embedding / head / final norm) need a
            # gather-before-use constraint WHEN their ZeRO-3 shard landed on
            # the d_model ("embed") axis: that axis is the contraction dim of
            # the consuming matmul, and propagating it in makes the
            # partitioner partial-sum full-batch logits with giant
            # all-reduces instead of gathering the 100 MB weight (observed:
            # 8.6 TB/chip temps on the OPT-13B/256 projection, where
            # vocab % 256 != 0 forced logical_to_physical onto d_model).
            # A vocab-axis shard is LEFT ALONE — vocab-parallel CE is the
            # better program (each device computes its logits slice with a
            # full contraction; measured cheaper at dp=8 than gathering).
            # ZeRO-3 discipline either way: masters stay sharded.
            if hasattr(self.module.config, "zero3_toplevel_gather_specs"):
                def _strip_embed_axis(axes, spec):
                    # strip the data shard from every axis EXCEPT vocab: a
                    # vocab shard means vocab-parallel CE (keep); any other
                    # placement (embed, unnamed, seq_table) sits on a
                    # contraction/gather dim of the consumer and must be
                    # gathered before use
                    return P(*(None if (s == DATA_AXIS and a != "vocab")
                               else s
                               for a, s in zip(axes, tuple(spec))))

                is_axes = lambda x: isinstance(x, tuple) and all(
                    isinstance(a, (str, type(None))) for a in x)
                self.module.config.zero3_toplevel_gather_specs = {
                    k: jax.tree_util.tree_map(
                        _strip_embed_axis, self._axes[k], v,
                        is_leaf=is_axes)
                    for k, v in self.param_specs.items() if k != "blocks"}
            log_dist(f"ZeRO-3 gather mode: per_layer (explicit schedule, "
                     f"impl={impl}, wire={wire})", ranks=[0])

        # -- progressive layer drop (reference engine.py:680 PLD hook) ---------------
        self._pld = None
        pld_cfg = self._config.progressive_layer_drop
        if pld_cfg.enabled:
            import inspect

            supported = ("pld_theta"
                         in inspect.signature(self.module.loss).parameters)
            if not supported:
                logger.warning(
                    "progressive_layer_drop enabled but %s.loss has no "
                    "pld_theta parameter; PLD is OFF",
                    type(self.module).__name__)
            elif self._onebit_active or self._offloaded is not None \
                    or self.pipe_stages > 1:
                logger.warning(
                    "progressive_layer_drop only engages on the fused "
                    "train_batch path (not 1-bit/offload/pipeline); PLD is OFF")
            else:
                from .extras import ProgressiveLayerDrop

                self._pld = ProgressiveLayerDrop(theta=pld_cfg.theta,
                                                 gamma=pld_cfg.gamma)
                log_dist(
                    f"Progressive layer drop: theta_bar={pld_cfg.theta} "
                    f"gamma={pld_cfg.gamma}", ranks=[0])

        # -- curriculum learning (reference engine.py:1675 seqlen scheduling) --------
        self._curriculum = None
        cl = self._config.curriculum_learning
        if cl.enabled:
            from .data_pipeline import CurriculumScheduler

            self._curriculum = CurriculumScheduler({
                "curriculum_type": cl.curriculum_type,
                "min_difficulty": cl.min_difficulty,
                "max_difficulty": cl.max_difficulty,
                "schedule_type": cl.schedule_type,
                "schedule_config": dict(cl.schedule_config),
            })
            log_dist(
                f"Curriculum learning: {cl.curriculum_type} "
                f"{cl.min_difficulty}->{cl.max_difficulty} ({cl.schedule_type})",
                ranks=[0])

        # -- dataloader --------------------------------------------------------------
        self.training_dataloader = None
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data, collate_fn=collate_fn)

        # -- checkpointing -----------------------------------------------------------
        ckpt_cfg = self._config.checkpoint
        from ..utils.retry import RetryPolicy

        ckpt_retry = RetryPolicy(max_attempts=ckpt_cfg.retries,
                                 base_delay=ckpt_cfg.retry_backoff,
                                 retry_on=(OSError,))
        if ckpt_cfg.engine == "sharded":
            from ..checkpoint.sharded import (AsyncShardedCheckpointEngine,
                                              ShardedCheckpointEngine)

            self.checkpoint_engine = AsyncShardedCheckpointEngine(ckpt_retry) \
                if ckpt_cfg.async_save else ShardedCheckpointEngine(ckpt_retry)
        elif ckpt_cfg.async_save:
            from ..checkpoint.engine import AsyncCheckpointEngine

            self.checkpoint_engine = AsyncCheckpointEngine(ckpt_retry)
        else:
            from ..checkpoint.engine import NpzCheckpointEngine

            self.checkpoint_engine = NpzCheckpointEngine(ckpt_retry)

        # -- compiled functions (built lazily) ---------------------------------------
        self._fwd_bwd_fn = None
        self._accumulate_fn = None
        self._apply_fn = None
        self._train_step_fn = None
        self._eval_fn = None
        self._train_mode = True
        # per-step collective wire stats (comms_logger / collective_wire_stats)
        self._wire_stats = None
        self._last_batch_struct = None
        self._last_loss = None  # unfused path: forward()'s loss for health

        log_dist(
            f"DeepSpeedEngine: mesh={dict(self.mesh.shape)} zero_stage={self.zero_stage} "
            f"dtype={self._config.mixed_precision_dtype} "
            f"batch(total={self.train_batch_size_}, micro={self.micro_batch_size}, "
            f"gas={self.gradient_accumulation_steps_})",
            ranks=[0],
        )
        if self._config.dump_state:
            # reference engine.py dump_state: print the resolved config
            import json as _json

            log_dist("config state:\n" + _json.dumps(
                self._config.to_dict(), indent=2, default=str), ranks=[0])

    # ------------------------------------------------------------------------------
    # init helpers
    # ------------------------------------------------------------------------------
    def _device_fence(self):
        """Zero-arg device fence for spans/timers (``telemetry.device_sync``):
        block on the freshest step output — the cached (loss, grads) right
        after a forward, else the live params the step just rewrote. Abstract
        engines (ShapeDtypeStruct trees) have nothing to block on; the guard
        keeps tracing from ever taking a step down."""
        try:
            jax.block_until_ready(
                self._cached if self._cached is not None else self.params)
        except Exception:
            pass

    def _resolve_gather_wire(self):
        """``zero3_gather_dtype`` -> (impl, wire-dtype name for the model).

        bf16/int8 wires imply the shard_map impl — a constraint chain cannot
        pin the wire dtype (the partitioner reshards an elementwise op's
        input to match its constrained output; PERF.md "known 2x"). "bf16"
        means "the 16-bit compute dtype": under fp16 training the wire is
        fp16. Masters stay sharded fp32 in every mode.
        """
        z = self._config.zero_optimization
        impl, gdtype = z.zero3_gather_impl, z.zero3_gather_dtype
        if gdtype in ("bf16", "int8") and impl != "shard_map":
            log_dist(
                f"zero3_gather_dtype={gdtype!r} implies "
                f"zero3_gather_impl='shard_map' (a sharding-constraint chain "
                f"cannot pin the wire dtype); upgrading", ranks=[0])
            impl = "shard_map"
        if gdtype == "auto":
            wire = "compute" if impl == "shard_map" else "fp32"
        elif gdtype == "bf16":
            wire = "fp16" if self._config.fp16.enabled else "bf16"
        else:
            wire = gdtype  # "fp32" | "int8"
        return impl, wire

    def _init_parameters(self, model_parameters):
        if model_parameters is not None:
            if isinstance(model_parameters, tuple) and len(model_parameters) == 2:
                values, axes = model_parameters
            else:
                values, axes = split_params_axes(model_parameters)
        else:
            # Trace init to get shapes/axes without materializing anything.
            params_shape = jax.eval_shape(self.module.init, self._rng)
            is_param = lambda x: isinstance(x, Param)
            axes = jax.tree_util.tree_map(lambda p: p.axes, params_shape, is_leaf=is_param)
            values = None

        if values is not None:
            shapes = jax.tree_util.tree_map(lambda v: tuple(v.shape), values)
        else:
            shapes = jax.tree_util.tree_map(
                lambda p: tuple(p.value.shape), params_shape,
                is_leaf=lambda x: isinstance(x, Param),
            )

        self._axes = axes
        self._shapes = shapes
        self.param_specs = param_partition_specs(
            axes, shapes, self.mesh, zero_stage=self.zero_stage,
            min_data_shard_elems=self._persist_threshold,
        )
        self.param_shardings = named(self.mesh, self.param_specs)

        if values is None:
            # init directly into the sharded layout: the zero.Init equivalent.
            init_fn = lambda rng: split_params_axes(self.module.init(rng))[0]
            if _ABSTRACT_INIT:
                self.params = _abstract_tree(
                    jax.eval_shape(init_fn, self._rng), self.param_shardings)
            else:
                with self.mesh:
                    self.params = jax.jit(init_fn, out_shardings=self.param_shardings)(self._rng)
        elif _ABSTRACT_INIT:
            self.params = _abstract_tree(
                jax.tree_util.tree_map(
                    lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), values),
                self.param_shardings)
        else:
            self.params = jax.tree_util.tree_map(jax.device_put, values, self.param_shardings)

        n_params = sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
            self._shapes, is_leaf=lambda x: isinstance(x, tuple)))
        self.num_parameters = n_params
        log_dist(f"Model parameters: {n_params / 1e6:.2f}M", ranks=[0])

    def _configure_optimizer(self):
        """Reference ``engine.py:1157`` _configure_optimizer: client optimizer wins,
        else build from config; then "wrap" = attach sharded state specs (or hand
        masters+state to the host/NVMe offload manager, the ZeRO-Offload path)."""
        self._onebit_active = False
        if self.client_optimizer is not None:
            self.optimizer = self.client_optimizer
        else:
            opt_cfg = self._config.optimizer
            self.optimizer = get_optimizer(opt_cfg.type or "adamw", opt_cfg.params)

        # weight decay mask: no decay on 1-D params (biases, norms) — the grouping
        # the reference expresses via param_groups.
        self._wd_mask = jax.tree_util.tree_map(lambda s: len(s) > 1, self._shapes,
                                               is_leaf=lambda x: isinstance(x, tuple))

        offload_cfg = self._config.zero_optimization.offload_optimizer
        self._offloaded = None
        if offload_cfg.device.value != "none":
            if _ABSTRACT_INIT:
                raise ConfigError(
                    "abstract_init does not support optimizer offload (host "
                    "masters are materialized at construction)")
            from .offload import OffloadedOptimizer

            self._offloaded = OffloadedOptimizer(
                self.optimizer, self.params, self._wd_mask,
                compute_dtype=self.compute_dtype,
                param_shardings=self.param_shardings,
                device=offload_cfg.device.value,
                nvme_path=offload_cfg.nvme_path,
                clip=self._config.gradient_clipping,
            )
            # device keeps compute-dtype params only; fp32 masters live on host
            self.params = self._offloaded._device_params()
            self.optimizer_state = None
            log_dist(
                f"Optimizer offload to {offload_cfg.device.value}: device params "
                f"in {self._config.mixed_precision_dtype}, masters on host",
                ranks=[0],
            )
            return

        # -- 1-bit (compressed-momentum) engine path --------------------------------
        from ..ops.onebit import OnebitAdam as _OnebitBase

        self._onebit_active = False
        if isinstance(self.optimizer, _OnebitBase):
            pure_dp = (self.mp_world_size == 1 and self.pipe_stages == 1
                       and self.seq_parallel_size == 1
                       and self.mesh.shape.get(EXPERT_AXIS, 1) == 1)
            dp = self.mesh.shape[DATA_AXIS]
            self._onebit_active = (pure_dp and dp > 1 and self.zero_stage <= 1
                                   and not self.fp16_enabled)
            if self._onebit_active:
                log_dist(
                    f"1-bit optimizer: compressed momentum engages after "
                    f"freeze_step={self.optimizer.freeze_step} "
                    f"(train_batch path, dp={dp})", ranks=[0])
            else:
                logger.warning(
                    "1-bit optimizer: compression requires a pure data-parallel "
                    "mesh, ZeRO<=1, bf16/fp32; running with exact numerics "
                    "(the reference's compression-off behavior)")

        state_shape = jax.eval_shape(self.optimizer.init, self.params)
        if self._onebit_active:
            # worker/server error feedback is per-device state; keep the
            # optimizer moments replicated so every device applies the same
            # reduced-momentum update
            opt_state_specs = jax.tree_util.tree_map(lambda _: P(), state_shape)
        else:
            opt_state_specs = self._opt_state_specs(state_shape)
        self._opt_shardings = named(self.mesh, opt_state_specs)
        if _ABSTRACT_INIT:
            self.optimizer_state = _abstract_tree(state_shape, self._opt_shardings)
        else:
            with self.mesh:
                self.optimizer_state = jax.jit(
                    self.optimizer.init, out_shardings=self._opt_shardings
                )(self.params)
        if self._onebit_active and _ABSTRACT_INIT:
            raise ConfigError(
                "abstract_init does not support 1-bit optimizers (their "
                "error-feedback buffers are materialized at construction)")
        if self._onebit_active:
            if self._config.health.enabled:
                logger.warning(
                    "health.enabled has no effect on the 1-bit optimizer "
                    "step path (no in-graph health side output, no "
                    "skip_step/detectors); the flight recorder stays empty")
            dp = self.mesh.shape[DATA_AXIS]
            L = self.num_parameters
            self._onebit_lpad = -(-L // dp) * dp
            data_sh = NamedSharding(self.mesh, P(DATA_AXIS))
            self._onebit_we = jax.device_put(
                np.zeros(dp * self._onebit_lpad, np.float32), data_sh)
            self._onebit_se = jax.device_put(
                np.zeros(self._onebit_lpad, np.float32), data_sh)
            self._onebit_fns = {}

    def _opt_state_specs(self, state_shape):
        """Param-shaped leaves get ZeRO-1+ data-sharded specs; scalars replicate."""
        sharded_specs = state_partition_specs(
            self._axes, self._shapes, self.mesh,
            zero_stage=self.zero_stage if self.zero_stage >= 1 else 0,
            min_data_shard_elems=self._persist_threshold if self.zero_stage >= 1 else 2 ** 62,
        )

        def spec_for(path, leaf):
            if leaf.ndim == 0:
                return P()
            # state leaves live under a head key ("exp_avg", ...) followed by the
            # param path; strip the head and look up the param's sharded spec.
            sub = tuple(path[1:])
            node = sharded_specs
            try:
                for k in sub:
                    node = node[k.key if hasattr(k, "key") else k]
                if isinstance(node, P):
                    return node
            except (KeyError, TypeError):
                pass
            return P()

        paths, treedef = jax.tree_util.tree_flatten_with_path(state_shape)
        specs = [spec_for(path, leaf) for path, leaf in paths]
        return jax.tree_util.tree_unflatten(treedef, specs)

    # ------------------------------------------------------------------------------
    # compiled programs
    # ------------------------------------------------------------------------------
    def _use_pm_1f1b(self, warn=False):
        """1F1B for user PipelineModule layer lists (pipe-only meshes; TP/SP
        widen the manual region in ways the generic switch-vjp schedule does
        not support — those fall back to the module's GPipe loss)."""
        from ..parallel.pipeline_module import PipelineModule

        if not (self.pipe_stages > 1
                and self._config.pipeline.schedule == "1f1b"
                and isinstance(self.module, PipelineModule)):
            return False
        if self.mp_world_size > 1 or self.seq_parallel_size > 1:
            if warn:
                logger.warning(
                    "PipelineModule schedule '1f1b' supports pipe x data "
                    "meshes only (model=%d seq=%d); falling back to gpipe",
                    self.mp_world_size, self.seq_parallel_size)
            return False
        return True

    def _use_1f1b(self, warn=False):
        """Single source of truth for 1F1B eligibility (used by the fwd_bwd
        builder AND the fused-step gate — they must never disagree)."""
        use_1f1b = (self.pipe_stages > 1
                    and self._config.pipeline.schedule == "1f1b"
                    and isinstance(self.params, dict) and "blocks" in self.params
                    # the 1F1B head is autoregressive (label shift + ln_f);
                    # encoder objectives and no-final-norm models take GPipe
                    and getattr(self.module.config, "causal", True)
                    and getattr(self.module.config, "final_layernorm", True))
        if use_1f1b and self.seq_parallel_size > 1:
            if warn:
                logger.warning(
                    "pipeline schedule '1f1b' does not compose with sequence "
                    "parallelism (mesh seq=%d); falling back to gpipe — a "
                    "measured wontfix: root cause and activation-cost numbers "
                    "in PARITY.md 'Known gaps'", self.seq_parallel_size)
            use_1f1b = False
        if use_1f1b and self.mp_world_size > 1 and \
                getattr(self.module.config, "n_experts", 0) > 0:
            # the manual-TP block has no MoE dispatch path
            if warn:
                logger.warning(
                    "pipeline schedule '1f1b' with tensor parallelism does not "
                    "support MoE layers; falling back to gpipe")
            use_1f1b = False
        return use_1f1b

    def _compress(self, params):
        """Apply the current compression phase's masks/fake-quant inside a
        compiled step (no-op without compression_training). The phase's step
        is a BUILD-time constant: schedule transitions invalidate the compiled
        programs (bounded recompiles — one per bit level / phase start)."""
        if self._compression is None:
            return params
        return self._compression.compress_params(params, self._compression_step)

    def _maybe_refresh_compression(self):
        if self._compression is None:
            return
        rt = self._compression
        cfg = rt.config
        step = self.global_steps
        key = (rt.bits_at(step), rt.prune_ratio_at(step),
               cfg.head_pruning.enabled and step >= cfg.head_pruning.schedule_offset,
               cfg.row_pruning.enabled and step >= cfg.row_pruning.schedule_offset)
        if key != self._compression_phase:
            self._compression_phase = key
            self._compression_step = step
            self._train_step_fn = None
            self._fwd_bwd_fn = None
            self._eval_fn = None   # eval must see the same compressed net

    def _wrap_1f1b_step(self, raw_step):
        """Engine-level concerns the manual-vjp schedules don't see:
        compression (compress once outside the schedule, pull the grads back
        through its vjp — the fused step's exact pattern) and eval mode
        (deterministic = no dropout rng, the generic fwd_bwd's trace-time
        convention; mode flips rebuild the program)."""
        def step(params, batch, scale, rng):
            if not self._train_mode:
                rng = None
            if self._compression is None:
                return raw_step(params, batch, scale, rng)
            cp, pullback = jax.vjp(self._compress, params)
            loss, grads = raw_step(cp, batch, scale, rng)
            grads = jax.tree_util.tree_map(
                lambda g, p: g.astype(p.dtype), grads, cp)
            (grads,) = pullback(grads)
            return loss, grads

        return step

    def _build_fwd_bwd(self):
        gas = self.gradient_accumulation_steps_

        if self._grad_wire_dtype is not None and (
                self._use_1f1b() or self._use_pm_1f1b()):
            logger.warning(
                "grad_reduce_dtype=bf16 does not apply to 1F1B schedules "
                "(their grads cross manual boundaries in fp32 by design); "
                "reducing in fp32")

        if self._use_pm_1f1b(warn=True):
            # 1F1B over a user PipelineModule layer list: the module builds
            # the schedule (switch-vjp per tick); same fwd_bwd contract
            step = self._wrap_1f1b_step(self.module.build_1f1b_step(
                self.mesh, self._pipe_microbatches))
            with self.mesh:
                self._fwd_bwd_fn = jax.jit(
                    step,
                    out_shardings=(NamedSharding(self.mesh, P()),
                                   self._grad_shardings))
            return

        if self._use_1f1b(warn=True):
            # 1F1B: the whole microbatch window (fwd AND bwd, interleaved) is one
            # compiled schedule — in-flight activations bounded by stages, not
            # microbatches (reference runtime/pipe/schedule.py:189 TrainSchedule).
            from ..parallel.pipeline_1f1b import build_1f1b_train_step

            step = self._wrap_1f1b_step(build_1f1b_train_step(
                self.module, self.mesh, self._pipe_microbatches,
                blocks_param_specs=self.param_specs.get("blocks")
                if isinstance(self.param_specs, dict) else None))
            with self.mesh:
                self._fwd_bwd_fn = jax.jit(
                    step,
                    out_shardings=(NamedSharding(self.mesh, P()),
                                   self._grad_shardings),
                )
            return

        def fwd_bwd(params, batch, scale, rng):
            def scaled_loss(p):
                loss = self.module.loss(self._compress(p), batch,
                                        deterministic=not self._train_mode,
                                        dropout_rng=rng)
                # reference scales by 1/gas at backward (engine.py:1793) and by the
                # fp16 loss scale inside the scaler
                return loss * scale.astype(loss.dtype) / gas, loss

            (_, loss), grads = jax.value_and_grad(scaled_loss, has_aux=True)(params)
            if self._grad_wire_dtype is not None:
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(self._grad_wire_dtype), grads)
            return loss, grads

        with self.mesh:
            self._fwd_bwd_fn = jax.jit(
                fwd_bwd, out_shardings=(NamedSharding(self.mesh, P()), self._grad_shardings)
            )

    def _build_accumulate(self):
        def accumulate(acc, grads):
            return jax.tree_util.tree_map(jnp.add, acc, grads)

        with self.mesh:
            self._accumulate_fn = jax.jit(
                accumulate, donate_argnums=(0,), out_shardings=self._grad_shardings
            )

    def _apply_body(self, params, opt_state, acc_grads, scale, good_steps, lr):
        """Unscale -> overflow check -> clip -> optimizer update -> loss-scale
        update. Shared by the standalone apply program and the fused train step.

        Also computes the per-param-group health side output (tiny f32[G]
        vectors — see ``telemetry/health.py``) and, when the health config's
        nonfinite detector is armed with ``skip_step``, generalizes the fp16
        overflow-skip to any-dtype non-finite grads. The returned flag is the
        *skip* decision (== overflow for plain fp16)."""
        from ..telemetry.health import group_health_stats

        clip = self._config.gradient_clipping
        fp16 = self.fp16_enabled
        window = self._config.fp16.loss_scale_window
        min_scale = self._config.fp16.min_loss_scale
        dynamic = (self._scaler_meta or {}).get("_dynamic", False)

        inv = (1.0 / scale).astype(jnp.float32)
        grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32) * inv, acc_grads)
        raw_grads = grads  # pre-clip: the health stats price true magnitudes
        overflow = check_overflow(grads) if fp16 else jnp.asarray(False)
        norm = global_grad_norm(grads)
        if clip > 0:
            grads, _ = clip_grads_by_global_norm(grads, clip, norm=norm)
        new_params, new_state = self.optimizer.update(
            grads, opt_state, params, lr=lr, wd_mask=self._wd_mask
        )
        skip = overflow
        if self._health_skip and not fp16:
            skip = check_overflow(grads)
        if fp16 or self._health_skip:
            # skip the update on overflow (reference FP16_Optimizer.step) /
            # on non-finite grads when the health skip is armed
            new_params = jax.tree_util.tree_map(
                lambda old, new: jnp.where(skip, old, new), params, new_params
            )
            new_state = jax.tree_util.tree_map(
                lambda old, new: jnp.where(skip, old, new), opt_state, new_state
            )
        if fp16 and dynamic:
            scale, good_steps = update_scale(
                scale, good_steps, overflow, loss_scale_window=window,
                min_scale=min_scale,
            )
        health = group_health_stats(raw_grads, params, new_params,
                                    self._health_groups)
        return new_params, new_state, scale, good_steps, skip, norm, health

    def _build_apply(self):
        def apply_step(params, opt_state, acc_grads, scale, good_steps, lr):
            return self._apply_body(params, opt_state, acc_grads, scale,
                                    good_steps, lr)

        # Donate params + opt state (NOT grads: arg 2 has the same
        # shapes/dtypes as the params but there are only len(outputs) buffers
        # to alias — new_params + new_state — so donating them too makes XLA
        # report one whole param-tree of "donated buffers were not usable";
        # the grads buffer is freed after the step either way, the engine
        # drops its reference). scale/good_steps are engine-owned and have
        # matching outputs, so they donate too (sanitizer donation rule).
        from ..telemetry.health import HEALTH_STAT_KEYS

        rep = NamedSharding(self.mesh, P())
        with self.mesh:
            self._apply_fn = jax.jit(
                apply_step,
                donate_argnums=(0, 1, 3, 4),
                out_shardings=(
                    self.param_shardings,
                    self._opt_shardings,
                    rep, rep, rep, rep,
                    {k: rep for k in HEALTH_STAT_KEYS},
                ),
            )

    def _build_train_step(self):
        """The whole optimizer step as ONE compiled program: grad-accum loop
        (lax.scan over stacked micro-batches), in-program rng split, optimizer
        apply — params/opt-state donated through. The reference pays a Python
        round-trip per micro-batch plus one per step (``engine.py:1634/:1775/:1971``);
        here ``train_batch`` is a single device dispatch, which also removes the
        grads' HBM round-trip between the backward and the update."""
        gas = self.gradient_accumulation_steps_

        pld_enabled = self._pld is not None

        def train_step(params, opt_state, batches, scale, good_steps, rng, lr,
                       pld_theta):
            new_rng, step_rng = jax.random.split(rng)

            def scaled_loss(p, batch, r):
                loss = self.module.loss(
                    p, batch, deterministic=not self._train_mode,
                    dropout_rng=r,
                    **({"pld_theta": pld_theta} if pld_enabled else {}))
                return loss * scale.astype(loss.dtype) / gas, loss

            grad_fn = jax.value_and_grad(scaled_loss, has_aux=True)
            grad_wire = self._grad_wire_dtype

            def constrain(g):
                # ZeRO-2: grads sharded over data; with grad_reduce_dtype=
                # bf16 the cast lands BEFORE the constraint, so the reduce
                # collective's payload (and the accumulation carry) is 16-bit
                if grad_wire is not None:
                    g = jax.tree_util.tree_map(
                        lambda a: a.astype(grad_wire), g)
                return jax.lax.with_sharding_constraint(
                    g, self._grad_shardings)
            # compression runs ONCE per step, outside the accumulation scan:
            # cp is the compressed tree the micro-batches differentiate
            # against, and the vjp pulls the accumulated grads back through
            # the masks/STE exactly (identity for fake-quant, mask multiply
            # for pruning) — not gas redundant fake-quant/sort passes
            if self._compression is not None:
                cp, compress_vjp = jax.vjp(self._compress, params)
            else:
                cp, compress_vjp = params, None
            if gas == 1:
                (_, loss), grads = grad_fn(cp, batches, step_rng)
                mean_loss = loss
            else:
                micro_rngs = jax.random.split(step_rng, gas)
                zeros = constrain(jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, p.dtype), params))

                def body(acc, xs):
                    micro, r = xs
                    (_, loss), g = grad_fn(cp, micro, r)
                    acc = constrain(jax.tree_util.tree_map(jnp.add, acc, g))
                    return acc, loss

                grads, losses = jax.lax.scan(body, zeros, (batches, micro_rngs))
                mean_loss = jnp.mean(losses)
            if compress_vjp is not None:
                # the pullback wants cotangents in the primal output dtype
                # (fp32 params); harmless identity cast when grad_wire is off
                grads = jax.tree_util.tree_map(
                    lambda g, p: g.astype(p.dtype), grads, cp)
                (grads,) = compress_vjp(grads)
            grads = constrain(grads)

            (new_params, new_state, scale, good_steps,
             overflow, norm, health) = self._apply_body(params, opt_state,
                                                        grads, scale,
                                                        good_steps, lr)
            return (new_params, new_state, scale, good_steps, overflow, norm,
                    mean_loss, new_rng, health)

        from ..telemetry.health import HEALTH_STAT_KEYS

        rep = NamedSharding(self.mesh, P())
        # Donate the engine-owned step state threaded through the program:
        # params, opt state, AND the loss-scale/good-steps/rng scalars (each
        # has a same-shape output to alias; the engine overwrites its
        # references right after the call, so the stale inputs are dead
        # either way — found by the program sanitizer's donation rule). lr
        # and the batch are caller-owned and have no matching output.
        with self.mesh:
            self._train_step_fn = jax.jit(
                train_step,
                donate_argnums=(0, 1, 3, 4, 5),
                out_shardings=(self.param_shardings, self._opt_shardings,
                               rep, rep, rep, rep, rep, rep,
                               {k: rep for k in HEALTH_STAT_KEYS}),
            )

    def _can_fuse_train_step(self):
        """One-dispatch train_batch: anything but the offloaded (host-step) path
        and the 1F1B schedules (whose fwd+bwd programs have their own contract)."""
        return self._offloaded is None and not self._use_1f1b() \
            and not self._use_pm_1f1b()

    def _fused_train_batch(self, micros):
        if self._train_step_fn is None:
            self._build_train_step()
        gas = self.gradient_accumulation_steps_
        if gas == 1:
            batches = self._shard_batch(micros[0])
        else:
            data_size = self.mesh.shape[DATA_AXIS]
            stacked = {}
            keys = micros[0].keys()
            for k in keys:
                stacked[k] = np.stack([np.asarray(m[k]) for m in micros])
                if stacked[k].ndim >= 2 and stacked[k].shape[1] % data_size:
                    raise ConfigError(
                        f"Batch leaf '{k}' has {stacked[k].shape[1]} rows, not "
                        f"divisible by the data-parallel mesh axis ({data_size}); "
                        f"global micro-batch must be a multiple of dp size")
            shapes = {k: tuple(v.shape[1:]) for k, v in stacked.items()}
            specs = batch_partition_specs(shapes, self.mesh)
            shardings = {
                k: NamedSharding(self.mesh, P(None, *specs[k]))
                for k in keys
            }
            batches = {k: jax.device_put(jnp.asarray(stacked[k]), shardings[k])
                       for k in keys}
        lr = self._current_lr()
        pld_theta = jnp.asarray(
            self._pld.update_state(self.global_steps) if self._pld else 1.0,
            jnp.float32)
        self._last_batch_struct = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding), batches)
        if self.health is not None and self.health.enabled:
            # the record must pin the key that SEEDS this step (the step fn
            # donates + replaces self._rng); host copy before the dispatch
            self._health_rng = np.asarray(self._rng).tolist()
        (self.params, self.optimizer_state, self._scale, self._good_steps,
         skip, grad_norm, mean_loss, self._rng, health) = self._train_step_fn(
            self.params, self.optimizer_state, batches, self._scale,
            self._good_steps, self._rng, jnp.asarray(lr, jnp.float32),
            pld_theta,
        )
        self.micro_steps += gas
        self.global_steps += 1
        skipped = (self.fp16_enabled or self._health_skip) and bool(skip)
        if skipped:
            self.skipped_steps += 1
            log_dist(
                f"step {self.global_steps}: "
                + ("fp16 overflow" if self.fp16_enabled
                   else "non-finite grads (health skip_step)")
                + f", skipping update (loss scale -> {float(self._scale)})",
                ranks=[0],
            )
        elif self.lr_scheduler is not None:
            self.lr_scheduler.step()
        self._observe_health(health, loss=mean_loss, grad_norm=grad_norm,
                             skipped=skipped, lr=lr, batch=micros)
        if self.global_steps % self._config.steps_per_print == 0:
            events = [("Train/lr", lr, self.global_steps),
                      ("Train/grad_norm", float(grad_norm), self.global_steps),
                      ("Train/loss", float(mean_loss), self.global_steps),
                      ("Train/loss_scale", float(self._scale),
                       self.global_steps),
                      ("Train/skipped_steps", float(self.skipped_steps),
                       self.global_steps)]
            if self._config.comms_logger.enabled:
                ws = self.collective_wire_stats()
                if ws:
                    for kind, s in ws["collectives"].items():
                        if s["count"]:
                            events.append((f"Comm/{kind.replace('-', '_')}_gb",
                                           s["wire_bytes"] / 1e9,
                                           self.global_steps))
                    events.append(("Comm/total_wire_gb",
                                   ws["total_wire_bytes"] / 1e9,
                                   self.global_steps))
                    sched = ws.get("schedule")
                    if sched:
                        # the exposed-vs-overlappable split of the same wire
                        # bytes (schedule audit): trace_summary.py flags
                        # steps whose exposed share exceeds budget
                        events.append(("Comm/exposed_wire_gb",
                                       sched["exposed_bytes"] / 1e9,
                                       self.global_steps))
                        events.append(("Comm/exposed_frac",
                                       sched["exposed_fraction"],
                                       self.global_steps))
            self.monitor.write_events(events)
            self._report_progress()
            self.tracer.flush()
            if self._config.memory_breakdown:
                # reference see_memory_usage role, via the accelerator seam
                from ..accelerator import get_accelerator

                a = get_accelerator()
                log_dist(
                    f"memory: {a.memory_allocated() / 2**30:.2f} GiB in use / "
                    f"{a.total_memory() / 2**30:.2f} GiB", ranks=[0])
        return mean_loss

    def _observe_health(self, stats, loss=None, grad_norm=None, skipped=False,
                        lr=None, batch=None):
        """Feed one step's in-graph health side output to the flight
        recorder (no-op unless ``health.enabled``; the host conversion is
        the one sync the health path pays). Raises ``HealthHalted`` when a
        halt-action detector fires — after its black-box dump published."""
        hm = self.health
        if hm is None or not hm.enabled or stats is None:
            return None
        if self.global_steps % self._config.health.check_interval:
            return None
        from ..telemetry.health import (HealthHalted, batch_fingerprint,
                                        record_from_stats)

        rec = record_from_stats(
            self.global_steps, self._health_groups, stats,
            loss=None if loss is None else float(loss),
            loss_scale=float(self._scale), skipped=bool(skipped),
            grad_norm=None if grad_norm is None else float(grad_norm),
            lr=None if lr is None else float(lr),
            rng=self._health_rng,
            fingerprint=batch_fingerprint(batch))
        anomalies = hm.observe(rec)
        halt = [a for a in anomalies if a.action == "halt"]
        if halt:
            raise HealthHalted(
                f"health detector halt at step {self.global_steps}: "
                + "; ".join(a.message for a in halt))
        return anomalies

    def _apply_curriculum(self, batch):
        """Truncate sequence-dim leaves to the scheduled difficulty (seqlen
        curriculum, reference ``engine.py:1675``). Each distinct difficulty
        value compiles once — schedules quantize via ``difficulty_step``."""
        if self._curriculum is None:
            return batch
        diff = int(self._curriculum.update_difficulty(self.global_steps + 1))
        out = {}
        for k, v in batch.items():
            a = np.asarray(v)
            out[k] = a[:, :diff] if a.ndim >= 2 and a.shape[1] > diff else a
        return out

    @property
    def curriculum_difficulty(self):
        if self._curriculum is None:
            return None
        return self._curriculum.state["current_difficulty"]

    def _build_onebit_step(self, stage, batch_tree):
        """One compiled program per 1-bit stage (reference ``onebit/adam.py``
        warmup vs compressed): everything — local grads, grad accumulation,
        the compressed momentum allreduce, and the update — runs inside ONE
        shard_map over ``data``. The stage is picked HOST-side from
        global_steps (freeze_step is static), so no collective sits inside a
        conditional."""
        from jax.flatten_util import ravel_pytree

        from ..comm.compressed import compressed_allreduce_local

        gas = self.gradient_accumulation_steps_
        opt = self.optimizer
        L_pad = self._onebit_lpad
        bits = self._config.gradient_compression.bits \
            if self._config.gradient_compression.enabled else 1

        def local_grads(params, batches, rng):
            def gfn(p, micro, r):
                loss = self.module.loss(p, micro,
                                        deterministic=not self._train_mode,
                                        dropout_rng=r)
                return loss

            grad_fn = jax.value_and_grad(gfn)
            if gas == 1:
                loss, g = grad_fn(params, batches, rng)
            else:
                rngs = jax.random.split(rng, gas)
                zeros = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, p.dtype), params)

                def body(carry, xs):
                    acc, lsum = carry
                    micro, r = xs
                    l, g = grad_fn(params, micro, r)
                    return (jax.tree_util.tree_map(jnp.add, acc, g),
                            lsum + l), None

                (g, lsum), _ = jax.lax.scan(
                    body, (zeros, jnp.zeros((), jnp.float32)), (batches, rngs))
                g = jax.tree_util.tree_map(lambda a: a / gas, g)
                loss = lsum / gas
            return loss, g

        clip = self._config.gradient_clipping

        def body(params, state, we, se, batches, rng, lr):
            loss, g = local_grads(params, batches, rng)
            loss = jax.lax.pmean(loss, DATA_AXIS)
            if stage == "warmup":
                g = jax.tree_util.tree_map(
                    lambda a: jax.lax.pmean(a.astype(jnp.float32), DATA_AXIS), g)
                if clip > 0:  # exact global-norm clip, matching the adamw path
                    g, _ = clip_grads_by_global_norm(g, clip)
                new_params, new_state = opt.update(
                    g, state, params, lr=lr, wd_mask=self._wd_mask)
                return new_params, new_state, we, se, loss
            g = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), g)
            if clip > 0:
                # compressed stage: the exact global-grad norm would need the
                # uncompressed pmean (defeating the compression), so clip each
                # local grad by sqrt(pmean ||g_local||^2) — an upper bound on
                # the mean-grad norm, so spikes are still bounded
                sq = sum(jnp.sum(jnp.square(a))
                         for a in jax.tree_util.tree_leaves(g))
                norm = jnp.sqrt(jax.lax.pmean(sq, DATA_AXIS))
                factor = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
                g = jax.tree_util.tree_map(lambda a: a * factor, g)
            m_tree = opt.local_momentum(g, state)
            flat, unravel = ravel_pytree(m_tree)
            flat = jnp.pad(flat, (0, L_pad - flat.size))
            m_red, we, se = compressed_allreduce_local(
                flat, we, se, DATA_AXIS, bits=bits)
            new_params, new_state = opt.apply_compressed(
                unravel(m_red[:self.num_parameters]), state, params,
                lr=lr, wd_mask=self._wd_mask)
            return new_params, new_state, we, se, loss

        batch_in_specs = jax.tree_util.tree_map(
            lambda a: P(None, DATA_AXIS) if gas > 1 else P(DATA_AXIS),
            batch_tree)
        param_specs = jax.tree_util.tree_map(lambda _: P(), self.params)
        state_specs = jax.tree_util.tree_map(lambda _: P(), self.optimizer_state)
        sm = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(param_specs, state_specs, P(DATA_AXIS), P(DATA_AXIS),
                      batch_in_specs, P(), P()),
            out_specs=(param_specs, state_specs, P(DATA_AXIS), P(DATA_AXIS),
                       P()),
            axis_names={DATA_AXIS}, check_vma=False)
        with self.mesh:
            return jax.jit(sm, donate_argnums=(0, 1, 2, 3))

    def _onebit_train_batch(self, micros):
        gas = self.gradient_accumulation_steps_
        dp = self.mesh.shape[DATA_AXIS]
        if gas == 1:
            batches = {k: jnp.asarray(np.asarray(micros[0][k]))
                       for k in micros[0]}
        else:
            batches = {k: jnp.asarray(np.stack(
                [np.asarray(m[k]) for m in micros])) for k in micros[0]}
        rows_axis = 1 if gas > 1 else 0
        for k, v in batches.items():
            if v.shape[rows_axis] % dp:
                raise ConfigError(
                    f"Batch leaf '{k}' has {v.shape[rows_axis]} rows, not "
                    f"divisible by the data-parallel mesh axis ({dp})")
        stage = "warmup" if self.optimizer.wants_exact_step(self.global_steps) \
            else "compressed"
        key = (stage, jax.tree_util.tree_structure(batches),
               tuple(tuple(v.shape) for v in batches.values()))
        if key not in self._onebit_fns:
            self._onebit_fns[key] = self._build_onebit_step(stage, batches)
        self._rng, step_rng = jax.random.split(self._rng)
        lr = self._current_lr()
        (self.params, self.optimizer_state, self._onebit_we, self._onebit_se,
         loss) = self._onebit_fns[key](
            self.params, self.optimizer_state, self._onebit_we,
            self._onebit_se, batches, step_rng, jnp.asarray(lr, jnp.float32))
        self.micro_steps += gas
        self.global_steps += 1
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
        if self.global_steps % self._config.steps_per_print == 0:
            self.monitor.write_events(
                [("Train/lr", lr, self.global_steps),
                 ("Train/loss", float(loss), self.global_steps)])
            self._report_progress()
        return loss

    # ------------------------------------------------------------------------------
    # data placement
    # ------------------------------------------------------------------------------
    def _shard_batch(self, batch):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        data_size = self.mesh.shape[DATA_AXIS]
        for k, v in batch.items():
            if v.ndim >= 1 and v.shape[0] % data_size:
                raise ConfigError(
                    f"Batch leaf '{k}' has {v.shape[0]} rows, not divisible by the "
                    f"data-parallel mesh axis ({data_size}); global micro-batch must "
                    f"be a multiple of dp size"
                )
        shapes = {k: tuple(v.shape) for k, v in batch.items()}
        specs = batch_partition_specs(shapes, self.mesh)
        shardings = named(self.mesh, specs)
        return {k: jax.device_put(batch[k], shardings[k]) for k in batch}

    def deepspeed_io(self, dataset, batch_size=None, collate_fn=None):
        """Reference ``engine.py:1542`` deepspeed_io."""
        return DeepSpeedDataLoader(
            dataset,
            batch_size=batch_size or self.micro_batch_size * self._pipe_microbatches
            * self.dp_world_size // max(dist.get_world_size(), 1),
            shuffle=True,
            seed=self._config.seed,
            collate_fn=collate_fn,
            rank=dist.get_rank(),
            num_shards=dist.get_world_size(),
        )

    # ------------------------------------------------------------------------------
    # train API (reference engine.forward :1634 / backward :1775 / step :1971)
    # ------------------------------------------------------------------------------
    def __call__(self, batch):
        return self.forward(batch)

    def forward(self, batch):
        """Compute loss AND gradients for one micro-batch (cached for backward).

        The reference runs a separate autograd backward; under XLA forward and
        backward are one fused program — ``forward`` returns the loss and stashes
        the grads, ``backward`` accumulates them. Numerically identical, one less
        pass over the activations.
        """
        with self.tracer.span("fwd", cat="train", sync=self._telemetry_sync,
                              step=self.global_steps + 1) as sp:
            if self._wall_clock_breakdown:
                self.timers(FORWARD_GLOBAL_TIMER).start()
            self._maybe_refresh_compression()
            if self._fwd_bwd_fn is None:
                self._build_fwd_bwd()
            batch = self._shard_batch(self._apply_curriculum(batch))
            if (self.health is not None and self.health.enabled
                    and self.is_gradient_accumulation_boundary()):
                # first micro-batch of the window: this key deterministically
                # seeds every micro-step split the window consumes
                self._health_rng = np.asarray(self._rng).tolist()
            self._rng, step_rng = jax.random.split(self._rng)
            loss, grads = self._fwd_bwd_fn(self.params, batch, self._scale, step_rng)
            self._cached = (loss, grads)
            self._last_loss = loss
            sp.fence(self._cached)
            if self._wall_clock_breakdown:
                self.timers(FORWARD_GLOBAL_TIMER).stop()
            return loss

    def backward(self, loss=None):
        """Accumulate the cached micro-batch grads (reference engine.backward)."""
        if self._cached is None:
            raise RuntimeError("backward() called before forward()")
        with self.tracer.span("bwd", cat="train", sync=self._telemetry_sync,
                              step=self.global_steps + 1) as sp:
            if self._wall_clock_breakdown:
                self.timers(BACKWARD_GLOBAL_TIMER).start()
            _, grads = self._cached
            self._cached = None
            if self._acc_grads is None:
                self._acc_grads = grads
            else:
                if self._accumulate_fn is None:
                    self._build_accumulate()
                self._acc_grads = self._accumulate_fn(self._acc_grads, grads)
            sp.fence(self._acc_grads)
            self.micro_steps += 1
            if self._wall_clock_breakdown:
                self.timers(BACKWARD_GLOBAL_TIMER).stop()
            return loss

    def is_gradient_accumulation_boundary(self):
        """Reference ``engine.py:1565``."""
        return self.micro_steps % self.gradient_accumulation_steps_ == 0

    def step(self):
        """Apply the optimizer at the accumulation boundary (reference engine.step)."""
        if not self.is_gradient_accumulation_boundary():
            return
        if self._acc_grads is None:
            raise RuntimeError("step() called with no accumulated gradients")
        with self.tracer.span("step", cat="train", sync=self._telemetry_sync,
                              step=self.global_steps + 1) as sp:
            if self._wall_clock_breakdown:
                self.timers(STEP_GLOBAL_TIMER).start()
            if self._offloaded is not None:
                return self._offloaded_step()
            if self._apply_fn is None:
                self._build_apply()
            lr = self._current_lr()
            (self.params, self.optimizer_state, self._scale,
             self._good_steps, skip, grad_norm, health) = self._apply_fn(
                self.params, self.optimizer_state, self._acc_grads, self._scale,
                self._good_steps, jnp.asarray(lr, jnp.float32),
            )
            self._acc_grads = None  # donated; re-seeded by the next backward()
            sp.fence(self.params)
            self.global_steps += 1
            skipped = (self.fp16_enabled or self._health_skip) and bool(skip)
            if skipped:
                self.skipped_steps += 1
                log_dist(
                    f"step {self.global_steps}: "
                    + ("fp16 overflow" if self.fp16_enabled
                       else "non-finite grads (health skip_step)")
                    + f", skipping update (loss scale -> {float(self._scale)})",
                    ranks=[0],
                )
            elif self.lr_scheduler is not None:
                self.lr_scheduler.step()
            self._observe_health(health, loss=self._last_loss,
                                 grad_norm=grad_norm, skipped=skipped, lr=lr)
            if self._wall_clock_breakdown:
                self.timers(STEP_GLOBAL_TIMER).stop()
                # monitor events read WITHOUT reset so the log() line below
                # still sees the same window (log resets)
                self.timers.write_events(
                    self.monitor,
                    [FORWARD_GLOBAL_TIMER, BACKWARD_GLOBAL_TIMER,
                     STEP_GLOBAL_TIMER],
                    self.global_steps, reset=False)
                self.timers.log(
                    [FORWARD_GLOBAL_TIMER, BACKWARD_GLOBAL_TIMER, STEP_GLOBAL_TIMER]
                )
            if self.global_steps % self._config.steps_per_print == 0:
                self.monitor.write_events(
                    [("Train/lr", lr, self.global_steps),
                     ("Train/grad_norm", float(grad_norm), self.global_steps),
                     ("Train/loss_scale", float(self._scale),
                      self.global_steps),
                     ("Train/skipped_steps", float(self.skipped_steps),
                      self.global_steps)]
                )
                self.tracer.flush()
            return grad_norm

    def _offloaded_step(self):
        """ZeRO-Offload step: grads -> host, host optimizer on fp32 masters,
        compute-dtype params -> device (reference stage_1_and_2.py CPU-offload
        path :1031-1113 + cpu_adam kernels)."""
        from ..ops import update_scale

        lr = self._current_lr()
        scale_inv = 1.0 / float(self._scale)
        # only the health path needs the step's inputs held alive (to price
        # the applied update); otherwise release them on schedule — the
        # offload path exists for tight device memory
        grads = old_params = None
        if self.health is not None and self.health.enabled:
            grads, old_params = self._acc_grads, self.params
        self.params, grad_norm, overflow = self._offloaded.step(
            self._acc_grads, lr, scale_inv)
        self._acc_grads = None
        self.global_steps += 1
        if self.fp16_enabled:
            dynamic = (self._scaler_meta or {}).get("_dynamic", False)
            if dynamic:
                self._scale, self._good_steps = update_scale(
                    self._scale, self._good_steps, jnp.asarray(overflow),
                    loss_scale_window=self._config.fp16.loss_scale_window,
                    min_scale=self._config.fp16.min_loss_scale,
                )
        if overflow:
            self.skipped_steps += 1
            log_dist(
                f"step {self.global_steps}: overflow, skipping update "
                f"(loss scale -> {float(self._scale)})",
                ranks=[0],
            )
        elif self.lr_scheduler is not None:
            self.lr_scheduler.step()
        if self._wall_clock_breakdown:
            self.timers(STEP_GLOBAL_TIMER).stop()
            self.timers.log(
                [FORWARD_GLOBAL_TIMER, BACKWARD_GLOBAL_TIMER, STEP_GLOBAL_TIMER]
            )
        if self.health is not None and self.health.enabled:
            # device-side stats for the host-stepped path: one small jitted
            # program over (grads, old, new) — still no callbacks in-step
            if self._health_fn is None:
                from ..telemetry.health import group_health_stats

                groups = self._health_groups
                with self.mesh:
                    self._health_fn = jax.jit(
                        lambda g, old, new, inv: group_health_stats(
                            jax.tree_util.tree_map(
                                lambda a: a.astype(jnp.float32) * inv, g),
                            old, new, groups))
            stats = self._health_fn(grads, old_params, self.params,
                                    jnp.asarray(scale_inv, jnp.float32))
            self._observe_health(stats, loss=self._last_loss,
                                 grad_norm=grad_norm, skipped=bool(overflow),
                                 lr=lr)
        if self.global_steps % self._config.steps_per_print == 0:
            self.monitor.write_events(
                [("Train/lr", lr, self.global_steps),
                 ("Train/grad_norm", float(grad_norm), self.global_steps),
                 ("Train/loss_scale", float(self._scale), self.global_steps),
                 ("Train/skipped_steps", float(self.skipped_steps),
                  self.global_steps)]
            )
        return grad_norm

    def train_batch(self, data_iter=None, batch=None):
        """Full accumulation window in one call (reference PipelineEngine.train_batch
        shape). Feeds ``gradient_accumulation_steps`` micro-batches. On the main
        path this is ONE device dispatch (see ``_build_train_step``); the returned
        loss is a device scalar — not synced — so back-to-back calls pipeline.
        Exception: fp16's dynamic loss scaling must read the overflow flag each
        step (as the reference's ``FP16_Optimizer.step`` does), which syncs;
        the pipelining guarantee holds for bf16/fp32 (and for the health
        monitor's per-step observe when ``health.enabled``, which also syncs).
        """
        try:
            return self._train_batch_impl(data_iter=data_iter, batch=batch)
        except Exception as e:
            # black-box on the way down: an unhandled step exception
            # publishes the ring buffer before propagating. HealthHalted
            # already dumped (the halt action fires dump first).
            from ..telemetry.health import HealthHalted

            if (self.health is not None and self.health.enabled
                    and self._config.health.dump_on_exception
                    and not isinstance(e, HealthHalted)):
                self.health.dump("exception",
                                 extra={"exception": repr(e),
                                        "step": self.global_steps})
            raise

    def _train_batch_impl(self, data_iter=None, batch=None):
        step_no = self.global_steps + 1
        with self.tracer.span("train_batch", cat="train",
                              sync=self._telemetry_sync, step=step_no):
            self.tput_timer.start()
            self._maybe_refresh_compression()
            with self.tracer.span("data", cat="train", step=step_no):
                micros = []
                for _ in range(self.gradient_accumulation_steps_):
                    micro = batch if batch is not None else next(data_iter)
                    micros.append(self._apply_curriculum(micro))
            if self._onebit_active:
                with self.tracer.span("step", cat="train", step=step_no):
                    mean_loss = self._onebit_train_batch(micros)
                self.tput_timer.stop(global_step=True)
                return mean_loss
            if self._can_fuse_train_step():
                # ONE device dispatch: fwd+bwd+apply (and the in-program
                # ZeRO-3 gather schedule) are indistinguishable host-side —
                # the schedule auditor attributes inside the program
                with self.tracer.span("step", cat="train", step=step_no):
                    mean_loss = self._fused_train_batch(micros)
                self.tput_timer.stop(global_step=True)
                return mean_loss
            losses = []
            for micro in micros:
                loss = self.forward(micro)
                self.backward(loss)
                losses.append(loss)
            self.step()
            self.tput_timer.stop(global_step=True)
            mean_loss = jnp.mean(jnp.stack(losses)) if len(losses) > 1 else losses[0]
            if self.global_steps % self._config.steps_per_print == 0:
                self.monitor.write_events([("Train/loss", float(mean_loss), self.global_steps)])
                self._report_progress()
            return mean_loss

    def eval_batch(self, batch):
        """Loss without grads. On pipe meshes this runs the PIPELINED forward
        with a single microbatch: weights stay stage-local and activations move
        by ppermute, where the previous non-pipelined eval read the pipe-sharded
        layer stack through the auto partitioner — an all-gather of every block
        weight per eval step (brutal at multi-B params). M=1 keeps eval free of
        any microbatch divisibility contract; the (S-1)/S bubble is irrelevant
        at eval rates."""
        self._maybe_refresh_compression()
        if self._eval_fn is None:
            module = self.module
            if self.pipe_stages > 1:
                import dataclasses

                module = type(self.module)(
                    dataclasses.replace(self.module.config,
                                        pipeline_microbatches=1)
                )
            # eval the COMPRESSED net (what redundancy_clean will deploy),
            # not the dense masters
            with self.mesh:
                self._eval_fn = jax.jit(
                    lambda p, b: module.loss(self._compress(p), b))
        return self._eval_fn(self.params, self._shard_batch(batch))

    def _current_lr(self):
        if self.lr_scheduler is not None:
            return self.lr_scheduler.get_last_lr()[0]
        return self.optimizer.lr

    def get_lr(self):
        return [self._current_lr()]

    def set_lr(self, lr):
        """Override the learning rate (reference engine ``set_lr``): updates
        the scheduler's base lr when one is attached, else the optimizer's.
        Takes effect next step — lr is a traced runtime argument, so no
        recompile."""
        if self.lr_scheduler is not None and hasattr(self.lr_scheduler, "set_lr"):
            self.lr_scheduler.set_lr(lr)
        elif self.lr_scheduler is not None:
            raise ValueError(
                f"{type(self.lr_scheduler).__name__} does not support set_lr; "
                "drive the schedule through its own params")
        else:
            self.optimizer.lr = lr

    def train(self, mode=True):
        """torch-style mode flag (reference engine.train/eval): eval mode makes
        ``forward``/``train_batch`` run deterministically (no dropout/PLD).
        Flipping the mode rebuilds the compiled step programs (the flag is
        baked into the trace)."""
        mode = bool(mode)
        if mode != self._train_mode:
            self._train_mode = mode
            self._fwd_bwd_fn = None
            self._train_step_fn = None
            if getattr(self, "_onebit_active", False):
                self._onebit_fns = {}
        return self

    def eval(self):
        return self.train(False)

    def destroy(self):
        """Release device memory and compiled programs (reference
        engine.py:381 ``destroy``). The engine's jitted closures capture
        ``self``, so dropping the last user reference leaves a cycle that
        holds params/optimizer state in HBM until an eventual full gc pass;
        after ``destroy()`` the buffers are freed immediately. The engine is
        unusable afterwards."""
        self.params = None
        self.optimizer_state = None
        self._acc_grads = None
        self._cached = None   # forward()'s stashed (loss, grads)
        self._fwd_bwd_fn = None
        self._accumulate_fn = None
        self._apply_fn = None
        self._train_step_fn = None
        self._eval_fn = None
        if getattr(self, "_onebit_active", False):
            self._onebit_fns = {}
            self._onebit_we = None   # error-feedback buffers (~params-sized)
            self._onebit_se = None
        self._offloaded = None
        self.tracer.flush()  # don't lose the trace tail with the engine
        import gc

        # no jax.clear_caches(): that is process-global and would force every
        # OTHER live engine in the process to recompile; dropping this
        # engine's jitted wrappers frees its executables
        gc.collect()

    def lower_train_step(self, batch=None):
        """The fused train step as a ``jax.stages.Lowered`` — for audits and
        smoke checks that read the program (``.compile().as_text()``,
        ``.compile().memory_analysis()``). ``batch``: a host micro-batch to
        shape the step for (gradient_accumulation_steps == 1); default: the
        shape of the last batch ``train_batch`` ran. Lowering only traces
        avals — nothing executes and nothing is donated; compiling the
        result goes through the persistent compilation cache like the
        step's own first dispatch."""
        if self._train_step_fn is None:
            self._build_train_step()
        struct = self._last_batch_struct
        if batch is not None:
            if self.gradient_accumulation_steps_ > 1:
                raise ConfigError(
                    "lower_train_step(batch) shapes a single micro-batch; "
                    "with gradient accumulation call it after train_batch")
            struct = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=a.sharding),
                self._shard_batch(batch))
        if struct is None:
            raise ConfigError("lower_train_step: pass a batch, or run "
                              "train_batch once first")
        return self._train_step_fn.lower(
            self.params, self.optimizer_state, struct, self._scale,
            self._good_steps, self._rng, jnp.asarray(0.0, jnp.float32),
            jnp.asarray(1.0, jnp.float32))

    def collective_wire_stats(self, refresh=False):
        """Per-step collective wire bytes of the compiled train step, by
        kind and payload dtype (``profiling/collectives.py``).

        Available after the first fused ``train_batch`` call. The first call
        triggers ONE extra AOT compile of the step program (the audit needs
        a fresh pass-pipeline run to snapshot the post-SPMD-partitioning
        HLO); the result is cached. Returns None when the fused step has not
        run yet (pipeline/offload/1-bit paths are not audited here — use
        ``tools/collective_audit.py`` on a matching config instead).

        Only offered at gradient_accumulation_steps == 1: with gas > 1 the
        accumulation scan and the layer scan are BOTH while bodies, and the
        single loop-trip multiplier would mis-scale them in opposite
        directions (gathers x8 under, reduces x5 over at gas=8/L=40) —
        wrong monitor numbers are worse than none.
        """
        if self._wire_stats is not None and not refresh:
            return self._wire_stats
        if self._train_step_fn is None or self._last_batch_struct is None:
            return None
        if self.gradient_accumulation_steps_ > 1:
            logger.warning(
                "collective_wire_stats: not emitted at gradient_accumulation"
                "_steps=%d — the HLO loop-trip attribution is only exact at "
                "gas=1 (audit a gas=1 config with tools/collective_audit.py "
                "instead)", self.gradient_accumulation_steps_)
            return None
        from ..profiling.collectives import audit_lowered

        lowered = self.lower_train_step()
        trip = getattr(self.module.config, "n_layers", 1) \
            if getattr(self.module.config, "scan_layers", False) else 1
        from ..profiling.sanitizer import ATTENTION_F32_ALLOW

        dtype = {jnp.bfloat16: "bf16", jnp.float16: "f16"}.get(
            self.compute_dtype, "f32")
        self._wire_stats = audit_lowered(
            lowered, self.dp_world_size * self.mp_world_size
            * self.pipe_stages * self.seq_parallel_size,
            loop_trip_count=trip,
            sanitizer_config={"compute_dtype": dtype,
                              "allow": list(ATTENTION_F32_ALLOW)})
        return self._wire_stats

    def _report_progress(self):
        """Reference ``engine.py:2167`` _report_progress."""
        log_dist(
            f"step={self.global_steps}, skipped={self.skipped_steps}, "
            f"lr={self._current_lr():.3e}, loss_scale={float(self._scale):.1f}",
            ranks=[0],
        )

    # ------------------------------------------------------------------------------
    # config accessors (reference engine.py:641-836 property farm)
    # ------------------------------------------------------------------------------
    @property
    def config(self):
        return self._config

    def train_batch_size(self):
        return self.train_batch_size_

    def train_micro_batch_size_per_gpu(self):
        return self.micro_batch_size

    def gradient_accumulation_steps(self):
        return self.gradient_accumulation_steps_

    def zero_optimization_stage(self):
        return self.zero_stage

    @property
    def loss_scale(self):
        return float(self._scale)

    def get_global_grad_norm(self):
        if self._acc_grads is None:
            return 0.0
        return float(global_grad_norm(self._acc_grads))

    def module_state_dict(self):
        """Reference ``engine.module_state_dict``: the module's weights as a
        host tree (consolidated across shards)."""
        return self.consolidated_16bit_state_dict()

    def consolidated_16bit_state_dict(self):
        """Live consolidated weights in the compute dtype (reference
        ``_zero3_consolidated_16bit_state_dict``, ``engine.py:3127``): gathers
        every (possibly ZeRO-3/TP-sharded) param to host as one numpy tree.
        Rank 0 returns the dict; other processes return None. Small/medium
        models only — a 13B tree will not fit one host; use the sharded
        checkpoint + ``consolidate`` offline tool instead."""
        params = self._offloaded.masters if self._offloaded is not None \
            else self.params
        if dist.get_rank() != 0 and jax.process_count() > 1:
            # participate in any cross-host gathers, drop the result
            jax.tree_util.tree_map(lambda a: np.asarray(jax.device_get(a)),
                                   params)
            return None
        cast = np.dtype(jnp.dtype(self.compute_dtype).name) \
            if self.compute_dtype != jnp.float32 else np.float32
        return jax.tree_util.tree_map(
            lambda a: np.asarray(jax.device_get(a)).astype(cast), params)

    # ------------------------------------------------------------------------------
    # checkpointing (reference engine.py:2493 load / :2798 save)
    # ------------------------------------------------------------------------------
    def capture_step_state(self, client_state=None):
        """The complete step state as a ``(state_tree, meta)`` pair — the
        single source of truth for what a checkpoint must carry so a resumed
        trajectory is CONTINUOUS: params + optimizer state (the tree), and in
        meta the counters, loss-scale/good-steps, the live rng key (bitwise
        stream continuity across restarts), the lr-scheduler state, and the
        health monitor's ring-buffer window (so spike/z-score detectors don't
        restart blind after a preemption). Also the capture point the elastic
        snapshot path reads every ``snapshot_interval`` steps."""
        if self._offloaded is not None:
            state = {
                "params": self._offloaded.masters,  # fp32 masters, not bf16 copies
                "optimizer_state": self._offloaded.state_for_checkpoint(),
            }
        else:
            state = {
                "params": self.params,
                "optimizer_state": self.optimizer_state,
            }
        meta = {
            "global_steps": self.global_steps,
            "micro_steps": self.micro_steps,
            "skipped_steps": self.skipped_steps,
            "loss_scale": float(self._scale),
            "good_steps": int(self._good_steps),
            "rng": np.asarray(self._rng).tolist(),
            "lr_scheduler": self.lr_scheduler.state_dict() if self.lr_scheduler else None,
            "zero_stage": self.zero_stage,
            "mesh": dict(self.mesh.shape),
            "client_state": client_state or {},
        }
        if self.health is not None and self.health.enabled:
            meta["health"] = self.health.state_dict()
        return state, meta

    def save_checkpoint(self, save_dir, tag=None, client_state=None):
        tag = tag or f"global_step{self.global_steps}"
        # all ranks must save the same tag/step or shard files interleave
        # (reference engine.py:2781 checkpoint tag validation)
        dist.assert_same_across_ranks(
            {"tag": np.frombuffer(tag.encode(), np.uint8),
             "step": self.global_steps}, name="checkpoint tag")
        state, meta = self.capture_step_state(client_state)
        path = os.path.join(save_dir, tag)
        with self.tracer.span("checkpoint/save", cat="checkpoint", tag=tag,
                              step=self.global_steps):
            with self.tracer.span("checkpoint/write", cat="checkpoint",
                                  step=self.global_steps):
                self.checkpoint_engine.save(state, path, meta=meta)
            with self.tracer.span("checkpoint/commit", cat="checkpoint",
                                  step=self.global_steps):
                self.checkpoint_engine.commit(tag)
        self.tracer.flush()
        log_dist(f"Saved checkpoint {path}", ranks=[0])
        return path

    def load_checkpoint(self, load_dir, tag=None, load_optimizer_states=True,
                        verify=True):
        with self.tracer.span("checkpoint/resume", cat="checkpoint",
                              tag=tag) as _resume_span:
            return self._load_checkpoint(load_dir, tag, load_optimizer_states,
                                         verify, _resume_span)

    def _load_checkpoint(self, load_dir, tag, load_optimizer_states, verify,
                         span):
        if tag is None:
            from ..checkpoint import atomic as ckpt_atomic

            tag = ckpt_atomic.read_latest(load_dir)
            if tag is not None and not os.path.isdir(
                    os.path.join(load_dir, tag)):
                # dangling pointer: the tag was quarantined/pruned out from
                # under it (routine after try_resume's recovery walk)
                log_dist(f"checkpoint 'latest' points at missing tag "
                         f"{tag!r} — falling back to newest published tag",
                         ranks=[0])
                tag = None
            if tag is None:
                # newest published tag; stale .tmp stages and quarantined
                # .corrupt dirs are never resume targets
                tags = ckpt_atomic.list_tags(load_dir, newest_first=True)
                if not tags:
                    return None, {}
                tag = tags[0]
        path = os.path.join(load_dir, tag)
        # the marker records the writing mesh — read it up front so a
        # rescaled resume traces as checkpoint/reshard (the region reads
        # through _parse_ranges onto the new mesh's shardings ARE the
        # reshard work), an equal-scale one as checkpoint/load
        from ..checkpoint import atomic as ckpt_atomic

        marker = ckpt_atomic.read_marker(path)
        marker_mesh = marker.get("mesh") if marker else None
        reshard = bool(marker_mesh
                       and dict(marker_mesh) != dict(self.mesh.shape))
        load_span = "checkpoint/reshard" if reshard else "checkpoint/load"
        if self._offloaded is not None:
            template = {"params": self._offloaded.masters,
                        "optimizer_state": self._offloaded.state_for_checkpoint()}
            with self.tracer.span(load_span, cat="checkpoint", tag=tag):
                state, meta = self.checkpoint_engine.load(path,
                                                          template=template,
                                                          shardings=None,
                                                          verify=verify)
            self._offloaded.load_masters(state["params"])
            if load_optimizer_states:
                self._offloaded.load_state(state["optimizer_state"])
            self.params = self._offloaded._device_params()
        else:
            template = {"params": self.params, "optimizer_state": self.optimizer_state}
            shardings = {"params": self.param_shardings,
                         "optimizer_state": self._opt_shardings}
            with self.tracer.span(load_span, cat="checkpoint", tag=tag):
                state, meta = self.checkpoint_engine.load(path,
                                                          template=template,
                                                          shardings=shardings,
                                                          verify=verify)
            self.params = state["params"]
            if load_optimizer_states:
                self.optimizer_state = state["optimizer_state"]
        self.global_steps = meta["global_steps"]
        self.micro_steps = meta["micro_steps"]
        self.skipped_steps = meta["skipped_steps"]
        self._scale = jnp.asarray(meta["loss_scale"], jnp.float32)
        self._good_steps = jnp.asarray(meta["good_steps"], jnp.int32)
        if meta.get("rng") is not None:
            # bitwise stream continuity: the restored trajectory folds the
            # SAME dropout/noise keys the uninterrupted run would have
            self._rng = jnp.asarray(np.asarray(meta["rng"], np.uint32))
        if self.health is not None and self.health.enabled \
                and meta.get("health"):
            # ring-buffer carry: the spike/z-score detectors resume with the
            # pre-preemption window instead of restarting blind
            self.health.load_state_dict(meta["health"])
        if self.lr_scheduler is not None and meta.get("lr_scheduler"):
            self.lr_scheduler.load_state_dict(meta["lr_scheduler"])
        # one source of truth for "rescaled": the marker mesh that chose the
        # span name, falling back to the meta mesh only for marker-less
        # (legacy) tags — the span and the Elastic/resumes_rescaled counter
        # must never contradict each other
        saved_mesh = marker_mesh or meta.get("mesh")
        self._last_resume_rescaled = bool(
            saved_mesh and dict(saved_mesh) != dict(self.mesh.shape))
        if self._last_resume_rescaled:
            log_dist(
                f"Checkpoint {tag} was written on mesh {dict(saved_mesh)} — "
                f"resharded onto {dict(self.mesh.shape)} "
                f"(params + ZeRO optimizer state)", ranks=[0])
        span.set(tag=tag, step=self.global_steps,
                 rescaled=self._last_resume_rescaled)
        log_dist(f"Loaded checkpoint {path} at step {self.global_steps}", ranks=[0])
        return path, meta.get("client_state", {})
