"""Continuous-batching serving engine (the tentpole of the serving layer).

Orca-style iteration-level scheduling, TPU-native by construction: ONE jitted
decode program runs over a **fixed pool of batch slots** (static shapes,
compiled exactly once per (model, pool) configuration). Each slot holds one
request's block-table row, cursor, last token, rng key and sampling knobs —
all as per-slot device arrays; the KV rows live in ONE store, the paged
block pool behind ``KVPoolManager`` (``serving/kv_pool.py``). A finished
request frees its slot and blocks mid-flight and a queued one is prefilled
(the existing bucketed ``prefill_flash`` path, over a dense b=1 scratch
cache) and its blocks written into the RUNNING decode batch's pool
(``models/decoding.py:insert_block_kv``). No recompilation, no waiting for
the whole batch to drain — the serving-side half of DeepSpeed-Inference's
latency/throughput story (arXiv:2207.00032) on top of the kernel path.

Greedy streams are bitwise-identical to sequential ``generate()`` calls
where the view serves (the same per-row attention math at the same
positions over the same KV window; pinned in tier-1
``tests/unit/test_serving.py``, ``test_kv_pool.py``).
"""

import collections
import dataclasses
from collections import OrderedDict

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..config.base import ConfigError
from ..inference.engine import lru_compiled
from ..models.decoding import (extract_slot_blocks, forward_with_cache,
                               forward_with_paged_cache, gather_slot_cache,
                               init_cache, init_paged_cache,
                               insert_block_kv, reset_block_kv,
                               sample_token_per_request,
                               verify_with_paged_cache, write_pool_blocks)
from ..ops.pallas.kv_block_write import blocks_in_lanes
from ..utils.logging import log_dist
from .clock import VirtualClock, WallClock
from .kv_pool import (GARBAGE_BLOCK, KVPoolManager, WindowGroupManager,
                      prefix_chain_keys)
from .migration import RequestSnapshot, advance_rng
from .metrics import ServingMetrics
from .queue import RequestQueue
from .request import (CLASS_BATCH, CLASS_INTERACTIVE, FINISH_EOS,
                      FINISH_LENGTH, FINISH_STOP, FINISH_UNHEALTHY,
                      REJECT_DEGRADED, Request, RequestState, TokenEvent,
                      as_request)
from .scheduler import ServingScheduler
from .state_cache import recurrent_state


@dataclasses.dataclass
class _PrefillJob:
    """A prompt prefill in flight across scheduler steps (chunked prefill
    and/or preemption resume). The job owns its reserved slot and the
    partially-filled dense b=1 cache between chunks; ``pos`` is the next
    prompt position to prefill (``ids`` = prompt, or prompt + already-
    generated tokens on a resume replay). ``ahead``: the next chunk when it
    was dispatched a step early (``_dispatch_chunk_ahead``): its size and
    what its program handed out; ``cache`` is then already that chunk's."""

    req: object
    slot: int
    cache: dict
    ids: np.ndarray          # full token sequence to prefill
    pos: int                 # next position to write (starts at shared_len)
    shared_len: int
    shared_blocks: list
    resume: bool             # replaying a preempted request: no first-token
    #                          sampling, stream/metrics continue where left
    ahead: tuple = None      # (n, padded, program outputs) of the next chunk

    @property
    def done(self):
        return self.pos >= len(self.ids)


class ServingEngine:
    """Continuous batching over an ``InferenceEngine``'s weights: a fixed
    pool of batch slots whose KV lives in the paged block pool."""

    def __init__(self, engine, serving_config=None, clock=None, monitor=None,
                 tracer=None):
        if not hasattr(engine.module, "config"):
            raise ConfigError(
                "serving needs a zoo-style model (config with kv cache "
                "geometry); an injection-policy-served unknown model "
                "supports forward() scoring only")
        self.engine = engine
        self.cfg = serving_config if serving_config is not None \
            else engine.config.serving
        self.n_slots = int(self.cfg.n_slots)
        self.max_len = int(self.cfg.max_len) or int(engine.config.max_tokens)
        if self.max_len > engine.config.max_tokens:
            raise ConfigError(
                f"serving.max_len {self.max_len} exceeds inference "
                f"max_tokens {engine.config.max_tokens}")
        self.clock = clock if clock is not None else (
            VirtualClock() if self.cfg.virtual_clock else WallClock())
        mcfg = engine.module.config
        # a model that routes drop-free hands its expert ids out of the
        # prefill and decode programs (load counters; Request.record_routing)
        self._routing = getattr(mcfg, "n_experts", 0) > 0 \
            and getattr(mcfg, "moe_routing", "") == "dropfree"
        self._latent = bool(getattr(mcfg, "latent_attention", False))
        # window and full attention layers mixed (models/window_moe.py):
        # two block groups, the window layers' a ring a slot
        self._window = bool(getattr(mcfg, "window_layers", False))
        if self._window:
            from ..models.window_moe import layer_groups

            # (window, full): the model's layers of each kind
            self._layer_groups = layer_groups(mcfg)
        if self._latent or self._window:
            self._refuse_for_cache_family(engine)
        # a model with Mamba layers (models/hybrid.py) holds a recurrent
        # state a slot beside the K/V blocks: ONE object says what it holds,
        # inserts, dispatches ahead and refuses (serving/state_cache.py)
        self._recurrent = recurrent_state(mcfg, self.n_slots, engine.dtype,
                                          lambda: len(self._slots))
        self._recurrent.refuse(self.cfg, engine.mp_world_size)
        # the KV store: block allocator + prefix cache on the host, a block
        # table on the device (serving/kv_pool.py)
        self.pool_mgr = KVPoolManager(self.cfg.kv_pool, self.n_slots,
                                      self.max_len)
        self.window_mgr = WindowGroupManager(
            self.cfg.kv_pool, self.n_slots, mcfg.sliding_window) \
            if self._window else None
        # which decode attention runs is this engine's choice, from what it
        # can observe: "kernel" (the flash-decode kernel walks the block
        # table and reads the live blocks only) or "view" (the n_slots x
        # max_len gather view through the table, then the dense attention).
        # The kernel is put to the compiler ONCE here, at this engine's
        # geometry; where it is refused ``attn_reason`` says why and the
        # view serves. ``attn_backend`` (also in snapshot()["kv_pool"])
        # always names the path that runs; a speculative verify step takes
        # the view whatever decode takes.
        self._decode_dispatches = {"kernel": 0, "view": 0}
        # of those, the decodes dispatched behind the step before theirs
        self._decode_ahead_dispatches = 0
        # chunk programs by the body their attention's key blocks were
        # traced with: the chunk kernel or XLA's; by suffix bucket, what the
        # trace took (``ops/pallas/chunk_attention.py:traced_paths``)
        self._chunk_attention_dispatches = {"kernel": 0, "xla": 0}
        self._chunk_attention_paths = {}
        self.attn_backend, self.attn_reason = self._choose_attention(engine)
        if self.cfg.scrub_freed_slots:
            # zero each physical block as its last reference drops
            self.pool_mgr._scrub = self._scrub_block
        # chunked prefill: long prompts prefill in fixed-token chunks
        # interleaved with decode steps (bounded co-batched TPOT); each chunk
        # is one suffix-prefill call against the request's partial cache
        self.chunked = bool(self.cfg.chunked_prefill.enabled)
        # disaggregated-fleet role (serving.pools): assigned by the Router
        # via set_pool_role after construction — "mixed" (default), or
        # "prefill"/"decode" with optional per-pool chunk-size override
        # (0 = the shared chunked_prefill.chunk_size)
        self.pool_role = "mixed"
        self.chunk_size_override = 0
        # on-demand block growth: admission reserves prompt blocks, decode
        # blocks are allocated as cursors advance, and pool exhaustion
        # preempts the newest request back to the queue
        self.growth = bool(self.cfg.kv_pool.on_demand_growth)
        self._prefill_jobs = collections.deque()
        # a decode dispatched behind the last step's, for the NEXT step to
        # read (``_dispatch_decode_ahead``): its outputs, or None
        self._decode_ahead = None
        self._decode_steps_since_chunk = 1 << 30  # first chunk never waits
        self._admit_seq = 0    # admission order (preemption victim = newest)
        # speculative decoding (serving/speculative.py): a drafter proposes
        # up to k tokens per greedy slot, ONE verify forward checks them,
        # the longest agreeing prefix is accepted; rollback rides the block
        # machinery.
        self.spec = bool(self.cfg.speculative.enabled)
        self.spec_k = int(self.cfg.speculative.k)
        self._spec_on = self.spec   # runtime toggle (set_speculation)
        self._drafter = None
        if self.spec:
            from .speculative import build_drafter

            self._drafter = build_drafter(self)
        self.queue = RequestQueue(self.cfg.max_queue_depth)
        self.scheduler = ServingScheduler(
            self.queue, self.n_slots,
            max_prefills_per_step=self.cfg.max_prefills_per_step,
            policy=self.cfg.policy,
            hol_bypass_limit=self.cfg.hol_bypass_limit,
            tenants=self.cfg.tenants if self.cfg.tenants.enabled else None)
        if monitor is None:
            mc = engine.config
            if (mc.tensorboard.enabled or mc.wandb.enabled
                    or mc.csv_monitor.enabled
                    or getattr(mc, "telemetry", None) is not None
                    and mc.telemetry.enabled):
                from ..monitor.monitor import MonitorMaster

                monitor = MonitorMaster(mc)
        self.metrics = ServingMetrics(self.n_slots, self.clock,
                                      monitor=monitor,
                                      interval=self.cfg.monitor_interval,
                                      kv_pool=self._kv_pool_stats,
                                      slo=self.cfg.slo)
        # numerics watchdog (the serving leg of telemetry/health.py): the
        # decode program ALWAYS emits the per-slot nonfinite-logit count
        # (so the sanitizer budget audits the real program); the shed hook
        # and Serving/health_* consumers arm on the inference config's
        # health block
        hcfg = getattr(engine.config, "health", None)
        self._health_shed = bool(hcfg is not None and hcfg.enabled)
        # request-lifecycle tracing AGAINST THE SCHEDULER CLOCK: under a
        # virtual clock the trace timestamps are virtual time, which is what
        # makes trace-derived TTFT/TPOT bit-identical to ServingMetrics
        from ..telemetry import SpanTracer

        self.tracer = tracer if tracer is not None else SpanTracer.from_config(
            getattr(engine.config, "telemetry", None), clock=self.clock.now,
            meta={"process": "serving", "n_slots": self.n_slots,
                  "max_len": self.max_len})
        # the structured slo/violation events ride the request tracer
        self.metrics.tracer = self.tracer
        # arms the Serving/spec_* monitor events (coherent with
        # snapshot()["speculative"], the PR 4 trace==metrics discipline)
        self.metrics.speculative_armed = self.spec
        self.metrics.moe_armed = self._routing
        self.metrics.ssm = self._recurrent.snapshot
        if self._routing and mcfg.held_experts[1] != mcfg.n_experts:
            # the expert layers hold a share of their experts
            self.metrics.moe_held = mcfg.held_experts
        # expert loads of this step's prefill chunk, still on the device:
        # read after the step's token read-back, never before it
        self._pending_loads = []
        # per-tenant SLO grading reads the class ttft overrides
        if self.cfg.tenants.enabled:
            self.metrics.tenants_cfg = self.cfg.tenants
        # degraded-mode ladder (serving.degraded): the engine-local control
        # loop — submit() consults it for class sheds + token caps, step()
        # drives its evaluation cadence, transitions toggle speculation
        self.degraded_ctl = None
        if self.cfg.degraded.enabled:
            from .control import DegradedModeController

            self.degraded_ctl = DegradedModeController(
                self.cfg.degraded, self.cfg.slo, self.metrics,
                tracer=self.tracer, engine=self)
            self.metrics.degraded = lambda: self.degraded_ctl.level
            self.metrics.degraded_snapshot = self.degraded_ctl.snapshot
        # priority preemption: step()s to skip re-attempting after an
        # eviction freed too few blocks for the interactive candidate
        # (prevents evict/re-admit ping-pong against a tight pool)
        self._pp_cooldown = 0

        self._slots = {}              # slot index -> running Request
        self._free_slots = list(range(self.n_slots - 1, -1, -1))  # pop() -> 0 first
        self._next_id = 0
        self._prefill_programs = OrderedDict()   # padded_len -> jitted prefill
        self._suffix_programs = OrderedDict()    # padded suffix -> jitted
        self._decode_jit = None
        self._insert_jit = None
        self._release_jit = None
        self._sample_first_jit = None
        self._insert_block_jit = None    # copy a request's blocks into the pool
        self._seed_cache_jit = None      # block table row -> dense view
        self._scrub_jit = None           # zero one physical block
        self._fresh_cache_jit = None     # chunked: zeroed dense b=1 cache
        self._grow_jit = None            # growth: append one table-row block
        self._verify_jit = None          # speculative: one-forward verify
        self._migrate_in_jit = None      # int8 migration: raw block splice
        # ONE sharding for the pool state, pinned as out_shardings on every
        # pool program: kv heads over the model axis (TP), everything else
        # replicated. Without the pin, insert and decode outputs would carry
        # different inferred shardings and each insert<->decode alternation
        # would recompile — the exact thing the slot pool exists to avoid.
        # ``_cache_sharding`` is the dense b=1 prefill cache's.
        mesh = engine.mesh
        from ..parallel import MODEL_AXIS

        kvh = engine.module.config.cache_geometry["k"][0]
        kv_axis = MODEL_AXIS if kvh % max(engine.mp_world_size, 1) == 0 \
            else None
        self._cache_sharding = NamedSharding(
            mesh, P(None, None, None, kv_axis, None))
        # the pool stores a token's kv heads merged with the head size (and
        # an int8 pool's scales one a head): the same axis, 3, splits into
        # contiguous groups of heads
        self._pool_sharding = NamedSharding(
            mesh, P(None, None, None, kv_axis))
        self._rep_sharding = NamedSharding(mesh, P())
        kv_names = self._pool_leaf_names()
        self._state_shardings = {
            name: self._pool_sharding if name in kv_names
            else self._rep_sharding
            for name in kv_names + (
                "table", "pos", "tok", "active", "remaining", "rng", "temp",
                "top_k", "top_p", "eos")
            + (("wtable",) if self._window else ())
            + self._recurrent.names}
        self._state = self._init_state()
        # the KV window is not n_slots x max_len: report the REAL capacity
        # (blocks and tokens) so operators see the effective slot multiplier
        mgr = self.pool_mgr
        cap = mgr.allocatable * mgr.block_size
        log_dist(
            f"ServingEngine: {self.n_slots} slots, paged KV pool "
            f"{mgr.allocatable} blocks x {mgr.block_size} tok = {cap} "
            f"tokens ({cap / self.max_len:.1f} max-len-equivalent slots"
            f", kv_dtype={self.cfg.kv_pool.kv_dtype or 'engine'}, "
            f"attention={self.attn_backend}, "
            f"prefix_cache={'on' if self.cfg.kv_pool.prefix_cache else 'off'}), "
            + (f"speculative={self.cfg.speculative.drafter}/k="
               f"{self.spec_k}, " if self.spec else "")
            + f"queue depth {self.cfg.max_queue_depth}, "
            f"clock={'virtual' if isinstance(self.clock, VirtualClock) else 'wall'}",
            ranks=[0])

    def _choose_attention(self, engine):
        """``(path, reason)`` of the decode program: the kernel where
        the model, the pool and the compiler allow it, else the view with
        the reason (``ops/pallas/paged_attention.fused_decode_supported``)."""
        from ..ops.pallas.paged_attention import fused_decode_supported

        mcfg = engine.module.config
        probe = dict(n_slots=self.n_slots, tp=max(engine.mp_world_size, 1),
                     kv_dtype=self.cfg.kv_pool.kv_dtype)
        # the layers whose K/V the pool holds (a model with recurrent layers:
        # its attention layers alone; None: every layer)
        groups = {False: dict(n_layers=self._recurrent.kv_layers)}
        if self._latent:
            # the latent form, probed at the pool the engine really holds
            groups = {False: dict(n_layers=mcfg.n_layers,
                                  n_blocks=self.pool_mgr.n_blocks)}
        if self._window:
            # each group probed at the leaves it really holds
            win, full = self._layer_groups
            groups = {False: dict(n_layers=len(full),
                                  n_blocks=self.pool_mgr.n_blocks),
                      True: dict(n_layers=len(win),
                                 n_blocks=self.window_mgr.n_blocks)}
        ok, reason = fused_decode_supported(
            mcfg, self.pool_mgr.block_size,
            blocks_per_slot=self.pool_mgr.blocks_per_slot, **probe,
            **groups.get(False, {}))
        if ok and self._window:
            # the window layers' calls have their own program: the band,
            # over a table as wide as the ring
            ok, reason = fused_decode_supported(
                mcfg, self.pool_mgr.block_size,
                blocks_per_slot=self.window_mgr.ring,
                window=mcfg.sliding_window, ring=True, **probe,
                **groups[True])
        return ("kernel", "") if ok else ("view", reason)

    def _refuse_for_cache_family(self, engine):
        """What this engine cannot do with a model whose cache is not one
        group of per-head K and V refuses here, by name, instead of
        computing something else. Latent attention: the cache holds one
        latent row a token, which only the pool in the engine's dtype, read
        on one model shard, knows. Window and full
        attention layers: two block groups and two tables, which the prefix
        cache (it would share a ring's blocks, overwritten in place), the
        verify step, the int8 pool, growth, the scrub and the snapshot do
        not know."""
        cfg = self.cfg
        family = "latent attention" if self._latent \
            else "window and full attention layers"
        why = None
        if self._window and cfg.kv_pool.prefix_cache:
            why = ("the prefix cache over window blocks "
                   "(serving.kv_pool.prefix_cache)")
        elif self._window and cfg.kv_pool.on_demand_growth:
            why = ("on-demand block growth "
                   "(serving.kv_pool.on_demand_growth)")
        elif self._window and cfg.scrub_freed_slots:
            why = "the freed-block scrub (serving.scrub_freed_slots)"
        elif cfg.kv_pool.kv_dtype == "int8":
            why = "an int8 pool (serving.kv_pool.kv_dtype='int8')"
        elif cfg.speculative.enabled:
            why = "speculative verify (serving.speculative.enabled)"
        elif engine.mp_world_size > 1:
            why = f"tensor parallel {engine.mp_world_size} (tp_size > 1)"
        elif cfg.migration.snapshot_interval_tokens > 0:
            why = ("live KV migration (serving.migration."
                   "snapshot_interval_tokens > 0)")
        if why is not None:
            raise ValueError(
                f"ServingEngine: {family} does not implement {why}")

    @property
    def chunk_size(self):
        """Effective chunked-prefill chunk size: the per-pool override when
        the Router specialized this replica (serving.pools.*_chunk_size),
        else the shared ``chunked_prefill.chunk_size``."""
        return self.chunk_size_override or self.cfg.chunked_prefill.chunk_size

    def set_pool_role(self, role, chunk_size=0, speculation=""):
        """Assign this replica's disaggregated-pool role (Router-driven,
        ``serving.pools``): records the role for the banner/snapshot,
        applies the per-pool chunk-size override (0 = inherit) and the
        speculation override (""/"on"/"off"). Chunk size only changes the
        SCHEDULE (chunks ride the bucketed suffix programs) and speculation
        toggling never perturbs a seeded stream, so pool specialization
        cannot change any committed token."""
        if role not in ("mixed", "prefill", "decode"):
            raise ValueError(f"unknown pool role {role!r}")
        if role != "mixed":
            self._recurrent.refuse_feature(
                f"the disaggregated hand-off (pool role {role!r})")
        if role != "mixed" and (self._latent or self._window):
            raise ValueError(
                "ServingEngine: latent attention and window and full "
                "attention layers do not implement the "
                f"disaggregated hand-off (pool role {role!r})")
        self.pool_role = role
        self.chunk_size_override = int(chunk_size)
        if speculation:
            self.set_speculation(speculation == "on")
        log_dist(
            f"ServingEngine: pool role {role} "
            f"(chunk_size={self.chunk_size}"
            f"{'*' if self.chunk_size_override else ''}, "
            f"speculation={'on' if self._spec_on else 'off'})", ranks=[0])

    def block_until_idle(self):
        """Wait for every program this engine has dispatched. ``step()``
        returns with its tokens read, and a chunk dispatched ahead for the
        next step may still be running: a profiler started or stopped then
        would cut it."""
        jax.block_until_ready(
            (self._state, [job.cache for job in self._prefill_jobs]))

    def pool_layouts(self):
        """``{leaf: major_to_minor}`` of the live cache leaves as the device
        keeps them: what decides which block writer runs and what a write
        costs (PR 27); None where the backend tells no layout."""
        out = {}
        for name in self._pool_leaf_names():
            layout = getattr(getattr(self._state[name], "format", None),
                             "layout", None)
            out[name] = None if layout is None \
                else tuple(layout.major_to_minor)
        return out

    def _kv_pool_stats(self):
        """``KVPoolManager.stats()`` + the decode attention that runs, why
        (where the view serves) and the decode dispatches by path (a
        speculative verify step counts as ``view``): the kv_pool block every
        consumer reads (``snapshot()["kv_pool"]``, Serving/* events, bench
        artifacts), so committed numbers always record WHICH decode path
        produced them."""
        st = self.pool_mgr.stats()
        st["attention_backend"] = self.attn_backend
        st["attention_reason"] = self.attn_reason
        st["decode_dispatches"] = dict(self._decode_dispatches)
        st["decode_ahead_dispatches"] = self._decode_ahead_dispatches
        if self._chunk_attention_paths:
            st["chunk_attention_dispatches"] = dict(
                self._chunk_attention_dispatches)
        # the attention layers' blocks, and the slots' recurrent state
        st.update(self._recurrent.groups(
            st, self.metrics.latent_kv_tokens_read))
        if self._window:
            # by group: the blocks each holds, those of the live requests
            # (the slots' bindings), and the K/V rows the decode steps read
            # in a layer of each kind, booked from the cursors
            st["groups"] = {
                "full": {"layers": len(self._layer_groups[1]),
                         "allocated_blocks": st["allocated_blocks"],
                         "rows_read_per_layer":
                         self.metrics.latent_kv_tokens_read},
                "window": dict(self.window_mgr.stats(),
                               layers=len(self._layer_groups[0]),
                               rows_read_per_layer=self.metrics
                               .kv_window_rows_read)}
        return st

    # ------------------------------------------------------------------ state
    def _init_state(self):
        cfg = self.engine.module.config
        s = self.n_slots
        mgr = self.pool_mgr
        if self._window:
            # two groups: every token of a request in the full layers', a
            # ring a slot in the window layers'
            wmgr = self.window_mgr
            cache = init_paged_cache(cfg, mgr.n_blocks, mgr.block_size,
                                     self.engine.dtype,
                                     n_layers=len(self._layer_groups[1]),
                                     geometry=cfg.group_pool_geometry(False))
            ring = init_paged_cache(cfg, wmgr.n_blocks, mgr.block_size,
                                    self.engine.dtype,
                                    n_layers=len(self._layer_groups[0]),
                                    geometry=cfg.group_pool_geometry(True))
            cache.update(wk=ring["k"], wv=ring["v"],
                         wtable=jnp.full((s, wmgr.ring), GARBAGE_BLOCK,
                                         jnp.int32))
        else:
            cache = init_paged_cache(cfg, mgr.n_blocks, mgr.block_size,
                                     self.engine.dtype,
                                     self.cfg.kv_pool.kv_dtype or None,
                                     n_layers=self._recurrent.kv_layers)
        state = dict(cache, **self._recurrent.leaves(), **{
            # every slot starts parked on the garbage block: a dead decode
            # write can never land in an allocatable block
            "table": jnp.full((s, mgr.blocks_per_slot), GARBAGE_BLOCK,
                              jnp.int32),
            "pos": jnp.zeros((s,), jnp.int32),        # next KV write cursor
            "tok": jnp.zeros((s,), jnp.int32),        # last sampled token
            "active": jnp.zeros((s,), jnp.bool_),
            "remaining": jnp.zeros((s,), jnp.int32),  # decode steps left
            "rng": jnp.zeros((s, 2), jnp.uint32),     # per-slot PRNG keys
            "temp": jnp.zeros((s,), jnp.float32),
            "top_k": jnp.zeros((s,), jnp.int32),
            "top_p": jnp.ones((s,), jnp.float32),
            "eos": jnp.full((s,), -1, jnp.int32),     # -1 = no eos
        })
        return {name: jax.device_put(a, self._state_shardings[name])
                for name, a in state.items()}

    # -------------------------------------------------------------- programs
    def _prefill_program(self, padded_len):
        """One compiled prefill per prompt bucket (same LRU bound as the
        engine's generate cache)."""
        model, max_len, dtype = self.engine.module, self.max_len, self.engine.dtype

        def build():
            def prefill(params, ids, true_len):
                c = init_cache(model.config, 1, max_len, dtype)
                logits, c = forward_with_cache(model, params, ids, c, 0,
                                               max_len, prefill=True)
                last = jax.lax.dynamic_slice_in_dim(
                    logits, true_len - 1, 1, axis=1)[:, 0]
                return last, c

            def prefill_routed(params, ids, true_len):
                c = init_cache(model.config, 1, max_len, dtype)
                logits, c, routed = forward_with_cache(
                    model, params, ids, c, 0, max_len, prefill=True,
                    last_index=true_len - 1, return_routing=True)
                return logits[:, 0], c, self._routed_out(routed, true_len)

            cache_sh = self._dense_cache_shardings()
            with self.engine.mesh:
                if self._routing:
                    return jax.jit(prefill_routed, out_shardings=(
                        self._rep_sharding, cache_sh,
                        (self._rep_sharding, self._rep_sharding)))
                return jax.jit(prefill, out_shardings=(
                    self._rep_sharding, cache_sh))

        return lru_compiled(self._prefill_programs, padded_len, build,
                            int(self.engine.config.compile_cache_size or 0),
                            "serving prefill")

    def _suffix_program(self, padded_len):
        """Shared-prefix hit: prefill only the SUFFIX (cache already holds
        the prefix KV gathered from shared blocks) — one compiled program
        per suffix bucket, start position and true length traced."""
        from ..ops.pallas.chunk_attention import traced_paths

        model, max_len = self.engine.module, self.max_len

        def forward(*args, **kwargs):
            # every layer's key blocks in the chunk kernel, or not
            with traced_paths() as seen:
                out = forward_with_cache(*args, **kwargs)
            if seen:
                # booked by chunk where the model has a chunk attention
                self._chunk_attention_paths[padded_len] = \
                    "kernel" if seen == {"kernel"} else "xla"
            return out

        def build():
            def suffix_prefill(params, ids, cache, start_pos, true_len):
                logits, c = forward(model, params, ids, cache, start_pos,
                                    max_len)
                last = jax.lax.dynamic_slice_in_dim(
                    logits, true_len - 1, 1, axis=1)[:, 0]
                return last, c

            def suffix_routed(params, ids, cache, start_pos, true_len):
                logits, c, routed = forward(
                    model, params, ids, cache, start_pos, max_len,
                    last_index=true_len - 1, return_routing=True)
                return logits[:, 0], c, self._routed_out(routed, true_len)

            cache_sh = self._dense_cache_shardings()
            with self.engine.mesh:
                if self._routing:
                    return jax.jit(suffix_routed, donate_argnums=(2,),
                                   out_shardings=(
                                       self._rep_sharding, cache_sh,
                                       (self._rep_sharding,
                                        self._rep_sharding)))
                return jax.jit(suffix_prefill, donate_argnums=(2,),
                               out_shardings=(self._rep_sharding, cache_sh))

        return lru_compiled(self._suffix_programs, padded_len, build,
                            int(self.engine.config.compile_cache_size or 0),
                            "serving suffix prefill")

    def _dense_cache_shardings(self):
        """``{leaf: sharding}`` of a request's dense b=1 cache: K and V by
        head, a recurrent state replicated."""
        return {"k": self._cache_sharding, "v": self._cache_sharding,
                **{name: self._rep_sharding
                   for name in self._recurrent.names}}

    def _routed_out(self, routed, true_len):
        """What a prefill program of a routing model hands out beside its
        logits: the chosen expert ids [L_moe, q, k] and, made here where the
        ids are, the pairs per expert over the ``true_len`` real rows
        [L_moe, E] (bucket padding routes too, and is not counted)."""
        from ..moe.dropfree import load_counts, routed_ids

        routed = routed[:, 0]
        real = jnp.arange(routed.shape[1])[None, :, None] < true_len
        counts = load_counts(
            jnp.where(real, routed_ids(routed),
                      self.engine.module.config.n_experts),
            self.engine.module.config.n_experts)
        return routed, counts

    def _prefill_dispatch(self, program, req, start, n, *args):
        """Run one prefill program (a whole prompt, a suffix or a chunk)
        and book what a routing model hands out beside logits and cache."""
        return self._book_prefill(req, start, n, program(*args))

    def _book_prefill(self, req, start, n, out):
        """``(logits, cache)`` of a prefill program's outputs; a routing
        model's loads and, where asked for, its choices are booked."""
        if not self._routing:
            return out
        logits, cache, (routed, counts) = out
        self._pending_loads.append(counts)
        self._book_product_path(routed)
        if req.record_routing:
            req.routing.append((start, n, routed))
        return logits, cache

    def _book_product_path(self, routed):
        """``routed`` [L_moe, rows, 2k] of a program just dispatched (its
        shape alone is read): book its expert layers under the grouped
        product its trace chose (``moe/dropfree.py:product_path``)."""
        from ..moe.dropfree import product_path

        cfg = self.engine.module.config
        n_layers, rows = routed.shape[0], routed.shape[1]
        self.metrics.record_moe_product(
            product_path(rows * cfg.moe_top_k, cfg.attention_interpret,
                         cfg.mesh), n_layers)

    def _build_pool_programs(self):
        model, max_len = self.engine.module, self.max_len
        kernel = self.attn_backend == "kernel"
        bs = self.pool_mgr.block_size
        pool_keys = self._pool_leaf_names()
        window = self._window
        recurrent = self._recurrent
        # a model with window layers reads through two tables
        tables = lambda state: (state["table"], state["wtable"]) \
            if window else state["table"]
        carried = ("wtable",) if window else ()
        # how write_pool_blocks reaches the pool: by the layout the device
        # gave it, read off the live array
        writer = dict(lanes=blocks_in_lanes(self._state["k"]),
                      mesh=self.engine.mesh,
                      interpret=model.config.attention_interpret)

        def decode(params, state):
            # one token for EVERY slot, each at its own cursor; inactive
            # slots decode garbage into the reserved garbage block their
            # table row points at and are masked below
            split = jax.vmap(jax.random.split)(state["rng"])  # [S, 2, 2]
            # a routing model also hands out what its expert layers chose,
            # [L_moe, S, 1, 2k]
            logits, cache, *routed = forward_with_paged_cache(
                model, params, state["tok"][:, None],
                {k: state[k] for k in pool_keys + recurrent.names},
                tables(state),
                state["pos"], bs, kernel=kernel,
                return_routing=self._routing)
            # in-graph health: per-slot nonfinite-logit count (the serving
            # leg of the numerics flight recorder — one tiny i32[S] side
            # output, no host callback; the sanitizer budget audits it)
            nonfinite = jnp.sum(
                jnp.logical_not(jnp.isfinite(logits[:, 0])),
                axis=-1).astype(jnp.int32)
            # the sampler does the work the LIVE rows ask for (a freed slot
            # keeps the knobs of the request that left it) and says which
            # arm that was; the rng split above stays outside its branches
            active = state["active"]
            nxt, sampled = sample_token_per_request(
                logits[:, 0], split[:, 0], temperature=state["temp"],
                top_k=state["top_k"], top_p=state["top_p"], live=active)
            nxt = jnp.where(active, nxt, state["tok"])
            remaining = state["remaining"] - active.astype(jnp.int32)
            hit_eos = (state["eos"] >= 0) & (nxt == state["eos"])
            done_now = active & (hit_eos | (remaining <= 0))
            new_state = dict(cache, **{
                "table": state["table"],
                "pos": state["pos"] + active.astype(jnp.int32),
                "tok": nxt,
                "active": active & jnp.logical_not(done_now),
                "remaining": remaining,
                "rng": split[:, 1],
                "temp": state["temp"], "top_k": state["top_k"],
                "top_p": state["top_p"], "eos": state["eos"],
                **{k: state[k] for k in carried},
            })
            # the routing is an output of its own, [L_moe, S, 2k], read back
            # with the tokens
            return (nxt, done_now, nonfinite, sampled,
                    *(r[:, :, 0] for r in routed)), new_state

        def verify(params, state, drafts, draft_len):
            # speculative decoding's ONE target forward: k+1 positions per
            # slot — row 0 is the decode every active slot was owed, rows
            # 1..k check the drafts. Greedy acceptance, cursor advance,
            # remaining/eos bookkeeping all happen IN-GRAPH, so a verify
            # step is exactly one dispatch (the program the serving-verify
            # sanitizer budget audits) and the rng splits exactly once —
            # a co-batched sampled slot cannot tell verify from decode.
            split = jax.vmap(jax.random.split)(state["rng"])
            ids = jnp.concatenate([state["tok"][:, None], drafts], axis=1)
            logits, cache = verify_with_paged_cache(
                model, params, ids, {k: state[k] for k in pool_keys},
                state["table"], state["pos"], bs, draft_len)
            active = state["active"]
            kk = drafts.shape[1]
            # column 0 samples with the slot's key (greedy rows are exact
            # argmax inside the sampler); columns 1..k are greedy targets
            # — only greedy rows ever carry drafts (engine eligibility)
            first, sampled = sample_token_per_request(
                logits[:, 0], split[:, 0], temperature=state["temp"],
                top_k=state["top_k"], top_p=state["top_p"], live=active)
            tgt = jnp.argmax(logits.astype(jnp.float32),
                             axis=-1).astype(jnp.int32)
            out_toks = jnp.concatenate([first[:, None], tgt[:, 1:]], axis=1)
            # accept the longest prefix where draft == target argmax
            matches = (drafts == out_toks[:, :kk]) \
                & (jnp.arange(kk)[None, :] < draft_len[:, None])
            accepted = jnp.sum(
                jnp.cumprod(matches.astype(jnp.int32), axis=1), axis=1)
            # emit candidate j while j <= accepted, tokens are still owed,
            # and no earlier emitted token hit eos
            js = jnp.arange(kk + 1)[None, :]
            remaining = state["remaining"]
            cand = (js <= accepted[:, None]) & (js < remaining[:, None])
            is_eos = (state["eos"][:, None] >= 0) \
                & (out_toks == state["eos"][:, None])
            hit = (cand & is_eos).astype(jnp.int32)
            eos_before = (jnp.cumsum(hit, axis=1) - hit) > 0
            emit = cand & jnp.logical_not(eos_before) & active[:, None]
            n_emit = jnp.sum(emit.astype(jnp.int32), axis=1)
            # in-graph health guard over the EMITTED logit rows only (freed
            # slots decode garbage by design; rejected rows never stream)
            nonfinite = jnp.sum(
                jnp.logical_not(jnp.isfinite(logits)) & emit[:, :, None],
                axis=(1, 2)).astype(jnp.int32)
            new_tok = jnp.take_along_axis(
                out_toks, jnp.clip(n_emit - 1, 0, kk)[:, None], axis=1)[:, 0]
            new_tok = jnp.where(n_emit > 0, new_tok, state["tok"])
            remaining = remaining - n_emit
            hit_eos = jnp.any(emit & is_eos, axis=1)
            done_now = active & (hit_eos | (remaining <= 0))
            new_state = dict(cache, **{
                "table": state["table"],
                "pos": state["pos"] + n_emit,
                "tok": new_tok,
                "active": active & jnp.logical_not(done_now),
                "remaining": remaining,
                "rng": split[:, 1],
                "temp": state["temp"], "top_k": state["top_k"],
                "top_p": state["top_p"], "eos": state["eos"],
            })
            return (out_toks, n_emit, accepted, done_now, nonfinite,
                    sampled), new_state

        def insert_meta(state, slot, table_row, tok, pos, remaining, rng,
                        temp, top_k, top_p, eos, *ring_row):
            # the KV rows were already copied block-wise (insert_blocks);
            # this binds the slot's block table (a window model: its ring
            # too) + scalars. The slot index is TRACED: one compiled insert
            # covers every slot
            put = lambda a, v_: a.at[slot].set(v_)
            if window:
                state = dict(state, wtable=put(state["wtable"], ring_row[0]))
            return dict(state, **{
                "table": state["table"].at[slot].set(table_row),
                "pos": put(state["pos"], pos),
                "tok": put(state["tok"], tok),
                "active": put(state["active"], True),
                "remaining": put(state["remaining"], remaining),
                "rng": state["rng"].at[slot].set(rng),
                "temp": put(state["temp"], temp),
                "top_k": put(state["top_k"], top_k),
                "top_p": put(state["top_p"], top_p),
                "eos": put(state["eos"], eos),
            })

        def insert_blocks(state, dense_k, dense_v, block_ids, src_blocks,
                          *dense_state):
            # copy a request's private blocks from its freshly-prefilled
            # dense cache into the pool in ONE dispatch that writes only
            # those blocks: the (traced) [blocks_per_slot] id arrays are
            # padded with ids past the pool, which write nothing, so one
            # compiled program serves every request size; a recurrent
            # state (its dense leaves and the slot) is set whole beside
            state = dict(state, **recurrent.insert(state, *dense_state))
            pool = {k: state[k] for k in pool_keys}
            return dict(state, **insert_block_kv(
                pool, {"k": dense_k, "v": dense_v}, block_ids, src_blocks,
                bs, **writer))

        def insert_blocks_by_group(state, dense_k, dense_v, block_ids,
                                   src_blocks, ring_ids, ring_srcs):
            # a model with window layers: the full layers' rows of the dense
            # cache go to the full group as above, and of the window
            # layers' rows the blocks that hold the band go to the slot's
            # ring (the blocks before them are never read again)
            win, full = (jnp.asarray(g) for g in self._layer_groups)
            mcfg = model.config

            def of_kind(dense, layers, window):
                # the kind's layers and, where the dense cache is as wide
                # as the other kind, the kind's own K/V heads of it
                heads = mcfg.kv_geometry(window)["k"][0]
                rows = dense[layers]
                return rows if heads == rows.shape[3] \
                    else rows[:, :, :, :heads]

            out = insert_block_kv(
                {"k": state["k"], "v": state["v"]},
                {"k": of_kind(dense_k, full, False),
                 "v": of_kind(dense_v, full, False)}, block_ids,
                src_blocks, bs, **writer)
            ring = insert_block_kv(
                {"k": state["wk"], "v": state["wv"]},
                {"k": of_kind(dense_k, win, True),
                 "v": of_kind(dense_v, win, True)}, ring_ids, ring_srcs,
                bs, **writer)
            return dict(state, **out, wk=ring["k"], wv=ring["v"])

        def seed_cache(state, table_row):
            # shared-prefix hit: materialize the slot's dense cache view
            # from its (partly shared) block row for the suffix prefill
            return gather_slot_cache(model.config,
                                     {k: state[k] for k in pool_keys},
                                     table_row, self.engine.dtype)

        def fresh_cache():
            # chunked prefill / preemption resume: the request carries a
            # dense b=1 cache ACROSS scheduler steps, so it starts from an
            # explicit zeroed one instead of one built inside the prefill
            # program (the suffix programs donate and return it per chunk)
            return init_cache(model.config, 1, max_len, self.engine.dtype)

        def grow(state, slot, j, block_id):
            # on-demand growth: extend a running slot's KV coverage by one
            # block — table[slot, j] retargets from the garbage block to the
            # freshly-allocated one (slot/j/block_id traced: compiles once)
            return dict(state,
                        table=state["table"].at[slot, j].set(block_id))

        def release(state, slot):
            # MANDATORY (not hygiene): the freed slot's blocks go back to
            # the allocator, so its table row must retreat to the garbage
            # block before anything reuses them — a dead decode write to a
            # reallocated block would be silent cross-request corruption
            parked = lambda t: t.at[slot].set(
                jnp.full((t.shape[1],), GARBAGE_BLOCK, jnp.int32))
            return dict(
                state, table=parked(state["table"]),
                pos=state["pos"].at[slot].set(0),
                active=state["active"].at[slot].set(False),
                **{k: parked(state[k]) for k in carried})

        def scrub_block(state, block_id):
            # scrub_freed_slots: zero a physical block when its last
            # reference drops
            return dict(state, **reset_block_kv(
                {k: state[k] for k in pool_keys}, block_id))

        def migrate_in(state, raw_blocks, block_ids, src_blocks):
            # live KV migration splice for int8 pools: a migrated request's
            # RAW physical blocks, payload AND scales, through the same
            # writer as insert_blocks. Raw, never dequantized: a dequant ->
            # requant round trip can perturb the recomputed scale in its
            # last ulp (see serving/migration.py). Non-int8 pools migrate
            # through the EXISTING insert_blocks program: their dense view
            # IS the raw bytes.
            pool = {k: state[k] for k in pool_keys}
            return dict(state, **write_pool_blocks(
                pool, raw_blocks, block_ids, src_blocks, **writer))

        def sample_first(logits, key, temp, top_k, top_p):
            # same in-graph guard as decode: the first token samples from
            # prefill logits, which must never stream unchecked
            nonfinite = jnp.sum(
                jnp.logical_not(jnp.isfinite(logits))).astype(jnp.int32)
            tok, _ = sample_token_per_request(
                logits, key[None, :], temperature=jnp.reshape(temp, (1,)),
                top_k=jnp.reshape(top_k, (1,)),
                top_p=jnp.reshape(top_p, (1,)))
            return tok, nonfinite

        rep, st = self._rep_sharding, self._state_shardings
        with self.engine.mesh:
            self._decode_jit = jax.jit(decode, donate_argnums=(1,),
                                       out_shardings=(
                                           (rep,) * (4 + self._routing), st))
            self._insert_jit = jax.jit(insert_meta, donate_argnums=(0,),
                                       out_shardings=st)
            self._insert_block_jit = jax.jit(
                insert_blocks_by_group if window else insert_blocks,
                donate_argnums=(0,), out_shardings=st)
            self._seed_cache_jit = jax.jit(
                seed_cache, out_shardings={"k": self._cache_sharding,
                                           "v": self._cache_sharding})
            self._scrub_jit = jax.jit(scrub_block, donate_argnums=(0,),
                                      out_shardings=st)
            if self.growth:
                self._grow_jit = jax.jit(grow, donate_argnums=(0,),
                                         out_shardings=st)
            if self.spec:
                self._verify_jit = jax.jit(
                    verify, donate_argnums=(1,),
                    out_shardings=((rep,) * 6, st))
            if self.cfg.kv_pool.kv_dtype == "int8":
                self._migrate_in_jit = jax.jit(
                    migrate_in, donate_argnums=(0,), out_shardings=st)
            self._fresh_cache_jit = jax.jit(
                fresh_cache, out_shardings=self._dense_cache_shardings())
            self._release_jit = jax.jit(release, donate_argnums=(0,),
                                        out_shardings=st)
            self._sample_first_jit = jax.jit(sample_first,
                                             out_shardings=(rep, rep))

    def trace_decode(self):
        """``(lowered, jaxpr)`` of the decode program over the live
        slot pool — the entry point for the static sanitizer /
        ``tools/program_lint.py``. ONE trace serves both views (tracing only
        builds avals: nothing executes, and the donation annotations ride
        along for the audit)."""
        if self._decode_jit is None:
            self._build_pool_programs()
        t = self._decode_jit.trace(self.engine.params, self._state)
        return t.lower(), t.jaxpr

    def lower_decode(self):
        """The lowered (uncompiled) decode program (see ``trace_decode``)."""
        return self.trace_decode()[0]

    def lower_prefill(self, padded_len):
        """The lowered (uncompiled) prefill program of one prompt bucket —
        lets a caller see which attention the bucket got (a Mosaic
        ``tpu_custom_call`` for the flash kernel, or the XLA scan)."""
        return self._prefill_program(int(padded_len)).lower(
            self.engine.params, jnp.zeros((1, int(padded_len)), jnp.int32),
            np.int32(padded_len))

    def trace_prefill_chunk(self, chunk_tokens=None):
        """``(lowered, jaxpr)`` of the chunked suffix-prefill program
        (one full chunk's bucket) — the ``program_lint --program
        prefill-chunked`` entry point, mirroring ``trace_decode``. This is
        the SAME compiled program a chunk dispatches (and a shared-prefix
        suffix hit shares): q-block written at a traced start position
        against a donated, partially-filled dense b=1 cache."""
        if self._decode_jit is None:
            self._build_pool_programs()
        chunk = int(chunk_tokens or self.chunk_size)
        padded = self.engine._bucket_prompt_len(min(chunk, self.max_len),
                                                self.max_len)
        fn = self._suffix_program(padded)
        cache = init_cache(self.engine.module.config, 1, self.max_len,
                           self.engine.dtype)
        args = (self.engine.params, jnp.zeros((1, padded), jnp.int32), cache,
                np.int32(0), np.int32(min(chunk, padded)))
        t = fn.trace(*args)
        return t.lower(), t.jaxpr

    def trace_verify(self, spec_k=None):
        """``(lowered, jaxpr)`` of the speculative verify program —
        the ``program_lint --program verify`` entry point, mirroring
        ``trace_decode``. Traces the SAME jitted closure a verify step
        dispatches: k+1 positions per slot against the donated pool
        state, with the draft matrix and per-slot draft lengths traced (one
        compiled program per k)."""
        if not self.spec:
            raise ConfigError(
                "trace_verify: serving.speculative is not enabled")
        if self._decode_jit is None:
            self._build_pool_programs()
        kk = int(spec_k or self.spec_k)
        args = (self.engine.params, self._state,
                jnp.zeros((self.n_slots, kk), jnp.int32),
                jnp.zeros((self.n_slots,), jnp.int32))
        t = self._verify_jit.trace(*args)
        return t.lower(), t.jaxpr

    def compile_counts(self):
        """Compiled-program census, pinned by the tier-1 no-recompile test:
        the decode step compiles exactly once per (model, slot-pool)
        configuration no matter how requests join/leave mid-flight."""
        size = lambda f: f._cache_size() if f is not None else 0
        out = {
            "decode": size(self._decode_jit),
            "insert": size(self._insert_jit),
            "prefill_buckets": len(self._prefill_programs),
            "suffix_buckets": len(self._suffix_programs),
            "insert_block": size(self._insert_block_jit),
            "seed_cache": size(self._seed_cache_jit),
        }
        if self.cfg.kv_pool.kv_dtype == "int8":
            out["migrate_in"] = size(self._migrate_in_jit)
        if self.growth:
            out["grow"] = size(self._grow_jit)
        if self.spec:
            out["verify"] = size(self._verify_jit)
            out.update(self._drafter.compile_counts())
        return out

    def _scrub_block(self, block_id):
        """KVPoolManager scrub hook: zero one freed physical block."""
        if self._scrub_jit is not None and self._state is not None:
            self._state = self._scrub_jit(self._state, np.int32(block_id))

    # ------------------------------------------------------------ submission
    def submit(self, request, **kwargs):
        """Admit a request into the bounded queue (or shed it with a reason).

        ``request``: Request | dict | token array (kwargs become Request
        fields for the array form). Returns the Request; check ``.state`` —
        REJECTED means admission control shed it (``.reject_reason`` in
        {queue_full, prompt_too_long, bad_request})."""
        if kwargs and not isinstance(request, (Request, dict)):
            req = Request(prompt=np.asarray(request), **kwargs)
        else:
            req = as_request(request)
        if req.request_id is None:
            req.request_id = self._next_id
            self._next_id += 1
        if req.trace_id is None:
            # a Router stamps its own fleet-global trace id before this;
            # the standalone engine mints one so single-replica traces are
            # mergeable by the same machinery
            req.trace_id = f"req-{req.request_id:06d}"
        req.submit_time = self.clock.now()
        if req.arrival_time is not None and not req.arrival_resolved:
            # direct submit(): arrival_time is an offset from now (same
            # contract as serve()); without this, ttft would subtract a raw
            # offset from an absolute clock reading
            req.arrival_time += req.submit_time
            req.arrival_resolved = True
        reason = None
        if self.degraded_ctl is not None and not req.tokens:
            # degraded-mode admission policy (fresh submissions only — a
            # resumed/migrated stream is committed work, never shed here):
            # rung >= 1 sheds batch, only the LAST rung sheds interactive;
            # rung >= 2 caps the generation budget of what it still admits
            if self.degraded_ctl.sheds_class(req.tenant_class):
                reason = REJECT_DEGRADED
                req.state = RequestState.REJECTED
                req.reject_reason = reason
                self.queue.shed_counts[reason] += 1
            else:
                cap = self.degraded_ctl.token_cap()
                if cap and req.max_new_tokens > cap:
                    req.max_new_tokens = cap
        if reason is None:
            reason = self.queue.admit(req, self.max_len,
                                      kv_fits=self.pool_mgr.fits_ever)
        if reason is None:
            self.metrics.record_submit(req)
            self.tracer.instant(
                "request/queued", cat="serving", request_id=req.request_id,
                trace_id=req.trace_id, prompt_len=req.prompt_len,
                tenant_id=req.tenant_id, tenant_class=req.tenant_class,
                # TTFT's zero point, exactly as Request.ttft defines it
                start=req.start_time)
        else:
            self.metrics.record_shed(reason, req)
            self.tracer.instant("request/shed", cat="serving",
                                request_id=req.request_id,
                                trace_id=req.trace_id, reason=reason,
                                tenant_id=req.tenant_id,
                                tenant_class=req.tenant_class)
        return req

    # ------------------------------------------------------------- the loop
    def step(self):
        """One scheduler iteration: admit queued requests into free slots,
        advance at most one pending prefill chunk (chunked prefill), grow or
        preempt slots whose cursor reached the end of their blocks
        (on-demand growth), then run one decode step over the pool. Returns
        the list of TokenEvents produced.

        The iteration is the span ``serving/step``, and its phases are
        spans inside it (``telemetry/tracer.py``: profiler annotations
        whether or not the tracer records): ``admit``, ``prefill`` /
        ``prefill_chunk`` (a prefill's dispatch and booking), ``insert``,
        ``decode_step`` (the decode's dispatch), ``ahead`` (the next step's
        work dispatched behind it), ``read_back`` (every wait for a device
        result), ``book`` (tokens, finishes, loads) and ``upkeep``. One span
        a phase a step, never one a slot or a token; an admitted request
        adds its own prefill, first-token read-back and insert."""
        span = self.tracer.span
        with span("step", cat="serving"):
            events = []
            # a decode the last step dispatched behind its own: this step's
            ahead, self._decode_ahead = self._decode_ahead, None
            with span("admit", cat="serving"):
                can_admit = self._make_can_admit()
                admitted = self._maybe_priority_preempt(can_admit)
                if admitted is None:
                    admitted = self.scheduler.next_admissions(
                        len(self._free_slots), self.clock.now(),
                        can_admit=can_admit)
                for req in admitted:
                    self._start_request(req, events)
            if self._prefill_jobs and self._chunk_due():
                self._advance_prefill(events)
            if self.growth and self._slots:
                with span("upkeep", cat="serving"):
                    self._grow_or_preempt()
            if self._slots:
                drafts = self._collect_drafts() \
                    if (self.spec and self._spec_on) else None
                if drafts:
                    self._verify_once(events, drafts)
                else:
                    self._decode_once(events, ahead)
                self._decode_steps_since_chunk += 1
            elif not admitted and not self._prefill_jobs and self.queue.depth:
                # nothing running and the queue head hasn't arrived yet
                # (direct submit with a future arrival offset): idle the
                # clock forward to it, or a virtual-clock step() loop would
                # spin forever
                head = self.queue.peek()
                if head.arrival_time is not None:
                    gap = head.arrival_time - self.clock.now()
                    if gap > 0:
                        self.clock.sleep(gap)
            if self._pending_loads:
                # no decode read-back came before: reading the loads waits
                # for the prefill that made them
                with span("read_back", cat="serving"):
                    self._drain_prefill_loads()
            with span("upkeep", cat="serving"):
                if self._slots and self.cfg.migration.enabled \
                        and self.cfg.migration.snapshot_interval_tokens > 0:
                    self._maybe_snapshot()
                if self.degraded_ctl is not None:
                    self.degraded_ctl.observe(self.clock.now())
                self.metrics.observe_step(self.queue.depth, len(self._slots))
            return events

    def _maybe_priority_preempt(self, can_admit):
        """Priority preemption (serving.tenants.preempt): when every slot
        is busy and an arrived INTERACTIVE request waits, evict the
        newest-admitted BATCH stream through the rollback-safe preempt
        machinery (rng captured, blocks released — it resumes bitwise-
        identically later) and admit the interactive request DIRECTLY into
        the freed capacity, returning the admission list for this step.
        Direct admission is load-bearing: ``_preempt`` re-queues the
        victim at the HEAD (it outranks every queued arrival by original
        admission order), so routing the step through ``next_admissions``
        would hand the freed slot straight back to the victim — an
        evict/re-admit livelock instead of a priority grant. Returns None
        when no preemption applies (the normal admission path runs)."""
        tcfg = self.cfg.tenants
        if not (tcfg.enabled and tcfg.preempt) \
                or self._free_slots or not self.queue.depth:
            return None
        if self._pp_cooldown > 0:
            self._pp_cooldown -= 1
            return None
        now = self.clock.now()
        cand_i = None
        for i in range(self.queue.depth):
            r = self.queue.peek_at(i)
            if r.arrival_time is not None and r.arrival_time > now:
                break  # arrivals are time-ordered; nothing further is due
            if r.admit_time is not None:
                continue  # a preemption returner resumes the normal way
            if r.tenant_class == CLASS_INTERACTIVE \
                    and self.scheduler.budget_ok(r, now):
                cand_i = i
                break
        if cand_i is None:
            return None
        batch_slots = [s for s, r_ in self._slots.items()
                       if r_.tenant_class == CLASS_BATCH]
        if not batch_slots:
            return None  # nothing evictable: classes never evict their own
        victim_slot = max(batch_slots,
                          key=lambda s_: self._slots[s_].admit_seq)
        victim = self._slots[victim_slot]
        self._preempt(victim_slot)
        victim.priority_evictions += 1
        self.metrics.priority_evictions += 1
        self.tracer.instant("request/priority_evicted", cat="serving",
                            ts=self.clock.now(),
                            request_id=victim.request_id,
                            trace_id=victim.trace_id,
                            tenant_id=victim.tenant_id,
                            n_tokens=len(victim.tokens))
        # the victim's push_front shifted the candidate one slot back
        cand = self.queue.peek_at(cand_i + 1)
        if not can_admit(cand):
            # the eviction freed too few blocks (large prompt vs short
            # victim): leave the candidate queued and back off — retrying
            # every step would churn evictions without ever admitting
            self._pp_cooldown = 8
            return []
        cand = self.queue.pop_at(cand_i + 1)
        self.scheduler.charge(cand, now)  # fair-share + budget accounting
        return [cand]

    def _make_can_admit(self):
        """Block-aware admission predicate for the scheduler. The queue head
        waits until enough blocks are free, evictable, or unreserved; a
        granted admission RESERVES its blocks in the pool manager (not a
        step-local counter: chunked prefill opens a multi-step window
        between admission and slot insert, and growth/later admissions must
        not steal the head's blocks meanwhile). Prefix sharing is ignored
        here (a hit only needs FEWER blocks, so the check stays sound).
        No livelock: every queued request passed fits_ever at submit, and
        with no slots running every non-free block is prefix-cache-evictable
        and every reservation is consumed by a job already holding a slot,
        so the head always admits once running requests drain."""
        def can_admit(req):
            if self.growth:
                # reserve-as-you-decode: admission pays only the prefilled
                # positions (prompt, or prompt + replayed tokens on resume)
                # PLUS the first decode write — see _growth_admission_len
                need = self.pool_mgr.blocks_for_prefill(
                    self._growth_admission_len(req))
            else:
                need = self.pool_mgr.blocks_for(req.prompt_len,
                                                req.max_new_tokens)
            if not self.pool_mgr.can_allocate(need):
                return False
            self.pool_mgr.reserve(need)
            req.reserved_blocks = need
            return True

        return can_admit

    @staticmethod
    def _prefill_len(req):
        """Positions the request's prefill writes: the prompt, plus — on a
        preemption resume — every already-generated token except the last
        (which decode re-feeds at the cursor)."""
        return req.prompt_len + max(len(req.tokens) - 1, 0)

    def _growth_admission_len(self, req):
        """Positions a growth-mode admission must cover: the prefill PLUS
        the first decode write (at position ``prefill_len``) whenever the
        request will decode at all. Sizing only the prefill is a LIVELOCK:
        a resumed request re-enters exactly at a block boundary, so it
        must grow before producing a single token — and with the queue
        head's admission reservation holding the pool's last blocks, the
        grow fails, the request preempts itself, and the two ping-pong
        forever with zero progress (caught by the fleet-observability
        preemption workload, tier-1-pinned in test_fleet_obs). Covering
        the first write restores the progress guarantee: every admission
        nets at least one token before any preemption."""
        will_decode = bool(req.tokens) or req.max_new_tokens > 1
        return self._prefill_len(req) + (1 if will_decode else 0)

    def _unreserve(self, req):
        """Cancel an admission-time block reservation (early finish / shed
        paths that never reach the slot insert)."""
        if req.reserved_blocks:
            self.pool_mgr.consume_reservation(req.reserved_blocks)
            req.reserved_blocks = 0

    def _chunk_due(self):
        """A pending prefill chunk runs when nothing is decoding, when
        chunking is off (preemption-resume jobs complete in one shot), or
        once the configured decode steps have run since the last chunk.
        The chunk SIZE bounds the co-batched worst inter-token gap (one
        chunk at most between two decode steps); this pacing knob trades
        the long prompt's prefill completion for decode throughput."""
        if not self.chunked or not self._slots:
            return True
        return (self._decode_steps_since_chunk
                >= self.cfg.chunked_prefill.decode_steps_between_chunks)

    def _request_key(self, req):
        if req.sampling.seed is not None:
            base = jax.random.PRNGKey(int(req.sampling.seed))
        else:
            base = jax.random.fold_in(self.engine._rng, req.request_id)
        return jax.random.split(base)  # [2, 2]: (first-token key, slot chain)

    def _start_request(self, req, events):
        if self._decode_jit is None:
            self._build_pool_programs()
        resume = bool(req.tokens)  # preempted request rejoining from the queue
        if resume and len(req.tokens) > 1:
            # replay prefill: prompt + every generated token except the last
            # (decode re-feeds it at the cursor) — rebuilding exactly the KV
            # coverage the preemption released, so the stream continues
            # bitwise-identically
            ids_full = np.concatenate(
                [req.prompt, np.asarray(req.tokens[:-1], np.int32)])
        else:
            ids_full = req.prompt
        if req.prefill_start_time is None:
            # queue-wait window closes at the FIRST slot grant (a resume
            # replay keeps the original endpoint — its wait was decided when
            # it first left the queue)
            req.prefill_start_time = self.clock.now()
            self.metrics.record_queue_wait(req)
        # take refs on matched prefix blocks NOW so an eviction between
        # here and the slot insert can't dangle them
        shared_len, shared_blocks = self.pool_mgr.acquire_prefix(ids_full)
        if shared_len and not resume:
            # positions the prefix-cache hit never dispatches: reported in
            # the goodput block (work avoided, not part of the frac)
            req.prefix_saved_tokens += shared_len
            self.metrics.prefix_saved_tokens += shared_len
        if resume and req.migration is not None \
                and self.cfg.migration.enabled \
                and req.migration.compatible_with(self._pool_geometry()) \
                and self._splice_snapshot(req, req.migration, ids_full,
                                          shared_len, shared_blocks):
            # live KV migration: the snapshot spliced (fresh: straight back
            # into the decode pool; stale: full blocks landed, only the
            # tail replays) — the normal replay path below never runs
            return
        if req.handoff_pending:
            # the handoff splice degraded to a replay-resume (snapshot
            # incompatible here, or fully covered by this pool's prefix
            # cache): the stream still completed its move
            req.handoff_pending = False
            req.handoffs += 1
        chunk = self.chunk_size
        # a recurrent state is carried from chunk to chunk by a job's dense
        # cache: every prefill of such a model is a job
        if resume or self._recurrent.jobs_only \
                or (self.chunked and len(ids_full) - shared_len > chunk):
            # multi-step prefill (chunked and/or resume replay): reserve the
            # slot now, seed the partial cache, and let the step loop drive
            # chunks interleaved with decode steps (_advance_prefill)
            slot = self._free_slots.pop()
            if req.record_routing:
                req.routing = []   # a resume replays every position
            # the dense cache is made when the job's first chunk runs
            # (_advance_prefill): a job waiting behind others holds none
            self._prefill_jobs.append(_PrefillJob(
                req=req, slot=slot, cache=None,
                ids=np.asarray(ids_full, np.int32), pos=shared_len,
                shared_len=shared_len, shared_blocks=shared_blocks,
                resume=resume))
            return
        if shared_len:
            # shared-prefix hit: the pool already holds the prefix KV — seed
            # a dense view from the (partly shared) block row and prefill
            # ONLY the suffix. Capped at prompt_len - 1, so there is always
            # at least one suffix token to yield the first-token logits.
            mgr = self.pool_mgr
            row = np.full((mgr.blocks_per_slot,), GARBAGE_BLOCK, np.int32)
            row[:len(shared_blocks)] = shared_blocks
            suffix = req.prompt[shared_len:]
            # ceiling shrinks by the shared prefix: the suffix q-block is
            # written AT pos=shared_len, and a bucket that overruns max_len
            # would make XLA clamp the update start — silently clobbering
            # the prefix KV rows (bucket 64 + shared 16 in a 64 window did
            # exactly that before this cap)
            padded = self.engine._bucket_prompt_len(
                len(suffix), self.max_len - shared_len)
            req.padding_tokens += padded - len(suffix)
            self.metrics.record_prefill_work(padded, len(suffix))
            with self.tracer.span("prefill", cat="serving",
                                  request_id=req.request_id,
                                  trace_id=req.trace_id, n=len(suffix),
                                  padded_len=padded, shared_len=shared_len):
                cache = self._seed_cache_jit(self._state, jnp.asarray(row))
                ids = np.zeros((1, padded), np.int32)
                ids[0, :len(suffix)] = suffix
                logits, cache = self._prefill_dispatch(
                    self._suffix_program(padded), req, shared_len,
                    len(suffix), self.engine.params, jnp.asarray(ids), cache,
                    np.int32(shared_len), np.int32(len(suffix)))
                # the prefix-cache win in virtual time: only the suffix pays
                self.clock.advance(
                    padded * self.cfg.virtual_prefill_cost_per_token)
        else:
            # ceiling is the full slot window: pad rows past the cursor are
            # causally masked and then overwritten one-by-one as decode
            # advances (same scheme as generate()), so padding may overlap
            # the generation region — one bucket serves every max_new_tokens
            padded = self.engine._bucket_prompt_len(req.prompt_len,
                                                    self.max_len)
            req.padding_tokens += padded - req.prompt_len
            self.metrics.record_prefill_work(padded, req.prompt_len)
            with self.tracer.span("prefill", cat="serving",
                                  request_id=req.request_id,
                                  trace_id=req.trace_id, n=req.prompt_len,
                                  padded_len=padded):
                ids = np.zeros((1, padded), np.int32)
                ids[0, :req.prompt_len] = req.prompt
                logits, cache = self._prefill_dispatch(
                    self._prefill_program(padded), req, 0, req.prompt_len,
                    self.engine.params, jnp.asarray(ids),
                    np.int32(req.prompt_len))
                self.clock.advance(
                    padded * self.cfg.virtual_prefill_cost_per_token)

        self._after_prefill(req, cache, shared_len, shared_blocks, logits,
                            events)

    def _after_prefill(self, req, cache, shared_len, shared_blocks, logits,
                       events, slot=None):
        """Sample the first token from the prefill logits (in-graph health
        guard included) and either finish the request immediately or bind a
        slot. ``slot`` is the job-reserved slot for chunked prefills (freed
        back on an early finish); single-shot prefills pop one here."""
        keys = self._request_key(req)
        s = req.sampling
        tok, nf = self._sample_first_jit(
            logits, keys[0], np.float32(s.temperature),
            np.int32(s.top_k), np.float32(s.top_p))
        now = self.clock.now()
        with self.tracer.span("read_back", cat="serving"):
            nf, t = int(nf), int(np.asarray(tok)[0])
        if nf:
            # symmetric with decode: the counter reports whether or not the
            # shed hook is armed
            self.metrics.record_health_step(1)
        if self._health_shed and nf:
            # poisoned prefill: the first token is garbage — shed BEFORE
            # streaming anything (the request never takes a slot)
            self.pool_mgr.release_blocks(shared_blocks)
            self._unreserve(req)
            if slot is not None:
                self._free_slots.append(slot)
            self.metrics.record_shed("unhealthy_slot")
            self.metrics.record_unhealthy()
            self.tracer.instant("request/unhealthy", cat="serving", ts=now,
                                request_id=req.request_id,
                                trace_id=req.trace_id,
                                nonfinite_logits=int(nf))
            self._finish(req, FINISH_UNHEALTHY, now)
            events.append(TokenEvent(req.request_id, -1, 0, True,
                                     FINISH_UNHEALTHY, now))
            return
        req.state = RequestState.RUNNING
        req.first_token_time = now
        req.tokens.append(t)
        self.metrics.record_tokens(1, req)
        self.metrics.record_first_token(req)
        self.tracer.instant("request/first_token", cat="serving", ts=now,
                            request_id=req.request_id,
                            trace_id=req.trace_id)

        eos = req.eos_token_id
        if (eos is not None and t == eos) or t in req.stop_token_ids \
                or req.max_new_tokens == 1:
            if eos is not None and t == eos:
                reason = FINISH_EOS
            elif t in req.stop_token_ids:
                reason = FINISH_STOP
            else:
                reason = FINISH_LENGTH
            # finished at the first token: no blocks were bound
            self.pool_mgr.release_blocks(shared_blocks)
            self._unreserve(req)
            if slot is not None:
                self._free_slots.append(slot)
            self._finish(req, reason, now)
            events.append(TokenEvent(req.request_id, t, 0, True, reason, now))
            return
        if slot is None:
            slot = self._free_slots.pop()
        self._slots[slot] = req
        req.slot = slot
        if req.admit_seq < 0:
            # preemption-victim ordering: newest admission yields first; a
            # RESUMED request keeps its original seniority
            req.admit_seq = self._admit_seq
            self._admit_seq += 1
        with self.tracer.span("insert", cat="serving"):
            self._insert_paged(req, slot, cache, shared_len, shared_blocks,
                               tok[0], keys[1], s, eos,
                               req.max_new_tokens - 1)
        events.append(TokenEvent(req.request_id, t, 0, False, None, now))

    # ----------------------------------------------- chunked prefill driver
    def _advance_prefill(self, events):
        """Run ONE prefill chunk of the oldest pending job (the whole
        remaining suffix when chunking is off — preemption-resume replays).
        Each chunk is a suffix-prefill call: the q block is written at the
        job's cursor against its donated partial cache, bucketed so every
        full chunk shares one compiled program."""
        job = self._prefill_jobs[0]
        req = job.req
        with self.tracer.span("prefill_chunk", cat="serving",
                              request_id=req.request_id,
                              trace_id=req.trace_id, start=job.pos,
                              resume=job.resume) as sp:
            (n, padded, out), job.ahead = \
                job.ahead or self._dispatch_chunk(job), None
            sp.set(n=n, padded_len=padded)
            req.chunks += 1
            req.padding_tokens += padded - n
            if job.resume:
                # every replayed position is device work a preemption
                # burned: it was prefilled (prompt) or decoded (generated)
                # once already
                req.replay_tokens += n
            self.metrics.record_prefill_work(padded, n,
                                             replay=n if job.resume else 0)
            logits, _ = self._book_prefill(req, job.pos, n, out)
            self.clock.advance(
                padded * self.cfg.virtual_prefill_cost_per_token)
        job.pos += n
        self._decode_steps_since_chunk = 0
        if job.done:
            self._prefill_jobs.popleft()
            # the admission's last part: the first token and the slot
            with self.tracer.span("admit", cat="serving"):
                self._complete_job(job, logits, events)

    def _dispatch_chunk(self, job):
        """Dispatch the job's next chunk. Its cache is the program's from
        here on (the old one was donated); the chunk is booked, and the
        job's cursor moved, where ``_advance_prefill`` takes it. Returns
        the chunk's length, its bucket and what the program handed out."""
        remaining = len(job.ids) - job.pos
        n = min(self.chunk_size, remaining) \
            if self.chunked else remaining
        # ceiling shrinks by the already-prefilled prefix (same overrun
        # guard as the shared-prefix suffix path: a bucket past max_len
        # would make XLA clamp the q-block write start)
        padded = self.engine._bucket_prompt_len(n, self.max_len - job.pos)
        ids = np.zeros((1, padded), np.int32)
        ids[0, :n] = job.ids[job.pos:job.pos + n]
        if job.cache is None:
            job.cache = self._job_cache(job)
        out = self._suffix_program(padded)(
            self.engine.params, jnp.asarray(ids), job.cache,
            np.int32(job.pos), np.int32(n))
        job.cache = out[1]
        self.metrics.record_prefill_chunk(job.pos, n)
        if padded in self._chunk_attention_paths:
            self._chunk_attention_dispatches[
                self._chunk_attention_paths[padded]] += 1
        self._recurrent.book_chunk(n, padded)
        return n, padded, out

    def _dispatch_chunk_ahead(self):
        """Chunked prefill, called with this step's decode dispatched and
        its tokens not yet read: dispatch the chunk the NEXT step will run.
        The device then goes from this decode straight into that chunk while
        the host reads the tokens, books them and admits; without it the
        device idles through all of that, every step, and a step's length
        is the host's to disturb. Nothing the host does in between bears on
        the chunk: the oldest job stays the oldest until it is done, and a
        job that is dropped is dropped with what it holds."""
        if not (self.chunked and self._prefill_jobs):
            return
        job = self._prefill_jobs[0]
        if job.ahead is None and self._decode_steps_since_chunk + 1 >= \
                self.cfg.chunked_prefill.decode_steps_between_chunks:
            job.ahead = self._dispatch_chunk(job)

    def _dispatch_decode_ahead(self):
        """Chunked prefill with no chunk to send, called with this step's
        decode dispatched and its tokens not yet read: dispatch the NEXT
        step's decode behind it, where nothing can come between the two.
        The decode program carries everything a step needs in its state
        (token, cursor, remaining count, sampler keys), so a run of
        decode-only steps needs the host only to read what they made; without
        this the device waits for the host at every such step's edge (2.2 of
        17.9 ms in the mimo cell, and the step's length is the host's to
        disturb: PERF.md, PR 37). It decodes for the slots bound NOW: a
        request inserted in the next step joins the decode after it, and the
        insert itself lands on the state the ahead decode leaves (a slot's
        fields are set whole; its blocks were free). What may not happen
        between the two is a slot LEAVING or the state being read: so only
        where this step frees no slot (no request ends by length in it, none
        can end on a token the host has not seen), no prefill job and no
        queued request waits (then the chunk goes ahead instead), and none
        of the features that edit or read a running slot's state between
        steps exists: growth, verify, the health shed, tenants' preemption
        and the degraded ladder are off, and the cache family is one for
        which snapshots, evacuation and the hand-off are refused by name
        (``_refuse_for_cache_family``; the dense path, whose rng and cursors
        a ``capture_snapshot`` may read between any two steps, waits for
        ROADMAP S1). A virtual clock has no device to feed and keeps the
        schedule it always had."""
        if not self.chunked or self._prefill_jobs or self.queue.depth \
                or not isinstance(self.clock, WallClock) or self.growth \
                or self.spec or self._health_shed \
                or self.degraded_ctl is not None \
                or self.cfg.tenants.enabled \
                or not (self._latent or self._window or self._recurrent.ahead):
            return
        if all(r.eos_token_id is None and not r.stop_token_ids
               and len(r.tokens) + 1 < r.max_new_tokens
               for r in self._slots.values()):
            out, self._state = self._decode_jit(self.engine.params,
                                                self._state)
            self._decode_ahead = (out, dict(self._slots))
            self._decode_ahead_dispatches += 1
            self.metrics.decode_programs += 1

    def _job_cache(self, job):
        """The dense b=1 cache a job's chunks carry: seeded from the shared
        prefix blocks (whose references the job holds) or zeroed."""
        if not job.shared_len:
            # the admission's reset: a recurrent state starts from zero
            self._recurrent.book_reset()
            return self._fresh_cache_jit()
        mgr = self.pool_mgr
        row = np.full((mgr.blocks_per_slot,), GARBAGE_BLOCK, np.int32)
        row[:len(job.shared_blocks)] = job.shared_blocks
        return self._seed_cache_jit(self._state, jnp.asarray(row))

    def _complete_job(self, job, logits, events):
        req = job.req
        if not job.resume:
            self._after_prefill(req, job.cache, job.shared_len,
                                job.shared_blocks, logits, events,
                                slot=job.slot)
            return
        # resume: splice back at the saved cursor with the rng captured at
        # preemption — no first token is sampled (the last streamed token is
        # re-fed at the cursor), so the stream continues bitwise-identically
        slot, s, eos = job.slot, req.sampling, req.eos_token_id
        remaining = req.max_new_tokens - len(req.tokens)
        req.state = RequestState.RUNNING
        self._slots[slot] = req
        req.slot = slot
        with self.tracer.span("insert", cat="serving"):
            rng = jnp.asarray(req.resume_rng)
            # committed replicated scalar: the fresh path feeds tok[0]
            # straight out of _sample_first_jit (committed to the mesh via
            # its pinned out_shardings), and an uncommitted host scalar here
            # would open a SECOND jit-cache entry for the same aval —
            # breaking the insert-compiles-once pin
            tok = jax.device_put(jnp.asarray(req.tokens[-1], jnp.int32),
                                 self._rep_sharding)
            self._insert_paged(req, slot, job.cache, job.shared_len,
                               job.shared_blocks, tok, rng, s, eos,
                               remaining)
        self.tracer.instant("request/resumed", cat="serving",
                            ts=self.clock.now(), request_id=req.request_id,
                            trace_id=req.trace_id,
                            n_tokens=len(req.tokens),
                            preemptions=req.preemptions,
                            # positions this resume re-prefilled (the wide
                            # event's replay attribution per round trip)
                            replay_tokens=len(job.ids) - job.shared_len)

    # ------------------------------------------------- on-demand growth
    def _grow_or_preempt(self):
        """Reserve-as-you-decode: before the decode step, any active slot
        whose write cursor reached the end of its bound blocks grows by one
        block; when the pool can't provide one, the NEWEST-admitted running
        request is preempted back to the queue head (its blocks free, its
        stream resumes bitwise-identically later) instead of OOM/shed."""
        mgr = self.pool_mgr
        for slot in sorted(list(self._slots)):
            req = self._slots.get(slot)
            if req is None:
                continue  # preempted earlier in this same pass
            pos = req.prompt_len + len(req.tokens) - 1  # this step's write
            j = pos // mgr.block_size
            if j < mgr.slot_block_count(slot):
                continue
            preempted_self = False
            while not mgr.can_allocate(1):
                # victim order: batch class before interactive (QoS), then
                # newest admission first — a legacy all-interactive pool
                # reduces to the original newest-admission rule exactly
                victim = max(self._slots, key=lambda s_: (
                    self._slots[s_].tenant_class == CLASS_BATCH,
                    self._slots[s_].admit_seq))
                self._preempt(victim)
                if victim == slot:
                    preempted_self = True
                    break
            if preempted_self:
                continue
            bid = mgr.grow_slot(slot, live_tokens=pos + 1)
            req.kv_blocks_peak = max(req.kv_blocks_peak, j + 1)
            self._state = self._grow_jit(self._state, np.int32(slot),
                                         np.int32(j), np.int32(bid))

    def _preempt(self, slot):
        """Preempt-to-queue: capture the slot's rng (the resume replay needs
        the exact stream), release its blocks and table row, and push the
        request back to the QUEUE HEAD (it outranks everything queued behind
        it — FCFS by original admission)."""
        req = self._slots.pop(slot)
        req.resume_rng = np.asarray(self._state["rng"])[slot].copy()
        req.preemptions += 1
        self.pool_mgr.preempted_requests += 1
        self.metrics.record_preempt()
        self._state = self._release_jit(self._state, np.int32(slot))
        self._free_slot_blocks(slot)
        self._free_slots.append(slot)
        if self._drafter is not None:
            self._drafter.release(slot)
        req.slot = None
        self.queue.push_front(req)
        self.tracer.instant("request/preempted", cat="serving",
                            ts=self.clock.now(), request_id=req.request_id,
                            trace_id=req.trace_id,
                            n_tokens=len(req.tokens))

    def _writer_ids(self, targets, sources, mgr=None):
        """The block writer's two ``[blocks_per_slot]`` id arrays for one
        dispatch: source block ``sources[i]`` lands on pool block
        ``targets[i]``. Every entry past them is padding: a distinct id past
        the pool, which writes nothing (``write_pool_blocks``). Counted
        here, so ``kv_insert_blocks`` is the blocks really written."""
        mgr = mgr or self.pool_mgr
        ids = mgr.n_blocks + np.arange(mgr.blocks_per_slot, dtype=np.int32)
        srcs = np.zeros((mgr.blocks_per_slot,), np.int32)
        ids[:len(targets)] = targets
        srcs[:len(targets)] = list(sources)
        self.metrics.record_kv_insert(len(targets))
        return jnp.asarray(ids), jnp.asarray(srcs)

    def _insert_paged(self, req, slot, cache, shared_len, shared_blocks,
                      tok, chain_key, s, eos, remaining):
        """Bind a slot: allocate the request's footprint in blocks,
        copy the freshly-prefilled PRIVATE blocks from the dense cache
        (shared prefix blocks are refcounted, never copied — copy-on-write),
        set the slot's table row + scalars, and content-address the full
        prompt blocks for future prefix hits. Under on-demand growth the
        footprint is only the PREFILLED positions; decode blocks arrive via
        ``_grow_or_preempt`` as the cursor advances."""
        mgr = self.pool_mgr
        prefill_len = self._prefill_len(req)
        needed = mgr.blocks_for_prefill(self._growth_admission_len(req)) \
            if self.growth \
            else mgr.blocks_for(req.prompt_len, req.max_new_tokens)
        # the scheduler's can_admit reserved this; alloc may still evict
        self._unreserve(req)
        private = mgr.alloc(needed - len(shared_blocks))
        blocks = list(shared_blocks) + private
        ids, srcs = self._writer_ids(
            private, range(len(shared_blocks), len(blocks)))
        ring_args = ring_row = ()
        if self._window:
            # the slot's ring in the window group: its footprint, capped at
            # the ring (a free slot always finds it: kv_pool.py); of the
            # prefilled blocks those that hold the band are copied
            wmgr = self.window_mgr
            ring = wmgr.alloc(wmgr.blocks_for(req.prompt_len,
                                              req.max_new_tokens))
            wmgr.bind_slot(slot, ring,
                           req.prompt_len + req.max_new_tokens - 1)
            held = wmgr.ring_columns(prefill_len)
            ring_args = self._writer_ids(
                [ring[c] for _, c in held], [j for j, _ in held], wmgr)
            row_w = np.full((wmgr.ring,), GARBAGE_BLOCK, np.int32)
            row_w[:len(ring)] = ring
            ring_row = (jnp.asarray(row_w),)
        self._state = self._insert_block_jit(
            self._state, cache["k"], cache["v"], ids, srcs, *ring_args,
            *self._recurrent.insert_args(cache, slot))
        row = np.full((mgr.blocks_per_slot,), GARBAGE_BLOCK, np.int32)
        row[:len(blocks)] = blocks
        self._state = self._insert_jit(
            self._state, np.int32(slot), jnp.asarray(row), tok,
            np.int32(prefill_len), np.int32(remaining),
            chain_key, np.float32(s.temperature), np.int32(s.top_k),
            np.float32(s.top_p), np.int32(-1 if eos is None else eos),
            *ring_row)
        mgr.bind_slot(slot, blocks,
                      self._growth_admission_len(req) if self.growth
                      else req.prompt_len + req.max_new_tokens - 1)
        req.kv_blocks_peak = max(req.kv_blocks_peak, len(blocks))
        mgr.register_prefix(req.prompt, blocks)

    # ------------------------------------------------- live KV migration
    def _pool_leaf_names(self):
        if self._window:
            return ("k", "v", "wk", "wv")
        return ("k", "v", "k_scale", "v_scale") \
            if self.cfg.kv_pool.kv_dtype == "int8" else ("k", "v")

    def _free_slot_blocks(self, slot):
        """The slot's blocks go back to their allocators, group by group."""
        self.pool_mgr.free_slot(slot)
        if self._window:
            self.window_mgr.free_slot(slot)

    def _pool_geometry(self):
        """The splice-compatibility fingerprint a ``RequestSnapshot``
        carries: a snapshot only splices into a pool whose physical block
        layout is identical — anything else falls back to replay-resume."""
        cfg = self.engine.module.config
        return (cfg.n_layers, self.pool_mgr.block_size, cfg.kv_heads,
                cfg.head_dim,
                str(self.cfg.kv_pool.kv_dtype or np.dtype(self.engine.dtype)))

    def capture_snapshot(self, req):
        """Serialize a RUNNING request's device state into a portable
        :class:`RequestSnapshot` (between scheduler steps): the physical
        pool blocks holding positions ``[0, pos)`` as RAW pool-dtype bytes,
        the cursor, the per-slot rng chain key, the committed tokens, the
        sampling knobs, and the prompt's SHA-256 prefix chain keys. Host
        gathers only — no new compiled program, no device mutation — so a
        capture can run on any step boundary without perturbing the
        stay-put stream."""
        self._recurrent.refuse_feature("live KV migration (a snapshot of "
                                       "the blocks and its splice)")
        if self._latent or self._window:
            raise ValueError(
                "ServingEngine: latent attention and window and full "
                "attention layers do not implement live KV "
                "migration (a snapshot of their blocks and its splice)")
        if req.slot is None or self._slots.get(req.slot) is not req:
            return None
        mgr = self.pool_mgr
        slot = req.slot
        pos = req.prompt_len + len(req.tokens) - 1  # KV coverage [0, pos)
        cover = -(-pos // mgr.block_size)           # ceil: blocks holding it
        nb = min(mgr.slot_block_count(slot), cover)
        if nb <= 0:
            return None
        row = np.asarray([mgr.slot_block(slot, j) for j in range(nb)],
                         np.int32)
        raw = {name: np.asarray(self._state[name][:, row])
               for name in self._pool_leaf_names()}
        s = req.sampling
        snap = RequestSnapshot(
            request_id=req.request_id, prompt=req.prompt, tokens=req.tokens,
            pos=pos, rng=np.asarray(self._state["rng"])[slot].copy(),
            blocks=raw, block_size=mgr.block_size,
            chain_keys=prefix_chain_keys(req.prompt, mgr.block_size),
            temperature=s.temperature, top_k=s.top_k, top_p=s.top_p,
            seed=s.seed, max_new_tokens=req.max_new_tokens,
            eos_token_id=req.eos_token_id, geometry=self._pool_geometry())
        req.migration = snap
        self.metrics.record_snapshot()
        return snap

    def _maybe_snapshot(self):
        """Periodic snapshot cadence (``serving.migration
        .snapshot_interval_tokens``): re-capture a running request once it
        has committed that many tokens past its last snapshot — the bound
        a replica-kill recovery replays from."""
        interval = self.cfg.migration.snapshot_interval_tokens
        for slot in sorted(self._slots):
            req = self._slots[slot]
            have = len(req.migration.tokens) \
                if req.migration is not None else 0
            if len(req.tokens) - have >= interval:
                self.capture_snapshot(req)

    def chain_key_for_resume(self, req):
        """The per-slot rng chain key a replayed request must re-enter with
        when NO snapshot exists (replica killed before the first cadence
        capture): re-derive the insert-time chain key deterministically
        from the request's seed and advance it by the committed decode
        steps, exactly as the compiled decode would have."""
        return advance_rng(np.asarray(self._request_key(req)[1]),
                           len(req.tokens) - 1)

    def _inject_raw(self, snap, blocks, n_shared, n_inject):
        """The device half of a splice: copy snapshot source blocks
        ``[n_shared, n_shared + n_inject)`` into the pool blocks of the
        same index. Non-int8 pools ride the EXISTING compiled
        insert_blocks program (their dense view is the raw bytes, and the
        compiled-once pin holds — the dense source is device_put with the
        same pinned cache sharding prefill outputs carry); int8 pools run
        the dedicated raw program so payload AND scales move verbatim."""
        mgr = self.pool_mgr
        bs = mgr.block_size
        span = range(n_shared, n_shared + n_inject)
        ids, srcs = self._writer_ids(blocks[span.start:span.stop], span)
        if self.cfg.kv_pool.kv_dtype == "int8":
            raw = {}
            for name, a in snap.blocks.items():
                pad = np.zeros((a.shape[0], mgr.blocks_per_slot)
                               + a.shape[2:], a.dtype)
                pad[:, :a.shape[1]] = a
                raw[name] = jax.device_put(pad, self._pool_sharding)
            self._state = self._migrate_in_jit(self._state, raw, ids, srcs)
            return
        dense = {}
        for name, row in self.engine.module.config.cache_geometry.items():
            a = snap.blocks[name]                 # [L, NB, bs, kvh * dh]
            d = np.zeros((a.shape[0], 1, self.max_len) + row,
                         np.dtype(self.engine.dtype))
            d[:, 0, :a.shape[1] * bs] = a.reshape((a.shape[0], -1) + row)
            dense[name] = jax.device_put(d, self._cache_sharding)
        self._state = self._insert_block_jit(
            self._state, dense["k"], dense["v"], ids, srcs)

    def _splice_snapshot(self, req, snap, ids_full, shared_len,
                         shared_blocks):
        """Splice a migrated request's snapshot into this replica instead
        of replaying it. FRESH snapshot (captured at the current commit
        point — drain-by-migration): every computed position lands
        verbatim, including the partial tail block (its garbage rows past
        the cursor are causally masked, exactly as on the stay-put
        replica), and the request re-enters the decode pool directly —
        zero recompute. STALE snapshot (periodic cadence, after a kill):
        the FULL blocks splice and only the tail since the capture replays
        through the standard resume-prefill machinery (counted as replay
        tokens). Prefix-cache hits on the target always win first: blocks
        the target already shares are taken by reference, never copied.
        Returns False (no side effects) when the prefix hit already covers
        the snapshot — the caller falls through to the normal path."""
        mgr = self.pool_mgr
        bs = mgr.block_size
        prefill_len = self._prefill_len(req)
        n_shared = len(shared_blocks)
        fresh = snap.pos >= prefill_len
        cover = min(-(-snap.pos // bs) if fresh else snap.full_blocks,
                    mgr.blocks_per_slot)
        if cover <= n_shared:
            return False
        delta = len(req.tokens) - len(snap.tokens)
        self.clock.advance(
            (cover - n_shared) * self.cfg.migration.virtual_cost_per_block)
        if fresh:
            slot = self._free_slots.pop()
            needed = mgr.blocks_for_prefill(self._growth_admission_len(req)) \
                if self.growth \
                else mgr.blocks_for(req.prompt_len, req.max_new_tokens)
            self._unreserve(req)
            private = mgr.alloc(needed - n_shared)
            blocks = list(shared_blocks) + private
            n_inject = min(cover, len(blocks)) - n_shared
            self._inject_raw(snap, blocks, n_shared, n_inject)
            row = np.full((mgr.blocks_per_slot,), GARBAGE_BLOCK, np.int32)
            row[:len(blocks)] = blocks
            # committed replicated scalar, same reason as _complete_job:
            # an uncommitted host scalar would open a second jit-cache
            # entry and break the insert-compiles-once pin
            tok = jax.device_put(jnp.asarray(req.tokens[-1], jnp.int32),
                                 self._rep_sharding)
            rng = jnp.asarray(advance_rng(snap.rng, delta))
            s, eos = req.sampling, req.eos_token_id
            self._state = self._insert_jit(
                self._state, np.int32(slot), jnp.asarray(row), tok,
                np.int32(prefill_len),
                np.int32(req.max_new_tokens - len(req.tokens)), rng,
                np.float32(s.temperature), np.int32(s.top_k),
                np.float32(s.top_p), np.int32(-1 if eos is None else eos))
            mgr.bind_slot(slot, blocks,
                          self._growth_admission_len(req) if self.growth
                          else req.prompt_len + req.max_new_tokens - 1)
            req.kv_blocks_peak = max(req.kv_blocks_peak, len(blocks))
            mgr.register_prefix(req.prompt, blocks)
            req.state = RequestState.RUNNING
            self._slots[slot] = req
            req.slot = slot
            if req.admit_seq < 0:
                req.admit_seq = self._admit_seq
                self._admit_seq += 1
            saved = min(cover * bs, snap.pos) - n_shared * bs
            replay = 0
        else:
            n_inject = cover - n_shared
            # the admission reservation covers these blocks: consume our
            # own share BEFORE alloc so the target's pending count stays
            # honest (and never eats another request's reservation)
            mgr.consume_reservation(min(n_inject, req.reserved_blocks))
            req.reserved_blocks = max(req.reserved_blocks - n_inject, 0)
            blocks = list(shared_blocks) + mgr.alloc(n_inject)
            self._inject_raw(snap, blocks, n_shared, n_inject)
            slot = self._free_slots.pop()
            row = np.full((mgr.blocks_per_slot,), GARBAGE_BLOCK, np.int32)
            row[:len(blocks)] = blocks
            cache = self._seed_cache_jit(self._state, jnp.asarray(row))
            # teacher-forced tail: the tokens committed after the capture
            # replay as prefill, and the rng re-joins the original chain
            req.resume_rng = advance_rng(snap.rng, delta)
            self._prefill_jobs.append(_PrefillJob(
                req=req, slot=slot, cache=cache,
                ids=np.asarray(ids_full, np.int32), pos=cover * bs,
                shared_len=cover * bs, shared_blocks=blocks, resume=True))
            saved = n_inject * bs
            replay = len(ids_full) - cover * bs
        if shared_len:
            # the dedupe win: positions the target's prefix cache already
            # held, so the splice never re-sent their blocks (a resume
            # replay is not credited, but a migrated snapshot arriving over
            # the wire is genuinely avoided transfer + prefill work)
            req.prefix_saved_tokens += shared_len
            self.metrics.prefix_saved_tokens += shared_len
        req.migrations += 1
        self.metrics.record_migration_in(saved)
        # the handoff instant pair's IN side: a first-token prefill->decode
        # handoff splice is telemetered distinctly from a recovery splice
        # (same machinery, different latency semantics — wide events charge
        # the out->in gap to "handoff", not "migrated")
        name = "request/migrated"
        if req.handoff_pending:
            name = "request/handoff_in"
            req.handoff_pending = False
            req.handoffs += 1
        self.tracer.instant(name, cat="serving",
                            ts=self.clock.now(), request_id=req.request_id,
                            trace_id=req.trace_id, n_tokens=len(req.tokens),
                            spliced_blocks=n_inject, shared_len=shared_len,
                            saved_tokens=saved, replay_tokens=replay,
                            fresh=fresh)
        return True

    def evacuate_request(self, req, instant="request/migrated_out"):
        """Live-move ONE running stream off this replica: capture a FRESH
        snapshot while the slot binding is live (the ownership guard in
        ``capture_snapshot`` rejects an unbound request), release the
        slot's device state, and hand the request back QUEUED for
        re-dispatch on a peer. This is the unit the first-token handoff
        (``instant="request/handoff_out"``) and the rebalancer move;
        ``evacuate()`` is this over every slot. Returns False when the
        request is not a slot-bound stream here (nothing to move)."""
        slot = req.slot
        if slot is None or self._slots.get(slot) is not req:
            return False
        if self.cfg.migration.enabled:
            self.capture_snapshot(req)
        self._slots.pop(slot)
        # keep the plain resume path viable too (snapshot may not
        # splice on the target): the rng at this commit point
        req.resume_rng = np.asarray(self._state["rng"])[slot].copy()
        self._state = self._release_jit(self._state, np.int32(slot))
        self._free_slot_blocks(slot)
        if self._drafter is not None:
            self._drafter.release(slot)
        self._free_slots.append(slot)
        req.slot = None
        req.state = RequestState.QUEUED
        self.metrics.record_migration_out()
        self.tracer.instant(instant, cat="serving",
                            ts=self.clock.now(),
                            request_id=req.request_id,
                            trace_id=req.trace_id,
                            n_tokens=len(req.tokens),
                            snapshot=req.migration is not None)
        return True

    def evacuate(self):
        """Drain-by-migration: capture a FRESH snapshot of every running
        request, release its device state, and hand every unfinished
        request back (original admission order) for re-dispatch on a peer
        replica — a drained replica restarts with ZERO lost and (when the
        snapshot splices) zero recomputed tokens. Pending prefill jobs and
        the queue ride along as-is: their work is not on this device yet
        beyond the shared prefix."""
        out = []
        for slot in sorted(self._slots,
                           key=lambda s_: self._slots[s_].admit_seq):
            req = self._slots[slot]
            self.evacuate_request(req)
            out.append(req)
        for job in list(self._prefill_jobs):
            req = job.req
            self.pool_mgr.release_blocks(job.shared_blocks)
            self._unreserve(req)
            self._free_slots.append(job.slot)
            req.slot = None
            req.state = RequestState.QUEUED
            out.append(req)
        self._prefill_jobs.clear()
        while self.queue.depth:
            out.append(self.queue.pop())
        return out

    def abandon_inflight(self):
        """A killed replica's post-mortem: collect every unfinished request
        WITHOUT touching the device (the replica is gone — no capture, no
        release; recovery runs from whatever snapshot the periodic cadence
        already took, or replays the prompt + committed tokens). Host
        bookkeeping only: reservations are zeroed ON THE REQUEST — the
        pool they were pending against died with the replica, and carrying
        them to a survivor would eat its reservations."""
        out = []
        for slot in sorted(self._slots,
                           key=lambda s_: self._slots[s_].admit_seq):
            req = self._slots.pop(slot)
            req.slot = None
            req.state = RequestState.QUEUED
            req.reserved_blocks = 0
            out.append(req)
        for job in list(self._prefill_jobs):
            req = job.req
            req.slot = None
            req.state = RequestState.QUEUED
            req.reserved_blocks = 0
            out.append(req)
        self._prefill_jobs.clear()
        while self.queue.depth:
            req = self.queue.pop()
            req.reserved_blocks = 0
            out.append(req)
        return out

    # ------------------------------------------------- speculative decoding
    def set_speculation(self, enabled):
        """Toggle speculation at runtime (drafting is skipped when off; the
        compiled verify program stays warm). Seeded sampled streams are
        unaffected either way — the rng splits once per dispatched step in
        both the decode and verify programs (tier-1 pins it)."""
        self._spec_on = bool(enabled) and self.spec

    def _collect_drafts(self):
        """Ask the drafter for up to k candidates per eligible slot.

        Eligibility: active, GREEDY (sampled slots never speculate — greedy
        acceptance is an argmax identity, and a sampled slot's rng must
        advance exactly once per dispatched step), and >= 2 tokens still
        owed (a 1-token tail gains nothing from drafting). Draft length is
        capped at tokens-owed - 1 (so every written candidate row stays
        inside the request's block footprint) and, under on-demand growth,
        by the coverage the pool can provide RIGHT NOW: a k-token verify
        may cross a block boundary, and the grow must land before the
        dispatch — exactly the admission-coverage bug class PR 13's
        instrument caught, handled here by growing (never preempting) for
        speculation and truncating the drafts when the pool is tight."""
        wanted = {}
        for slot, req in self._slots.items():
            if req.sampling.temperature > 0:
                continue
            owed = req.max_new_tokens - len(req.tokens)
            cap = min(self.spec_k, owed - 1)
            if cap < 1:
                continue
            wanted[slot] = (np.concatenate(
                [req.prompt, np.asarray(req.tokens, np.int32)]), cap)
        if not wanted:
            return None
        proposals = self._drafter.propose(wanted)
        out = {}
        proposed = 0
        for slot, toks in proposals.items():
            toks = np.asarray(toks, np.int32).reshape(-1)[:wanted[slot][1]]
            proposed += len(toks)
            req = self._slots[slot]
            if self.growth and len(toks):
                toks = self._grow_for_verify(slot, req, toks)
            if len(toks):
                out[slot] = toks
                req.drafted_tokens += len(toks)
                self.metrics.record_draft(len(toks))
        if proposed and self._drafter.name == "model":
            self.clock.advance(
                proposed * self.cfg.speculative.virtual_draft_cost_per_token)
        return out or None

    def _grow_for_verify(self, slot, req, toks):
        """Under on-demand growth, candidate rows at positions
        [cursor, cursor + len(toks)] must be block-covered BEFORE the
        verify dispatches (padded rows redirect to the garbage block, but
        rows that could be ACCEPTED must land in real blocks). Grows one
        block at a time; when the pool cannot provide one, the drafts are
        truncated to the existing coverage — speculation is opportunistic
        and never preempts another request to make room for itself."""
        mgr = self.pool_mgr
        pos = req.prompt_len + len(req.tokens) - 1
        while (pos + len(toks)) // mgr.block_size \
                >= mgr.slot_block_count(slot):
            if not mgr.can_allocate(1):
                cover = mgr.slot_block_count(slot) * mgr.block_size
                return toks[:max(cover - 1 - pos, 0)]
            j = mgr.slot_block_count(slot)
            bid = mgr.grow_slot(slot, live_tokens=pos + 1)
            req.kv_blocks_peak = max(req.kv_blocks_peak, j + 1)
            self._state = self._grow_jit(self._state, np.int32(slot),
                                         np.int32(j), np.int32(bid))
        return toks

    def _verify_once(self, events, drafts):
        """One verify dispatch over the whole pool: every active slot
        advances >= 1 token (row 0 is its decode), speculating slots
        advance by the accepted prefix + 1. Costs ONE decode step in
        virtual time — that is the entire latency play, and why the worst
        inter-token gap bound from chunked prefill is unchanged."""
        kk = self.spec_k
        dmat = np.zeros((self.n_slots, kk), np.int32)
        dlen = np.zeros((self.n_slots,), np.int32)
        for slot, toks in drafts.items():
            dmat[slot, :len(toks)] = toks
            dlen[slot] = len(toks)
        span = self.tracer.span
        with span("decode_step", cat="serving", active=len(self._slots),
                  verify=True, drafted=int(dlen.sum())):
            ((toks, n_emit, accepted, done_now, nonfinite, sampled),
             self._state) = self._verify_jit(
                self.engine.params, self._state, jnp.asarray(dmat),
                jnp.asarray(dlen))
            self.clock.advance(self.cfg.virtual_decode_step_cost)
        with span("read_back", cat="serving"):
            toks, n_emit, accepted, done_now, nonfinite, sampled = \
                jax.device_get((toks, n_emit, accepted, done_now, nonfinite,
                                sampled))
        with span("book", cat="serving"):
            now = self.clock.now()
            self.metrics.record_health_step(
                sum(1 for s in self._slots if nonfinite[s] > 0))
            self.metrics.record_verify_step()
            self.metrics.record_decode_dispatch()
            self._decode_dispatches["view"] += 1
            self.metrics.record_sampler_step(bool(sampled))
            for slot in sorted(self._slots):
                req = self._slots[slot]
                # this step's cursor
                pos0 = req.prompt_len + len(req.tokens) - 1
                n, acc, d = int(n_emit[slot]), int(accepted[slot]), \
                    int(dlen[slot])
                if d:
                    # booked BEFORE any shed below: the drafted == accepted +
                    # rolled_back invariant must balance on every exit path
                    req.accepted_tokens += acc
                    req.rolled_back_tokens += d - acc
                    self.metrics.record_accept(acc, d - acc)
                if self._health_shed and nonfinite[slot] > 0:
                    self._shed_unhealthy(req, events, now,
                                         int(nonfinite[slot]))
                    continue
                reason = None
                for j in range(n):
                    t = int(toks[slot, j])
                    req.tokens.append(t)
                    self.metrics.record_tokens(1, req)
                    self.metrics.record_decode_tokens(1)
                    if j == n - 1 and bool(done_now[slot]):
                        reason = FINISH_EOS if (req.eos_token_id is not None
                                                and t == req.eos_token_id) \
                            else FINISH_LENGTH
                    elif t in req.stop_token_ids:
                        # host-side stop policy truncates the emitted run; the
                        # device state is ahead but the slot is freed anyway
                        reason = FINISH_STOP
                    events.append(TokenEvent(req.request_id, t,
                                             len(req.tokens) - 1,
                                             reason is not None, reason, now))
                    if reason is not None:
                        break
                if reason is not None:
                    self._finish(req, reason, now)
                    continue
                if d >= n:
                    # candidate rows [pos0 + n, pos0 + d] were written but the
                    # cursor rolled back short of them — reclaim at block
                    # granularity
                    self._rollback_stale(slot, new_cursor=pos0 + n,
                                         written_end=pos0 + d)
            self._drain_prefill_loads()

    def _rollback_stale(self, slot, new_cursor, written_end):
        """Rejected drafts rolled back: the in-graph verify already left
        the cursor at the accepted end, so the rejected rows sit PAST it —
        causally masked and overwritten before they could ever become
        visible (the same guarantee freed-slot garbage rides). At block
        granularity more is reclaimable: a block lying entirely past the
        cursor holds ONLY stale rows, so under on-demand growth it is
        released back to the pool (its scrub rides the normal last-ref
        drop) and under whole-footprint reservation it is scrubbed in
        place when the hygiene scrub is armed — both counted in
        ``scrubbed_blocks``/``rolled_back_blocks``."""
        mgr = self.pool_mgr
        first_stale = -(-new_cursor // mgr.block_size)   # ceil
        if self.growth:
            for j in range(mgr.slot_block_count(slot) - 1, first_stale - 1,
                           -1):
                # table entry retreats to the garbage block BEFORE the
                # allocator can hand the block to anyone else
                self._state = self._grow_jit(self._state, np.int32(slot),
                                             np.int32(j),
                                             np.int32(GARBAGE_BLOCK))
                mgr.shrink_slot(slot, live_tokens=new_cursor)
        elif self.cfg.scrub_freed_slots:
            last = min(written_end // mgr.block_size,
                       mgr.slot_block_count(slot) - 1)
            for j in range(first_stale, last + 1):
                self._scrub_block(mgr.slot_block(slot, j))
                mgr.scrubbed_blocks += 1

    def _decode_once(self, events, ahead=None):
        """One decode step over the pool. ``ahead``: ``(outputs, {slot:
        request})`` of this step's decode where the last step dispatched it
        already: it decoded for the slots bound then, so a slot bound since
        (this step's insert) gets its next token from the next decode. The
        span ``decode_step`` is the dispatch alone; the wait for its tokens
        is ``read_back`` and what is done with them ``book``."""
        span = self.tracer.span
        with span("decode_step", cat="serving", active=len(self._slots)):
            if ahead is None:
                out, self._state = self._decode_jit(self.engine.params,
                                                    self._state)
                self.metrics.decode_programs += 1
                live = dict(self._slots)
            else:
                out, bound = ahead
                live = {s: r for s, r in bound.items()
                        if self._slots.get(s) is r}
            self.clock.advance(self.cfg.virtual_decode_step_cost)
        self.metrics.record_decode_dispatch()
        self._decode_dispatches[self.attn_backend] += 1
        if self.chunked:
            with span("ahead", cat="serving"):
                self._dispatch_chunk_ahead()
                self._dispatch_decode_ahead()
        # one read-back for all the step hands out (a routing model: its
        # expert choices too)
        with span("read_back", cat="serving"):
            toks, done_now, nonfinite, sampled, *routed = jax.device_get(out)
        with span("book", cat="serving"):
            self.metrics.record_sampler_step(bool(sampled))
            if routed:
                self._book_decode_routing(routed[0], live)
            now = self.clock.now()
            self.metrics.record_health_step(
                sum(1 for s in live if nonfinite[s] > 0))
            for slot in sorted(live):
                req = live[slot]
                t = int(toks[slot])
                if self._health_shed and nonfinite[slot] > 0:
                    self._shed_unhealthy(req, events, now,
                                         int(nonfinite[slot]))
                    continue
                req.tokens.append(t)
                self.metrics.record_tokens(1, req)
                self.metrics.record_decode_tokens(1)
                if bool(done_now[slot]):
                    reason = FINISH_EOS if (req.eos_token_id is not None
                                            and t == req.eos_token_id) \
                        else FINISH_LENGTH
                elif t in req.stop_token_ids:
                    # stop sequences are host-side policy (a set, not the
                    # single device-tracked eos id): finish here and
                    # deactivate the slot
                    reason = FINISH_STOP
                else:
                    events.append(TokenEvent(req.request_id, t,
                                             len(req.tokens) - 1, False,
                                             None, now))
                    continue
                self._finish(req, reason, now)
                events.append(TokenEvent(req.request_id, t,
                                         len(req.tokens) - 1, True, reason,
                                         now))
            self._drain_prefill_loads()

    def _book_decode_routing(self, routed, live):
        """``routed`` [L_moe, S, 2k]: what this decode step's expert layers
        chose, every slot's (a freed slot routes its dead token too: the
        pairs were computed and their experts read). Counters, and the
        record of the requests (``live``: ``{slot: request}`` the step
        decoded for) that asked for theirs."""
        cfg = self.engine.module.config
        k = cfg.moe_top_k
        counts = np.stack([np.bincount(layer[:, :k].reshape(-1),
                                       minlength=cfg.n_experts)
                           for layer in routed])
        self.metrics.record_moe_loads(counts, decode=True)
        self._book_product_path(routed)
        for slot, req in live.items():
            # this step fed the slot's last token, at the position before
            # the one the new token takes
            live = req.prompt_len + len(req.tokens)
            self.metrics.latent_kv_tokens_read += live
            if self._window:
                # a window layer attended the band of them
                self.metrics.kv_window_rows_read += min(
                    live, self.window_mgr.window)
                self.window_mgr.book_cursor(live - 1)
            if req.record_routing:
                req.routing.append((live - 1, 1, routed[:, slot, None]))

    def _drain_prefill_loads(self):
        """Book the expert loads of the prefill programs dispatched in this
        step. Called after the decode step's token read-back: the arrays are
        ready by then, so reading them waits for nothing."""
        for counts in self._pending_loads:
            self.metrics.record_moe_loads(np.asarray(counts))
        self._pending_loads = []

    def _shed_unhealthy(self, req, events, now, n_bad):
        """The unhealthy_slot hook, shared by the decode and verify paths:
        this slot's logits went non-finite — its sampled token is poison,
        its KV rows are suspect. Shed the request with a reason (the
        admission-control discipline: fail loudly, never stream garbage)
        and free + deactivate the slot."""
        self.metrics.record_shed("unhealthy_slot")
        self.metrics.record_unhealthy()
        self.tracer.instant("request/unhealthy", cat="serving", ts=now,
                            request_id=req.request_id,
                            trace_id=req.trace_id, nonfinite_logits=n_bad)
        self._finish(req, FINISH_UNHEALTHY, now)
        events.append(TokenEvent(req.request_id, -1, len(req.tokens),
                                 True, FINISH_UNHEALTHY, now))

    def _finish(self, req, reason, now):
        req.state = RequestState.FINISHED
        req.finish_reason = reason
        req.finish_time = now
        if req.slot is not None:
            if req.record_state:
                # read before the release; no decode was sent ahead of a
                # step in which a request may end
                req.final_state = self._recurrent.read(self._state, req.slot)
            del self._slots[req.slot]
            self._free_slots.append(req.slot)
            if self._drafter is not None:
                self._drafter.release(req.slot)
            # ALWAYS release: the table row must retreat to the garbage
            # block before the allocator reuses the blocks (and a host-side
            # stop, which the device does not know of, clears its active
            # flag here)
            self._state = self._release_jit(self._state, np.int32(req.slot))
            self._free_slot_blocks(req.slot)
            req.slot = None
        self.metrics.record_finish(req)
        start = req.start_time
        # the per-request goodput/lifecycle rollup rides the finish instant
        # verbatim, so the fleet merger's wide event needs no cross-stream
        # reconstruction of engine-side counters. admit_wait splits the
        # queue wait: arrival -> scheduler admission (waiting for a slot /
        # KV blocks) vs admission -> prefill dispatch.
        self.tracer.instant("request/finish", cat="serving", ts=now,
                            request_id=req.request_id,
                            trace_id=req.trace_id, reason=reason,
                            n_tokens=len(req.tokens),
                            prompt_len=req.prompt_len,
                            # multi-tenant QoS: the wide event carries the
                            # tenant so fleet_report can grade per tenant
                            tenant_id=req.tenant_id,
                            tenant_class=req.tenant_class,
                            priority_evictions=req.priority_evictions,
                            queue_wait=req.queue_wait,
                            admit_wait=None
                            if req.admit_time is None or start is None
                            else req.admit_time - start,
                            chunks=req.chunks,
                            preemptions=req.preemptions,
                            replay_tokens=req.replay_tokens,
                            padding_tokens=req.padding_tokens,
                            prefix_saved_tokens=req.prefix_saved_tokens,
                            kv_blocks_peak=req.kv_blocks_peak,
                            # speculative accounting: the wide event's
                            # drafted/accepted/rolled_back counts reconcile
                            # with the fleet counters (tier-1-pinned)
                            drafted_tokens=req.drafted_tokens,
                            accepted_tokens=req.accepted_tokens,
                            rolled_back_tokens=req.rolled_back_tokens,
                            # fleet recovery accounting: completed replica
                            # moves and the bounded failover/retry budget
                            # spent (router-owned, but the Request object
                            # is the same across replicas)
                            migrations=req.migrations,
                            failovers=req.failovers,
                            retries=req.retries,
                            # disaggregated fleet: first-token handoffs and
                            # voluntary rebalance moves this stream rode
                            handoffs=req.handoffs,
                            rebalances=req.rebalances)

    # ------------------------------------------------------------- frontends
    def serve(self, requests=None, yield_rejections=True):
        """Streaming frontend: feed ``requests`` (each optionally carrying an
        ``arrival_time``) through the continuous-batching loop, yielding
        ``TokenEvent``s as they are produced. Runs until every accepted
        request finishes; shed requests surface as a single done event with
        ``finish_reason="rejected:<reason>"`` (and ``token == -1``)."""
        pending = sorted((as_request(r) for r in (requests or [])),
                         key=lambda r: r.arrival_time or 0.0)
        t0 = self.clock.now()
        for r in pending:
            # arrival offsets -> absolute clock times (TTFT counts queueing)
            if not r.arrival_resolved:
                r.arrival_time = t0 + (r.arrival_time or 0.0)
                r.arrival_resolved = True
            elif r.arrival_time is None:
                r.arrival_time = t0
        try:
            while pending or self.queue.depth or self._slots \
                    or self._prefill_jobs:
                now = self.clock.now()
                while pending and pending[0].arrival_time <= now:
                    req = self.submit(pending.pop(0))
                    if req.state is RequestState.REJECTED and yield_rejections:
                        yield TokenEvent(req.request_id, -1, -1, True,
                                         f"rejected:{req.reject_reason}", now)
                if not self._slots and not self.queue.depth \
                        and not self._prefill_jobs:
                    if not pending:
                        break
                    # idle until the next arrival
                    self.clock.sleep(max(pending[0].arrival_time - now, 1e-4))
                    continue
                for ev in self.step():
                    yield ev
        finally:
            # a consumer that breaks mid-stream (GeneratorExit) or a step()
            # exception must still land the lifecycle events on disk — this
            # is the only flush on the streaming path before destroy().
            # The terminal metrics emit closes the rate-limited monitor
            # cadence: short runs lose no tail interval (the Router does the
            # same fleet-wide).
            self.tracer.flush()
            self.metrics.emit_events()

    def run(self, requests):
        """Non-streaming convenience: serve ``requests`` to completion and
        return ``(finished, rejected, metrics_snapshot)``."""
        reqs = [as_request(r) for r in (requests or [])]
        for _ in self.serve(reqs, yield_rejections=False):
            pass
        finished = [r for r in reqs if r.state is RequestState.FINISHED]
        rejected = [r for r in reqs if r.state is RequestState.REJECTED]
        return finished, rejected, self.metrics.snapshot()

    def destroy(self):
        """Drop the slot pool and compiled programs (cf. InferenceEngine
        .destroy): the jitted closures capture self, which would otherwise
        pin the KV pool in HBM."""
        self._state = None
        self._decode_jit = None
        self._insert_jit = None
        self._release_jit = None
        self._sample_first_jit = None
        self._insert_block_jit = None
        self._seed_cache_jit = None
        self._scrub_jit = None
        self._fresh_cache_jit = None
        self._grow_jit = None
        self._verify_jit = None
        self._migrate_in_jit = None
        if self._drafter is not None and hasattr(self._drafter, "destroy"):
            self._drafter.destroy()
        self._drafter = None
        self._prefill_programs = OrderedDict()
        self._suffix_programs = OrderedDict()
        self._prefill_jobs = collections.deque()
        self._decode_ahead = None
        self._slots = {}
        self._free_slots = list(range(self.n_slots - 1, -1, -1))
        self.tracer.flush()
        import gc

        gc.collect()
