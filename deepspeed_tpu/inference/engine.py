"""Inference engine (reference ``inference/engine.py:89`` InferenceEngine).

The reference wraps a HF torch model, surgically replaces blocks with fused CUDA
containers (``module_inject/replace_module.py:276``), slices weights per TP rank
(``ReplaceWithTensorSlicing:28``) and captures CUDA graphs (``:500``). TPU-native:

- TP = weight PartitionSpecs over the ``model`` mesh axis (the same logical-axis
  rules as training — auto-TP is the default, not a fallback);
- kernel injection = XLA fusion + the jitted decode step (a compiled program IS
  the captured graph — replay is free);
- KV-cache attention = ``models/decoding.py`` (the "softmax_context" kernel);
- checkpoint loading reuses the sharded npz checkpoint engine; TP resharding
  happens by construction (specs place each shard, the ``SDLoaderFactory``
  merge/split logic disappears).
"""

from collections import OrderedDict

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..config.base import ConfigError
from ..config.config import MeshConfig
from ..models.layers import split_params_axes, Param
from ..models.decoding import init_cache, forward_with_cache, sample_token
from ..parallel import build_mesh, DATA_AXIS, MODEL_AXIS
from ..parallel.sharding import param_partition_specs, named
from ..utils.logging import log_dist

DTYPES = {"float16": jnp.float16, "bfloat16": jnp.bfloat16, "float32": jnp.float32}


def lru_compiled(cache, key, build, cap, label):
    """LRU lookup in ``cache`` (an OrderedDict) of the compiled program(s)
    for ``key``; ``build()`` compiles on miss. Over ``cap`` entries, the
    least-recently-used programs are evicted with a one-line warning —
    adversarial key mixes (e.g. prompt lengths) can't grow compiled programs
    without bound. Shared by the generate cache and the serving prefill
    cache."""
    if key in cache:
        cache.move_to_end(key)
        return cache[key]
    fns = build()
    cache[key] = fns
    if cap > 0 and len(cache) > cap:
        import logging

        evicted, _ = cache.popitem(last=False)
        log_dist(f"{label} compile cache over cap ({cap}): evicted programs "
                 f"for key {evicted}", ranks=[0], level=logging.WARNING)
    return fns


class InferenceEngine:
    def __init__(self, model, config, mesh=None, model_parameters=None):
        if model is None:
            raise ConfigError("init_inference: model is required")
        self.module = model
        self._config = config
        self.dtype = DTYPES[config.dtype]
        if hasattr(model, "config") and hasattr(model.config, "compute_dtype"):
            model.config.compute_dtype = self.dtype

        tp = config.tensor_parallel.tp_size if config.tensor_parallel.enabled else 1
        ep = config.moe.ep_size if config.moe.enabled else 1
        if ep > 1 and getattr(getattr(model, "config", None), "n_experts", 0) < 1:
            raise ConfigError(
                f"moe.ep_size={ep} needs an MoE model (n_experts > 0)")
        self.mesh = mesh if mesh is not None else build_mesh(
            MeshConfig(model=tp, expert=ep))
        self.mp_world_size = self.mesh.shape.get(MODEL_AXIS, 1)
        # the MoE dispatch constraints (moe/sharded_moe.py _expert_a2a) and
        # ring attention read the mesh off the model config
        if hasattr(model, "config") and hasattr(model.config, "mesh"):
            model.config.mesh = self.mesh
        if self.mp_world_size > 1 and hasattr(model, "config") \
                and getattr(model.config, "fused_qkv", False):
            # sharded-concat SPMD hazard (see runtime/engine.py): the fused
            # qkv concat is miscompiled when the kernels carry a model-axis
            # sharding; per-projection matmuls are bitwise per output column
            model.config.fused_qkv = False

        self._rng = jax.random.PRNGKey(config.seed)
        self._request_seq = 0  # folded into per-call rng: two requests with
        # the same prompt length must not share a sampling stream
        self._init_parameters(model_parameters)

        self._prefill_fn = None
        self._decode_fn = None
        # LRU of compiled (prefill, decode) pairs keyed by (batch, prompt
        # bucket, sampling shape); bounded by config.compile_cache_size so an
        # adversarial length mix can't grow compiled programs without bound
        self._prefill_cache = OrderedDict()
        self._serving = None

        log_dist(
            f"InferenceEngine: mesh={dict(self.mesh.shape)} dtype={config.dtype} "
            f"max_tokens={config.max_tokens}",
            ranks=[0],
        )

    # ------------------------------------------------------------------------------
    def _init_parameters(self, model_parameters):
        # unknown models (no Param axes metadata) default every leaf to
        # replicated — the injection_policy below is how users TP-place them
        if model_parameters is not None:
            if isinstance(model_parameters, tuple) and len(model_parameters) == 2:
                values, axes = model_parameters
            else:
                values, axes = split_params_axes(model_parameters)
        else:
            params_shape = jax.eval_shape(self.module.init, self._rng)
            axes = jax.tree_util.tree_map(
                lambda p: p.axes if isinstance(p, Param)
                else (None,) * len(p.shape),
                params_shape, is_leaf=lambda x: isinstance(x, Param))
            values = None

        if values is not None:
            shapes = jax.tree_util.tree_map(lambda v: tuple(v.shape), values)
        else:
            shapes = jax.tree_util.tree_map(
                lambda p: tuple((p.value if isinstance(p, Param) else p).shape),
                params_shape, is_leaf=lambda x: isinstance(x, Param))

        if self._config.injection_policy:
            from ..module_inject.policy import apply_injection_policy

            if self.mesh.shape.get(MODEL_AXIS, 1) <= 1:
                raise ConfigError(
                    "injection_policy given but tensor_parallel.tp_size is 1 "
                    "— the policy would silently serve a replicated model; "
                    "set tensor_parallel={'enabled': True, 'tp_size': N}")
            axes = apply_injection_policy(
                self._config.injection_policy, axes, shapes)

        # inference keeps params in the serving dtype (no fp32 masters) and TP-only
        # sharding (zero_stage=0: no data-sharded params)
        self.param_specs = param_partition_specs(axes, shapes, self.mesh, zero_stage=0)
        self.param_shardings = named(self.mesh, self.param_specs)

        if values is None:
            init_fn = lambda rng: jax.tree_util.tree_map(
                lambda a: (a.value if isinstance(a, Param) else a)
                .astype(self.dtype),
                self.module.init(rng), is_leaf=lambda x: isinstance(x, Param))
            with self.mesh:
                self.params = jax.jit(init_fn, out_shardings=self.param_shardings)(self._rng)
        else:
            self.params = jax.tree_util.tree_map(
                lambda v, s: jax.device_put(jnp.asarray(v, self.dtype), s),
                values, self.param_shardings)
        if self._config.quant.enabled:
            self._quantize_weights()

    def _quantize_weights(self):
        """int8 weight-only serving (reference ``replace_module.py:140``
        GroupQuantizer + the inference dequant kernels): every block matmul
        kernel becomes {kernel_q int8, kernel_scale} — the model reads weights
        from HBM at 8 bits and dequantizes inside the fused matmul
        (``models/layers.py linear_apply``)."""
        from ..ops.quantizer import quantize_per_channel

        bits = self._config.quant.bits
        group_size = self._config.quant.group_size
        counts = {"packed": 0, "int8": 0}

        def walk(tree, shardings, name=""):
            if isinstance(tree, dict):
                if "router" in name:
                    return tree  # MoE router must stay fp32 (stable gating)
                if "kernel" in tree and getattr(tree["kernel"], "ndim", 0) >= 2:
                    q, scale = quantize_per_channel(tree["kernel"], bits=bits,
                                                    group_size=group_size)
                    out = {k: v for k, v in tree.items() if k != "kernel"}
                    sh = shardings["kernel"]
                    if bits == 4 and q.shape[-2] % 2 == 0:
                        from ..ops.quantizer import pack_int4

                        # nibble-packed: 4 bits/weight in HBM
                        out["kernel_q4"] = jax.device_put(pack_int4(q), sh)
                        counts["packed"] += 1
                    else:
                        out["kernel_q"] = jax.device_put(q, sh)
                        counts["int8"] += 1
                    out["kernel_scale"] = scale
                    return out
                return {k: walk(v, shardings[k], f"{name}/{k}")
                        for k, v in tree.items()}
            return tree

        # only block matmuls; embeddings/norms stay in the serving dtype
        if not (isinstance(self.params, dict) and "blocks" in self.params):
            raise ConfigError(
                "quant.enabled needs a zoo-style model (params with a "
                "'blocks' subtree whose matmuls read quantized kernels); an "
                "injection-policy-served unknown model must be served "
                "unquantized")
        self.params = dict(self.params)
        self.params["blocks"] = walk(self.params["blocks"],
                                     self.param_shardings["blocks"])
        packed_note = f", {counts['packed']} nibble-packed" \
            if counts["packed"] else ""
        fallback_note = f", {counts['int8']} int8-stored" \
            if bits == 4 and counts["int8"] else ""
        log_dist(f"int{bits} weight-only quantization applied to "
                 f"{sum(counts.values())} block kernels "
                 f"(group_size={group_size}{packed_note}{fallback_note})",
                 ranks=[0])
        self._select_quantized_matmul(bits)

    def _select_quantized_matmul(self, bits):
        """Decide up front whether the Pallas dequant-matmul serves this
        engine, and say why not: a Mosaic call cannot be partitioned, so a
        program over more than one device takes the XLA dequant path (which
        partitions correctly); on one TPU the kernel is put to the compiler
        at every distinct weight geometry of the model."""
        import logging

        from ..models.layers import set_quantized_matmul_enabled
        from ..ops.pallas import target_platform
        from ..ops.pallas.quantized_matmul import quantized_matmul_supported

        ok, reason = True, ""
        if self.mesh.size > 1:
            ok, reason = False, (
                f"the program spans {self.mesh.size} devices and Mosaic "
                "kernels cannot be automatically partitioned")
        elif target_platform() == "tpu":
            geoms = set()

            def walk(tree):
                if isinstance(tree, dict):
                    q = tree.get("kernel_q4", tree.get("kernel_q"))
                    if q is not None and q.ndim == 3:  # [layers, k(/2), n]
                        k = q.shape[-2] * (2 if "kernel_q4" in tree else 1)
                        geoms.add((k, q.shape[-1],
                                   tree["kernel_scale"].shape[-3],
                                   4 if "kernel_q4" in tree else 8))
                    for v in tree.values():
                        walk(v)

            walk(self.params["blocks"])
            for k, n, groups, b in sorted(geoms):
                ok, reason = quantized_matmul_supported(
                    k, n, groups, bits=b, dtype=self.dtype)
                if not ok:
                    reason = f"[{k}x{n}] int{b}: {reason}"
                    break
        set_quantized_matmul_enabled(ok)
        if not ok:
            log_dist(f"int{bits} Pallas dequant-matmul refused ({reason}); "
                     "serving through the XLA dequant path", ranks=[0],
                     level=logging.WARNING)

    def load_checkpoint(self, load_dir, tag=None):
        """Load trained weights (npz layout from the training engine); TP
        resharding is just placement per the inference specs."""
        import os

        from ..checkpoint.sharded import ShardedCheckpointEngine

        if tag is None:
            latest = os.path.join(load_dir, "latest")
            tag = open(latest).read().strip() if os.path.exists(latest) else None
        path = os.path.join(load_dir, tag) if tag else load_dir
        # the checkpoint holds FULL-PRECISION weights: build the template from
        # the model's init shapes, not self.params (which may already be
        # int8-quantized with kernel_q/kernel_scale keys the manifest lacks)
        template = jax.tree_util.tree_map(
            lambda p: jax.ShapeDtypeStruct(p.value.shape, self.dtype),
            jax.eval_shape(self.module.init, self._rng),
            is_leaf=lambda x: isinstance(x, Param))
        # sharded engine reads both layouts (per-shard pieces OR legacy npz)
        # and reshapes to the serving TP specs on load
        state, _ = ShardedCheckpointEngine().load(
            path, template={"params": template},
            shardings={"params": self.param_shardings})
        self.params = jax.tree_util.tree_map(
            lambda v, s: jax.device_put(jnp.asarray(v, self.dtype), s),
            state["params"], self.param_shardings)
        if self._config.quant.enabled:
            self._quantize_weights()
        return path

    # ------------------------------------------------------------------------------
    # forward / generate (reference engine.forward :560, patched _generate :588)
    # ------------------------------------------------------------------------------
    def forward(self, input_ids):
        """Full-sequence logits (no cache) — scoring/perplexity path.

        Causal models bucket the sequence dim (right padding cannot reach
        earlier positions under a causal mask), so varying scoring lengths
        share compiled programs; the pad columns are sliced off."""
        input_ids = jnp.asarray(input_ids)
        b, s = input_ids.shape
        # no config = unknown model: don't assume causality — right-padding a
        # bidirectional model would let pad tokens attend into real positions
        # and silently corrupt the logits (skipping the bucket only costs one
        # compile per distinct length)
        mod_cfg = getattr(self.module, "config", None)
        causal = getattr(mod_cfg, "causal", True) if mod_cfg is not None \
            else False
        padded = s
        if causal:
            padded = self._bucket_prompt_len(s, self._config.max_tokens)
            if padded > s:
                input_ids = jnp.pad(input_ids, ((0, 0), (0, padded - s)))
        if self._prefill_fn is None:
            with self.mesh:
                self._prefill_fn = jax.jit(
                    lambda p, ids: self.module.apply(p, ids))
        logits = self._prefill_fn(self.params, input_ids)
        return logits[:, :s] if padded > s else logits

    def __call__(self, input_ids):
        return self.forward(input_ids)

    def destroy(self):
        """Release device memory and compiled programs (reference
        engine.py:381 role). Jitted prefill/decode closures capture ``self``;
        without this, dropping the engine leaves a gc cycle pinning the
        weights in HBM until a full collection happens to run."""
        self.params = None
        self._prefill_fn = None
        self._decode_fn = None
        self._prefill_cache = OrderedDict()
        if self._serving is not None:
            self._serving.destroy()
            self._serving = None
        import gc

        # no jax.clear_caches(): process-global, would wipe other live
        # engines' compiled programs; dropping our wrappers is enough
        gc.collect()

    def _bucket_prompt_len(self, prompt_len, ceiling):
        """Padded prompt length for ``prompt_len`` under the configured bucket
        policy, clipped to ``ceiling`` (the KV window minus generation room).

        "multiple": next multiple of prompt_bucket_size. "pow2" (default):
        next prompt_bucket_size doubling — at most log2(max_tokens) distinct
        buckets, so together with the LRU cap below the compiled-program set
        is bounded no matter what length mix arrives."""
        bucket = max(int(self._config.prompt_bucket_size), 1)
        if bucket > 1 and self._config.prompt_bucket_policy == "pow2":
            padded = bucket
            while padded < prompt_len:
                padded *= 2
        else:
            padded = -(-prompt_len // bucket) * bucket
        return max(min(padded, ceiling), prompt_len)

    def _compiled_programs(self, key, build):
        """LRU-bounded (prefill, decode) pair for ``key`` = (batch, prompt
        bucket, sampling shape)."""
        return lru_compiled(self._prefill_cache, key, build,
                            int(self._config.compile_cache_size or 0),
                            "inference")

    def generate(self, input_ids, max_new_tokens=32, temperature=1.0, top_k=0,
                 greedy=True, eos_token_id=None, rng=None):
        """Autoregressive generation with a jitted prefill + decode loop.

        input_ids: [b, prompt_len] (uniform length; pad+mask generation is the
        serving layer's job, as in the reference's simple generate patching).
        Returns [b, prompt_len + max_new_tokens] int32.
        """
        if not hasattr(self.module, "config"):
            raise ConfigError(
                "generate() needs a zoo-style model (config with kv cache "
                "geometry + prefill/decode methods); an injection-policy-"
                "served unknown model supports forward() scoring only")
        input_ids = jnp.asarray(input_ids, jnp.int32)
        b, prompt_len = input_ids.shape
        max_len = prompt_len + max_new_tokens
        if max_len > self._config.max_tokens:
            raise ConfigError(
                f"generate: prompt {prompt_len} + max_new_tokens {max_new_tokens} "
                f"exceeds max_tokens {self._config.max_tokens}")
        # per-request rng: fold a monotonically increasing request id into the
        # engine key (two same-length requests must not share a stream); pass
        # an explicit ``rng`` for reproducible sampling
        self._request_seq += 1
        if rng is None:
            rng = jax.random.fold_in(
                jax.random.fold_in(self._rng, self._request_seq), prompt_len)

        # cache [L, b, max_len, kvh, dh]: batch over data, kv heads over model
        # (only when divisible — MQA/GQA may have fewer kv heads than tp)
        kvh = self.module.config.kv_heads
        kv_axis = MODEL_AXIS if kvh % max(self.mp_world_size, 1) == 0 else None
        batch_axis = DATA_AXIS if b % max(self.mesh.shape.get(DATA_AXIS, 1), 1) == 0 else None
        cache_sharding = NamedSharding(
            self.mesh, P(None, batch_axis, None, kv_axis, None))
        token_sharding = NamedSharding(self.mesh, P(batch_axis))

        # temperature is a RUNTIME argument (a sampling-knob change must not
        # recompile — the CUDA reference takes it per call too); greedy/top_k
        # shape the program and stay in the key. A concrete temperature of 0.0
        # IS greedy (and must stay exact argmax, not logits/1e-6 + noise).
        if isinstance(temperature, (int, float)) and temperature == 0.0:
            greedy = True

        # Batch-size BUCKETING (opt-in): pad the row dim to the next bucket by
        # repeating row 0 (garbage rows decode too; their outputs are dropped)
        # so varying request batch sizes share compiled programs.
        b_real = b
        b_bucket = max(int(self._config.batch_bucket_size), 1)
        if b % b_bucket:
            padded_b = -(-b // b_bucket) * b_bucket
            input_ids = jnp.concatenate(
                [input_ids,
                 jnp.broadcast_to(input_ids[:1],
                                  (padded_b - b,) + input_ids.shape[1:])])
            b = padded_b

        # Prompt-length BUCKETING: right-pad the prompt to the next bucket and
        # pass the true length as a traced scalar, so a TTFT-critical serving
        # loop compiles once per bucket, not once per distinct prompt length.
        padded_len = self._bucket_prompt_len(
            prompt_len, self._config.max_tokens - max_new_tokens)
        max_len = padded_len + max_new_tokens
        if padded_len > prompt_len:
            ids_in = jnp.pad(input_ids, ((0, 0), (0, padded_len - prompt_len)))
        else:
            ids_in = input_ids
        true_len = jnp.asarray(prompt_len, jnp.int32)

        key = (b, padded_len, max_new_tokens, bool(greedy), int(top_k),
               eos_token_id)

        def build():
            from ..models.decoding import (decode_tokens, decode_tokens_until,
                                           prefill_and_first_token)

            model = self.module

            def prefill(params, ids, rng, temperature, true_len):
                return prefill_and_first_token(
                    model, params, ids, rng, temperature, max_len=max_len,
                    greedy=greedy, top_k=top_k, dtype=self.dtype,
                    true_len=true_len)

            def decode(params, cache, tok, rng, temperature, true_len):
                if eos_token_id is not None:
                    # early exit inside the compiled loop once every row hit eos
                    return decode_tokens_until(
                        model, params, cache, tok, rng, temperature,
                        prompt_len=true_len, max_len=max_len,
                        steps=max_new_tokens - 1, greedy=greedy, top_k=top_k,
                        eos_token_id=int(eos_token_id))
                return decode_tokens(
                    model, params, cache, tok, rng, temperature,
                    prompt_len=true_len, max_len=max_len,
                    steps=max_new_tokens - 1, greedy=greedy, top_k=top_k)

            with self.mesh:
                return (
                    jax.jit(prefill,
                            out_shardings=(token_sharding,
                                           {"k": cache_sharding, "v": cache_sharding})),
                    jax.jit(decode, donate_argnums=(1,)),
                )

        prefill_fn, decode_fn = self._compiled_programs(key, build)
        rng, r1, r2 = jax.random.split(rng, 3)
        temp = jnp.asarray(temperature, jnp.float32)
        first, cache = prefill_fn(self.params, ids_in, r1, temp, true_len)
        out = [input_ids, first[:, None]]
        if max_new_tokens > 1:
            # the final cache is dropped, but returning it from the jitted fn
            # lets the donated input cache alias the output (no entry copy)
            toks, _ = decode_fn(self.params, cache, first, r2, temp, true_len)
            out.append(jnp.transpose(toks))
        result = jnp.concatenate(out, axis=1)
        if b_real < b:
            result = result[:b_real]
        if eos_token_id is not None:
            result = _truncate_after_eos(np.asarray(result), prompt_len, eos_token_id)
        return result

    def warmup(self, prompt_lens, max_new_tokens=32, batch_size=1,
               temperature=1.0, top_k=0, greedy=True, eos_token_id=None):
        """Precompile (and execute once) the prefill + decode programs for the
        given prompt lengths, so no live request ever pays a compile — the
        reference's capture-at-init role (cuda-graph capture on first forward,
        ``inference/engine.py:500``). Lengths collapse into prompt buckets;
        pass the production sampling shape (greedy/top_k/eos), since those
        are part of the compile key. Returns the number of compiled programs.
        """
        rng = np.random.RandomState(0)
        for p in prompt_lens:
            ids = rng.randint(0, self.module.config.vocab_size,
                              (batch_size, int(p))).astype(np.int32)
            self.generate(ids, max_new_tokens=max_new_tokens,
                          temperature=temperature, top_k=top_k, greedy=greedy,
                          eos_token_id=eos_token_id)
        return len(self._prefill_cache)

    def serve(self, requests=None, **kwargs):
        """Continuous-batching streaming serving: yields per-request
        ``TokenEvent``s as tokens are produced (``serving/engine.py``). One
        jitted decode program over a fixed slot pool; finished requests free
        their slot mid-flight and queued ones are spliced in — no
        recompilation, no waiting for the batch to drain. Configure via the
        inference config's ``serving`` block."""
        return self.serving.serve(requests, **kwargs)

    @property
    def serving(self):
        """The lazily-built ServingEngine bound to this engine's weights."""
        if self._serving is None:
            from ..serving import ServingEngine

            self._serving = ServingEngine(self)
        return self._serving

    def decode_program_report(self, loop_trip_count=1):
        """Static audit of the serving decode program: collective wire bytes,
        schedule split, AND the program-sanitizer findings (dtype leaks,
        donation coverage of the slot-pool state, host transfers, replicated
        tensors, peak-HBM estimate) — the serving-side analogue of
        ``DeepSpeedEngine.collective_wire_stats``. Triggers one audit
        compile of the decode step (pass-dump pipeline, compilation cache
        off for that compile)."""
        from ..profiling.collectives import audit_lowered
        from ..profiling.sanitizer import (ATTENTION_F32_ALLOW,
                                           merge_reports, sanitize_jaxpr)

        sv = self.serving
        dtype = {jnp.bfloat16: "bf16", jnp.float16: "f16"}.get(
            self.dtype, "f32")
        cfg = {"compute_dtype": dtype, "allow": list(ATTENTION_F32_ALLOW)}
        n = max(self.mesh.devices.size, 1)
        lowered, jaxpr = sv.trace_decode()
        report = audit_lowered(lowered, n, loop_trip_count=loop_trip_count,
                               sanitizer_config=cfg)
        report["sanitizer"] = merge_reports(
            report["sanitizer"], sanitize_jaxpr(jaxpr, config=cfg))
        return report

    def prefill_chunk_report(self, chunk_tokens=None):
        """Static audit of the chunked suffix-prefill program (one full
        chunk's bucket against a donated partial cache) — the serving-side
        fence for chunked prefill, enforced via the
        ``serving-prefill-chunked/8/bf16`` budget
        (``tools/program_lint.py --program prefill-chunked``)."""
        from ..profiling.collectives import audit_lowered
        from ..profiling.sanitizer import (ATTENTION_F32_ALLOW,
                                           merge_reports, sanitize_jaxpr)

        sv = self.serving
        dtype = {jnp.bfloat16: "bf16", jnp.float16: "f16"}.get(
            self.dtype, "f32")
        cfg = {"compute_dtype": dtype, "allow": list(ATTENTION_F32_ALLOW)}
        n = max(self.mesh.devices.size, 1)
        lowered, jaxpr = sv.trace_prefill_chunk(chunk_tokens)
        report = audit_lowered(lowered, n, sanitizer_config=cfg)
        report["sanitizer"] = merge_reports(
            report["sanitizer"], sanitize_jaxpr(jaxpr, config=cfg))
        return report

    def verify_program_report(self, spec_k=None):
        """Static audit of the speculative verify program (one target
        forward over k+1 positions per slot against the donated paged pool
        state) — the serving-side fence for speculative decoding, enforced
        via the ``serving-verify/8/bf16`` budget
        (``tools/program_lint.py --program verify``)."""
        from ..profiling.collectives import audit_lowered
        from ..profiling.sanitizer import (ATTENTION_F32_ALLOW,
                                           merge_reports, sanitize_jaxpr)

        sv = self.serving
        dtype = {jnp.bfloat16: "bf16", jnp.float16: "f16"}.get(
            self.dtype, "f32")
        cfg = {"compute_dtype": dtype, "allow": list(ATTENTION_F32_ALLOW)}
        n = max(self.mesh.devices.size, 1)
        lowered, jaxpr = sv.trace_verify(spec_k)
        report = audit_lowered(lowered, n, sanitizer_config=cfg)
        report["sanitizer"] = merge_reports(
            report["sanitizer"], sanitize_jaxpr(jaxpr, config=cfg))
        return report

    @property
    def config(self):
        return self._config


def _truncate_after_eos(tokens, prompt_len, eos):
    """Replace everything after the first EOS (per row) with EOS."""
    tokens = tokens.copy()
    gen = tokens[:, prompt_len:]
    for row in range(gen.shape[0]):
        hits = np.where(gen[row] == eos)[0]
        if hits.size:
            gen[row, hits[0]:] = eos
    tokens[:, prompt_len:] = gen
    return tokens
