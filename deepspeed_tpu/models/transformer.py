"""Transformer backbone: block + scan-over-layers stack + causal-LM wrapper.

This is the TPU-native replacement for the reference's fused transformer layer
(``deepspeed/ops/transformer/transformer.py:296`` ``DeepSpeedTransformerLayer`` backed
by ~7.4k LoC of CUDA in ``csrc/transformer/``): on TPU, XLA fuses LN/gelu/bias/dropout
into the matmuls, so the "kernel" is a plain function; the stacked blocks run under
``lax.scan`` (one compiled block, L iterations — compile time O(1) in depth) with
optional ``jax.checkpoint`` rematerialisation standing in for the reference's
activation checkpointing (``runtime/activation_checkpointing/checkpointing.py``).

The block covers the model zoo's variants:
- pre/post-norm (GPT-2/OPT pre-norm, BERT post-norm)
- learned / rotary / ALiBi position encodings (GPT-2 / LLaMA-style / BLOOM)
- MHA with optional GQA (n_kv_heads < n_heads)
- gelu MLP or SwiGLU
- parallel attention+MLP (GPT-J style)
"""

import dataclasses
import typing

import jax
import jax.numpy as jnp

from . import layers as L
from .layers import Param


@dataclasses.dataclass
class TransformerConfig:
    vocab_size: int = 50257
    max_seq_len: int = 1024
    n_layers: int = 12
    n_heads: int = 12
    d_model: int = 768
    d_ff: int = 3072
    n_kv_heads: typing.Optional[int] = None
    activation: str = "gelu_new"
    norm: str = "layernorm"  # layernorm | rmsnorm
    position_embedding: str = "learned"  # learned | rope | alibi | none
    rope_base: float = 10000.0
    # partial rotary (GPT-J rotary_dim / NeoX rotary_pct): rope the first
    # ``rotary_dim`` dims of each head, pass the rest through. None = full.
    rotary_dim: typing.Optional[int] = None
    rotary_interleaved: bool = False  # GPT-J rotate-every-two pairing
    tie_embeddings: bool = True
    head_bias: bool = False  # untied LM head with bias (GPT-J)
    mlp_bias: typing.Optional[bool] = None  # None -> use_bias (GPT-J: attn
    # projections have no bias but the MLP does)
    embed_layernorm: bool = False  # LN right after the embedding (BLOOM)
    # causal=False -> bidirectional (encoder) attention: BERT-family models
    causal: bool = True
    # segment/token-type embeddings (BERT); 0 disables
    type_vocab_size: int = 0
    # post-norm encoders (BERT) end each block with LN and have no final norm
    final_layernorm: bool = True
    # GPT-Neo-style banded local attention: window size (0 = off) and the
    # per-layer pattern ("global"/"local" strings, cycled over the layers —
    # HF GPTNeoConfig.attention_types expanded)
    local_attention_window: int = 0
    attention_layers: tuple = ()
    # attention logit scale; None = 1/sqrt(head_dim). GPT-Neo uses 1.0
    attn_scale: typing.Optional[float] = None
    use_bias: bool = True
    prenorm: bool = True
    parallel_attn_mlp: bool = False
    # parallel residual with SEPARATE norms: x + attn(ln1 x) + mlp(ln2 x)
    # (GPT-NeoX use_parallel_residual) vs GPT-J's shared ln1 for both
    parallel_norm_split: bool = False
    dropout: float = 0.0
    attn_dropout: float = 0.0
    layernorm_eps: float = 1e-5
    initializer_range: float = 0.02
    scan_layers: bool = True
    # Fused vocab-chunked cross entropy (ops/cross_entropy.py): the LM-head matmul
    # and softmax-CE as one streaming op — the [tokens, vocab] logit matrix is
    # never materialized (fwd or bwd). Big memory + bandwidth win at LLM vocabs.
    fused_ce: bool = True
    fused_ce_chunks: int = 8  # vocab chunks in the streaming CE (tuning knob)
    # "pallas": forward via the streaming Pallas kernel (chunk logits never
    # touch HBM, ops/pallas/cross_entropy.py); backward stays chunked XLA
    fused_ce_impl: str = "xla"  # xla | pallas
    remat: bool = False
    remat_policy: str = "nothing_saveable"  # nothing_saveable | dots_with_no_batch_dims
    compute_dtype: typing.Any = jnp.bfloat16
    attention_impl: str = "xla"  # xla | flash (pallas) | jax_flash (official
    # jax.experimental TPU kernel) | block_sparse (pallas)
    # "bf16": materialize XLA-attention logits/probs in bf16 (fp32
    # normalization sum) — halves the profiled [b,h,s,s] attention HBM
    # traffic; opt-in, measured by the bench sweep ("fp32" = exact default).
    # Applies to attention_impl="xla" only: flash/block_sparse never
    # materialize the logits, which is their whole point.
    attention_logits_dtype: str = "fp32"
    # block_sparse settings (reference sparse_attention_utils.py integration
    # role): pattern name + block size + pattern kwargs
    sparse_pattern: str = "fixed"  # dense|fixed|bigbird|bslongformer|variable
    sparse_block: int = 128
    sparse_pattern_config: typing.Any = None  # dict of pattern kwargs
    attention_interpret: bool = False  # pallas interpret mode (CPU tests)
    # Fused qkv projection (concat the q/k/v kernels, one matmul). The engines
    # force this OFF whenever the ``model`` mesh axis is >1: jnp.concatenate
    # along an axis the operands are sharded on is miscompiled by the SPMD
    # partitioner (jaxlib 0.4.x; a pure sharded concat returns wrong bytes),
    # and under tensor parallelism the three column-parallel matmuls are the
    # standard Megatron form anyway. Fused vs unfused is bitwise-identical
    # per output column, so flipping it never breaks parity pins.
    fused_qkv: bool = True
    # Flash-kernel tile sizes (None = kernel defaults: 256x512 fwd, 256x256
    # bwd). Tuning knobs for tools/bench_attention.py BENCH_BLOCKS sweeps.
    flash_block_q: typing.Any = None
    flash_block_kv: typing.Any = None
    flash_block_q_bwd: typing.Any = None
    flash_block_kv_bwd: typing.Any = None
    # Pipeline parallelism (set by the engine from mesh/config; see parallel/pipeline.py)
    pipeline_stages: int = 1
    pipeline_microbatches: int = 1
    mesh: typing.Any = None  # jax.sharding.Mesh when pipeline_stages > 1
    # Explicit ZeRO-3 gather schedule (set by the engine from
    # zero_optimization.zero3_gather_mode="per_layer"): constrain each scanned
    # block's params to their gathered (data-unsharded) layout INSIDE the layer
    # loop, so the compiler must gather layer-by-layer — bounded live gathered
    # params (the reference coordinator's max_live_parameters semantics,
    # partitioned_param_coordinator.py:230) instead of trusting XLA's schedule.
    zero3_per_layer_gather: bool = False
    zero3_gather_specs: typing.Any = None  # per-block spec tree (no layers dim)
    # "constraint" | "shard_map" (see config.ZeroConfig.zero3_gather_impl);
    # shard_map additionally needs the SHARDED per-block specs below
    zero3_gather_impl: str = "constraint"
    zero3_sharded_specs: typing.Any = None
    # Wire dtype of the shard_map gathers (set by the engine from
    # zero_optimization.zero3_gather_dtype): "compute" (historical — gather
    # at the compute dtype), "fp32" (gather masters, cast after), "bf16" /
    # "fp16" (explicit 16-bit wire), "int8" (ZeRO++ qwZ blockwise-quantized
    # payload + per-block fp32 scales). Masters stay sharded fp32 throughout.
    zero3_gather_dtype: str = "compute"
    zero3_gather_block: int = 256
    # Same discipline for the top-level params (wte / lm_head / ln_f / wpe):
    # {param_name: spec tree} with the data axis stripped. Without this, a
    # ZeRO-3 embedding sharded on its d_model axis (vocab % dp != 0 fallback)
    # propagates INTO the logits matmul and the partitioner partial-sums
    # full-batch logits instead of gathering the weight.
    zero3_toplevel_gather_specs: typing.Any = None
    # Sequence parallelism: shard the sequence dim over the ``seq`` mesh axis with
    # ring attention (set by the engine; see parallel/ring_attention.py)
    sequence_parallel: bool = False
    # Chunk each ring tile's kv axis: peak memory O(s_local * ring_inner_block)
    # instead of O(s_local^2) per ring step. None = whole-tile (short s_local).
    ring_inner_block: typing.Optional[int] = None
    # Serving: route the prefill (q_len == kv_len) through the flash kernel so
    # TTFT never materializes O(s^2) logits. None = auto (TPU backend only);
    # True/False force. Decode steps always keep the dense cached path.
    prefill_flash: typing.Optional[bool] = None
    # Activation quantization (reference compression/basic_layer.py:17 QuantAct
    # via compression.apply_to_model_config): fake-quantize the attention/MLP
    # residual-branch outputs in-graph. 0 = off.
    activation_quant_bits: int = 0
    activation_quant_group: int = 64
    # Explicit per-head width. None = d_model // n_heads; head-pruned models
    # (compression.redundancy_clean) keep the ORIGINAL head width while
    # n_heads shrinks, so attention width n_heads*head_dim < d_model.
    head_dim_override: typing.Optional[int] = None
    # Mixture-of-Experts (see moe/sharded_moe.py; reference deepspeed/moe/)
    n_experts: int = 0            # 0 = dense FFN
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_eval_capacity_factor: float = 0.0  # <=0: drop-free eval (capacity = seq len)
    moe_min_capacity: int = 4
    moe_aux_loss_weight: float = 0.01
    moe_noise_std: float = 0.0
    # Reference TopKGate noisy_gate_policy (sharded_moe.py:398): "jitter"
    # multiplies the gate INPUT by uniform(1±eps); "rsample" adds gumbel noise
    # to the selection logits (gates stay clean). "" = off. Training only.
    moe_noisy_gate_policy: str = ""
    # Random Token Selection (reference top1gating use_rts, sharded_moe.py:220):
    # capacity-overflow drops are decided by random priority, not sequence order
    moe_use_rts: bool = False
    # PR-MoE residual experts (reference moe/layer.py use_residual, arXiv
    # 2201.05596): a dense MLP runs alongside the experts; outputs are blended
    # by a learned 2-way softmax coefficient
    moe_use_residual: bool = False
    # Which routing the model PUBLISHES (a property of the checkpoint, not a
    # user's switch): "capacity" = GShard softmax top-k with per-group
    # capacity and drops (the reference's; gpt2_moe), "dropfree" = every
    # chosen token-expert pair is computed (moe/dropfree.py: sigmoid scores,
    # a per-expert selection bias that picks but does not weigh, weights
    # normalised over the chosen and scaled by ``moe_routed_scale``; sort by
    # expert, grouped product over ragged groups).
    moe_routing: str = "capacity"  # capacity | dropfree
    moe_routed_scale: float = 1.0  # routed_scaling_factor (dropfree)
    moe_d_ff: typing.Optional[int] = None  # one routed expert's width (None = d_ff)
    n_shared_experts: int = 0  # always-on experts: one SwiGLU of n * moe_d_ff
    # the first ``first_k_dense`` layers keep a dense FFN of width d_ff; the
    # rest are expert layers (params["dense_blocks"] beside params["blocks"])
    first_k_dense: int = 0
    # Latent attention (MLA, DeepSeek-V2/V3; models/latent.py): 0 = off. K and
    # V are expanded from one ``kv_lora_rank``-wide latent a token (+ one
    # ``qk_rope_head_dim`` rotated key shared by all heads), which is all the
    # cache holds. No q LoRA (q_lora_rank null in the supported configs).
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Window and full attention layers mixed (AFMoE, Arcee Trinity;
    # models/window_moe.py): the published ``layer_types``, one a layer
    # ("sliding_attention" sees the last ``sliding_window`` positions and
    # rotates q and k; "full_attention" sees all and rotates nothing). A
    # model that sets them gets that family's block: four norms a layer, RMS
    # norms on q and k per head, a sigmoid output gate on attention, the
    # embedding scaled by ``embed_scale``.
    layer_types: tuple = ()
    sliding_window: int = 0
    embed_scale: float = 1.0
    # The same two kinds of layer as Xiaomi MiMo-V2 publishes them
    # (``window_block="sink"``, beside it in models/window_moe.py): two norms
    # a layer, no q/k norms and no gate; q and k rotated over the first
    # ``rotary_dim`` dims in BOTH kinds, base ``rope_base`` in a full layer
    # and ``rope_base_window`` in a window layer; ``n_kv_heads`` K/V heads in
    # a full layer and ``n_kv_heads_window`` in a window layer; K rows of
    # ``head_dim`` beside V rows of ``v_head_dim``; V scaled by
    # ``attn_value_scale``; and in the window layers a learned per-head sink
    # that joins the softmax's sum and adds nothing to its output.
    window_block: str = "afmoe"  # afmoe | sink
    n_kv_heads_window: int = 0    # 0 = n_kv_heads
    rope_base_window: typing.Optional[float] = None  # None = rope_base
    attn_value_scale: float = 1.0
    # The share of a deployment's experts this program holds (moe/dropfree.
    # py): experts ``[moe_expert_offset, moe_expert_offset +
    # moe_local_experts)`` of ``n_experts``. The router keeps ``n_experts``
    # outputs and ``moe_top_k`` a token, weights normalised over all the
    # chosen; pairs on absent experts are not computed. 0 = all of them.
    moe_local_experts: int = 0
    moe_expert_offset: int = 0
    # Hybrid stacks of Mamba-2 mixers, expert layers and attention layers
    # (NVIDIA Nemotron-H, ``models/hybrid.py``): ``hybrid_pattern`` holds one
    # character a layer, "M" a Mamba-2 mixer, "E" a drop-free expert layer,
    # "*" a grouped-query attention layer without positions; each layer is
    # ``x += mixer(RMSNorm(x))``. The mixer has ``ssm_heads`` heads of
    # ``ssm_head_dim``, ``ssm_groups`` groups of B and C of ``ssm_state``
    # each, a depthwise causal conv of ``ssm_conv`` and a chunked scan in
    # blocks of ``ssm_chunk`` positions.
    hybrid_pattern: str = ""
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # LatentMoE (moe/dropfree.py): the routed experts run in a
    # ``moe_latent_size``-wide latent between two projections the layer's
    # experts share (0 = at d_model); ``moe_shared_d_ff`` is the shared
    # expert's width where it is not ``n_shared_experts * moe_d_ff``
    moe_latent_size: int = 0
    moe_shared_d_ff: typing.Optional[int] = None

    def __post_init__(self):
        # a typo here would silently run the exact fp32 path and let a
        # "bf16-logits" benchmark report fp32 numbers — normalize and refuse
        alias = {"bfloat16": "bf16", "float32": "fp32", "f32": "fp32"}
        self.attention_logits_dtype = alias.get(
            str(self.attention_logits_dtype).lower(),
            str(self.attention_logits_dtype).lower())
        if self.attention_logits_dtype not in ("fp32", "bf16"):
            raise ValueError(
                f"attention_logits_dtype must be 'fp32' or 'bf16', got "
                f"{self.attention_logits_dtype!r}")
        # same hazard for the kernel choice: the dispatch falls through to
        # the dense XLA path for anything it doesn't recognize, so a typo'd
        # impl would silently benchmark the wrong kernel (caught live by the
        # bench.py safe-fallback test, 2026-08-01)
        if self.attention_impl not in ("xla", "flash", "jax_flash",
                                       "block_sparse"):
            raise ValueError(
                f"attention_impl must be one of xla|flash|jax_flash|"
                f"block_sparse, got {self.attention_impl!r}")
        if self.moe_routing not in ("capacity", "dropfree"):
            raise ValueError(
                f"moe_routing must be 'capacity' or 'dropfree', got "
                f"{self.moe_routing!r}")
        self.layer_types = tuple(self.layer_types)
        if self.layer_types:
            kinds = {"sliding_attention", "full_attention"}
            if len(self.layer_types) != self.n_layers \
                    or not set(self.layer_types) <= kinds:
                raise ValueError(
                    f"layer_types must name one of {sorted(kinds)} for each "
                    f"of the {self.n_layers} layers, got "
                    f"{len(self.layer_types)}: {sorted(set(self.layer_types))}")
            if set(self.layer_types) != kinds or self.sliding_window < 1 \
                    or self.kv_lora_rank:
                raise ValueError(
                    "layer_types (models/window_moe.py) needs layers of both "
                    "kinds, sliding_window > 0 and per-head K and V (no "
                    "latent attention)")
        if self.n_experts > 0 and self.moe_routing == "dropfree" \
                and not (self.kv_lora_rank or self.layer_types
                         or self.hybrid_pattern):
            raise ValueError(
                "moe_routing='dropfree' is implemented beside latent "
                "attention (kv_lora_rank > 0, models/latent.py), beside "
                "the window and full attention layers of layer_types "
                "(models/window_moe.py) and in the hybrid stacks of "
                "hybrid_pattern (models/hybrid.py): the cache paths that "
                "carry its routing are theirs")
        if self.hybrid_pattern:
            if len(self.hybrid_pattern) != self.n_layers \
                    or not set(self.hybrid_pattern) <= set("ME*"):
                raise ValueError(
                    f"hybrid_pattern must give one of M, E, * for each of "
                    f"the {self.n_layers} layers, got "
                    f"{self.hybrid_pattern!r}")
            if self.layer_types or self.kv_lora_rank or self.first_k_dense \
                    or not (self.ssm_heads and self.ssm_head_dim
                            and self.ssm_state) \
                    or self.ssm_heads % self.ssm_groups \
                    or ("E" in self.hybrid_pattern
                        and self.moe_routing != "dropfree"):
                raise ValueError(
                    "hybrid_pattern (models/hybrid.py) needs ssm_heads, "
                    "ssm_head_dim and ssm_state, ssm_heads a multiple of "
                    "ssm_groups, drop-free expert layers, and no "
                    "layer_types, latent attention or leading dense layers")
        if self.window_block not in ("afmoe", "sink"):
            raise ValueError(
                f"window_block must be 'afmoe' or 'sink', got "
                f"{self.window_block!r}")
        if self.window_block == "sink" and not self.layer_types:
            raise ValueError("window_block='sink' (models/window_moe.py) "
                             "needs layer_types")
        if self.moe_local_experts or self.moe_expert_offset:
            held = self.moe_local_experts or self.n_experts
            if self.moe_routing != "dropfree" or not (
                    0 <= self.moe_expert_offset
                    and self.moe_expert_offset + held <= self.n_experts):
                raise ValueError(
                    f"moe_local_experts {self.moe_local_experts} at "
                    f"moe_expert_offset {self.moe_expert_offset} must name "
                    f"a range of the {self.n_experts} experts of a "
                    "drop-free layer (moe_routing='dropfree')")
        if self.first_k_dense and not 0 < self.first_k_dense < self.n_layers:
            raise ValueError(
                f"first_k_dense {self.first_k_dense} must leave at least one "
                f"expert layer of n_layers {self.n_layers}")
        if self.first_k_dense and self.n_experts < 1:
            raise ValueError("first_k_dense needs an expert model "
                             "(n_experts > 0)")

    @property
    def attn_logits_jnp_dtype(self):
        """None (exact fp32) or the low-precision logits dtype — the single
        switch read by both the training block and the decode path."""
        return jnp.bfloat16 if self.attention_logits_dtype == "bf16" else None

    @property
    def head_dim(self):
        return self.head_dim_override or self.d_model // self.n_heads

    @property
    def kv_heads(self):
        return self.n_kv_heads or self.n_heads

    @property
    def latent_attention(self):
        return self.kv_lora_rank > 0

    @property
    def window_layers(self):
        """Window and full attention layers mixed (``layer_types``)."""
        return bool(self.layer_types)

    @property
    def expert_d_ff(self):
        return self.moe_d_ff or self.d_ff

    @property
    def held_experts(self):
        """``(first, count)`` of the experts this program holds."""
        return self.moe_expert_offset, \
            self.moe_local_experts or self.n_experts

    @property
    def hybrid_layers(self):
        """Mamba-2, expert and attention layers mixed (``hybrid_pattern``)."""
        return bool(self.hybrid_pattern)

    @property
    def sink_window(self):
        """Window and full layers as MiMo-V2 has them (``window_block``)."""
        return self.window_block == "sink"

    def kv_geometry(self, window):
        """``{leaf: (kv_heads, width)}`` of one cached token in one layer of
        a kind (``window``: a window layer): a model of ``window_block=
        "sink"`` has another K/V head count in its window layers and V rows
        narrower than K rows."""
        heads = self.n_kv_heads_window if window and self.n_kv_heads_window \
            else self.kv_heads
        v_width = self.v_head_dim if self.sink_window and self.v_head_dim \
            else self.head_dim
        return {"k": (heads, self.head_dim), "v": (heads, v_width)}

    def group_pool_geometry(self, window):
        """``pool_geometry`` of one block group of a model of two kinds of
        layer: the merged row of that kind's K/V heads."""
        return {name: (heads * width,)
                for name, (heads, width) in self.kv_geometry(window).items()}

    @property
    def cache_geometry(self):
        """``{leaf: (kv_heads, width)}`` of one cached token in one layer:
        what every cache allocator (dense, paged, block writer) sizes its
        ``k`` and ``v`` leaves by. Latent attention caches one normed latent
        row (``k``) and one rotated shared key (``v``) a token, not per-head
        K and V."""
        if self.latent_attention:
            return {"k": (1, self.kv_lora_rank),
                    "v": (1, self.qk_rope_head_dim)}
        if self.sink_window:
            # the dense cache holds every layer at the wider kind's head
            # count; a layer of the other kind fills its own heads
            return max(self.kv_geometry(True), self.kv_geometry(False),
                       key=lambda geometry: geometry["k"][0])
        return {"k": (self.kv_heads, self.head_dim),
                "v": (self.kv_heads, self.head_dim)}

    @property
    def pool_geometry(self):
        """``{leaf: row shape}`` of one token in one layer of the PAGED
        pool. Per-head K and V are stored merged, ``(kv_heads * head_dim,)``:
        a token's whole row is the leaf's minor-most axis, so the device
        keeps a block of tokens contiguous whatever the head size (a leaf
        that ends in a 64-wide axis gets its largest axis, the blocks, in
        the lanes: PERF.md, PRs 27 and 30). The latent leaves already keep
        their row or their tokens there and stay ``(1, width)``."""
        if self.latent_attention:
            return self.cache_geometry
        return {name: (heads * width,)
                for name, (heads, width) in self.cache_geometry.items()}

    def num_params(self):
        """Analytic parameter count (embedding + blocks + final norm)."""
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        if self.hybrid_layers:
            from .hybrid import param_count

            return param_count(self)
        if self.sink_window:
            # q and o, K and V by the layer's kind, a sink a head in a
            # window layer, two norms; the experts held, the whole router
            H, kd = self.n_heads, self.first_k_dense
            attn = 0
            for kind in self.layer_types:
                (g, dk), (_, dv) = self.kv_geometry(
                    kind == "sliding_attention").values()
                attn += d * H * dk + H * dv * d + d * g * (dk + dv) + 2 * d \
                    + (H if kind == "sliding_attention" else 0)
            E = self.n_experts
            experts = self.held_experts[1] * 3 * d * self.expert_d_ff \
                + d * E + E
            return int(attn + kd * 3 * d * f + (self.n_layers - kd) * experts
                       + d + (1 if self.tie_embeddings else 2) * v * d)
        per_block = 4 * d * d * (self.kv_heads / self.n_heads if self.n_kv_heads else 1.0)
        # more precisely: q:d*q_dim, k,v:d*kv_dim, o:q_dim*d (q_dim < d for
        # head-pruned models with head_dim_override)
        q_dim = self.n_heads * self.head_dim
        kv_dim = self.kv_heads * self.head_dim
        per_block = d * q_dim + 2 * d * kv_dim + q_dim * d
        if self.latent_attention:
            H, r = self.n_heads, self.kv_lora_rank
            dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                          self.v_head_dim)
            per_block = (d * H * (dn + dr) + d * (r + dr) + r
                         + r * H * (dn + dv) + H * dv * d)
        if self.window_layers:  # (afmoe; the sink form is counted above)
            # the output gate, the q and k norms, the two post-branch norms
            per_block += d * q_dim + 2 * self.head_dim + 2 * d
        ffn = (3 if self.activation == "swiglu" else 2) * d * f
        per_block += 4 * d if self.use_bias else 0
        per_block += 2 * d  # two norms (scale+bias counted roughly)
        if self.n_experts > 0 and self.moe_routing == "dropfree":
            fe = self.expert_d_ff
            expert_ffn = (self.n_experts * 3 * d * fe
                          + self.n_shared_experts * 3 * d * fe
                          + d * self.n_experts + self.n_experts)
            kd = self.first_k_dense
            total = (self.n_layers * per_block + kd * ffn
                     + (self.n_layers - kd) * expert_ffn + v * d)
        else:
            total = self.n_layers * (per_block + ffn) + v * d
        if self.position_embedding == "learned":
            total += self.max_seq_len * d
        if not self.tie_embeddings:
            total += v * d
        return int(total)


def _norm_init(cfg):
    return L.layernorm_init(cfg.d_model) if cfg.norm == "layernorm" else L.rmsnorm_init(cfg.d_model)


def _norm_apply(cfg, p, x):
    if cfg.norm == "layernorm":
        return L.layernorm_apply(p, x, eps=cfg.layernorm_eps)
    return L.rmsnorm_apply(p, x, eps=cfg.layernorm_eps)


def _mlp_init(rng, cfg):
    k1, k2, k3 = jax.random.split(rng, 3)
    std = cfg.initializer_range
    # GPT-2 scales residual-projection init by 1/sqrt(2L)
    out_std = std / (2.0 * cfg.n_layers) ** 0.5
    bias = cfg.use_bias if cfg.mlp_bias is None else cfg.mlp_bias
    if cfg.activation == "swiglu":
        return {
            "gate": L.linear_init(k1, cfg.d_model, cfg.d_ff, ("embed", "mlp"), bias, std),
            "up": L.linear_init(k2, cfg.d_model, cfg.d_ff, ("embed", "mlp"), bias, std),
            "down": L.linear_init(k3, cfg.d_ff, cfg.d_model, ("mlp", "embed"), bias, out_std),
        }
    return {
        "fc": L.linear_init(k1, cfg.d_model, cfg.d_ff, ("embed", "mlp"), bias, std),
        "proj": L.linear_init(k2, cfg.d_ff, cfg.d_model, ("mlp", "embed"), bias, out_std),
    }


def _mlp_apply(cfg, p, x, tp_manual=False):
    from jax.ad_checkpoint import checkpoint_name

    # tp_manual: column-parallel in (local hidden shard), row-parallel out with
    # an explicit psum over the model axis (used inside manual regions where
    # the SPMD partitioner cannot insert the collective itself, e.g. 1F1B x TP)
    out = (lambda w, h: L.linear_apply_rowparallel(w, h, "model")) \
        if tp_manual else L.linear_apply
    if tp_manual:
        x = L.tp_copy(x, "model")  # completes dL/dx with a backward psum
    if cfg.activation == "swiglu":
        gate = checkpoint_name(L.linear_apply(p["gate"], x), "mlp_hidden")
        up = checkpoint_name(L.linear_apply(p["up"], x), "mlp_hidden")
        return out(p["down"], jax.nn.silu(gate) * up)
    act = L.ACTIVATIONS[cfg.activation]
    h = checkpoint_name(L.linear_apply(p["fc"], x), "mlp_hidden")
    return out(p["proj"], act(h))


def block_init(rng, cfg):
    if cfg.window_layers:
        from .window_moe import block_init as window_block_init

        return window_block_init(rng, cfg)
    k_attn, k_mlp = jax.random.split(rng)
    out_std = cfg.initializer_range / (2.0 * cfg.n_layers) ** 0.5
    if cfg.n_experts > 0 and cfg.moe_routing == "dropfree":
        from ..moe.dropfree import dropfree_moe_init

        mlp = dropfree_moe_init(k_mlp, cfg)
    elif cfg.n_experts > 0:
        from ..moe import moe_mlp_init

        mlp = moe_mlp_init(k_mlp, cfg)
    else:
        mlp = _mlp_init(k_mlp, cfg)
    if cfg.latent_attention:
        from .latent import latent_attention_init

        attn = latent_attention_init(k_attn, cfg, out_std)
    else:
        attn = L.attention_init(
            k_attn, cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.use_bias,
            cfg.initializer_range, out_stddev=out_std, head_dim=cfg.head_dim,
        )
    return {
        "ln_1": _norm_init(cfg),
        "attn": attn,
        "ln_2": _norm_init(cfg),
        "mlp": mlp,
    }


def _shard_map_gather(cfg, p):
    """Per-leaf explicit all_gather over the ``data`` mesh axis.

    Input leaves carry their ZeRO-3 sharded layout (``zero3_sharded_specs``);
    the output is the gathered layout (``zero3_gather_specs``). Each leaf with
    a data-sharded dim becomes a shard_map island whose body is ONE tiled
    ``jax.lax.all_gather`` — something a sharding constraint cannot pin (the
    partitioner reshards an elementwise op's input to match its constrained
    output, so cast/quantize-then-gather is inexpressible there). Leaves
    without a data shard pass through.

    Wire dtype per ``cfg.zero3_gather_dtype`` (matmul-weight leaves, ndim>=2):
    - ``"compute"`` / 16-bit names: the leaf is gathered at whatever dtype it
      holds (the compute dtype after ``_cast_block_params``; the explicit
      cast-before-wire corner only triggers when the leaf dtype differs,
      e.g. a bf16 wire under fp32 compute);
    - ``"int8"``: ZeRO++-style blockwise-quantized gather
      (``comm/collectives.all_gather_quantized``, per-block fp32 scales,
      straight-through backward);
    - ``"fp32"``: plain gather of the (fp32 master) leaf.
    1-D leaves (biases, norm scales) always gather at their own dtype — they
    are persistence-threshold-sized and norm math wants them exact.
    """
    from ..comm.collectives import all_gather_cast, all_gather_quantized
    from ..parallel.topology import DATA_AXIS

    wire = getattr(cfg, "zero3_gather_dtype", "compute") or "compute"
    wire_dtype = {"compute": cfg.compute_dtype, "bf16": jnp.bfloat16,
                  "fp16": jnp.float16, "fp32": None, "int8": None}[wire]

    def has_data(s):
        return s == DATA_AXIS or (isinstance(s, tuple) and DATA_AXIS in s)

    def one(a, sharded, gathered):
        axes = [i for i, s in enumerate(tuple(sharded)) if has_data(s)]
        if not axes:
            return a
        k = axes[0]
        compressible = a.ndim >= 2 and jnp.issubdtype(a.dtype, jnp.floating)
        if wire == "int8" and compressible:
            body = lambda x: all_gather_quantized(
                x, DATA_AXIS, axis=k, block=cfg.zero3_gather_block,
                out_dtype=a.dtype)
        elif compressible and wire_dtype is not None and a.dtype != wire_dtype:
            body = lambda x: all_gather_cast(
                x, DATA_AXIS, axis=k, wire_dtype=wire_dtype, out_dtype=a.dtype)
        else:
            body = lambda x: jax.lax.all_gather(x, DATA_AXIS, axis=k,
                                                tiled=True)
        f = jax.shard_map(
            body, mesh=cfg.mesh, in_specs=sharded, out_specs=gathered,
            # the varying-mesh-axes inference can't prove an all_gather
            # output replicated; it is (by construction of the collective)
            check_vma=False)
        return f(a)

    return jax.tree_util.tree_map(one, p, cfg.zero3_sharded_specs,
                                  cfg.zero3_gather_specs)


def _cast_block_params(cfg, p):
    """fp32 masters -> compute dtype for the matmul weights. Norm params stay
    fp32 (layernorm computes in fp32 internally anyway); int8 (weight-only-
    quantized) leaves must NOT be cast — their dequant scale lives next to
    them and linear_apply fuses it into the matmul; MoE params cast inside
    moe_mlp_apply (router stays fp32 for stable gating). Idempotent."""
    cast = lambda a: a.astype(cfg.compute_dtype) \
        if jnp.issubdtype(a.dtype, jnp.floating) else a
    return {
        "ln_1": p["ln_1"],
        "ln_2": p["ln_2"],
        "attn": jax.tree_util.tree_map(cast, p["attn"]),
        "mlp": p["mlp"] if cfg.n_experts > 0 else jax.tree_util.tree_map(
            cast, p["mlp"]),
    }


def block_apply(cfg, p, x, mask=None, rope=None, alibi=None, deterministic=True,
                dropout_rng=None, kv_mask=None, seq_manual=False,
                tp_manual=False):
    """One transformer block. x: [batch, seq, d_model] in compute dtype.
    Returns ``(x, aux_loss)`` — aux is the MoE load-balancing term (0 for dense).

    Params arrive as fp32 masters and are cast to the compute dtype here (norm
    params stay fp32 — layernorm computes in fp32 internally anyway)."""
    x = x.astype(cfg.compute_dtype)
    p = _cast_block_params(cfg, p)
    b, s, d = x.shape

    from jax.ad_checkpoint import checkpoint_name

    def attn(h):
        pa = p["attn"]
        if cfg.latent_attention:
            if tp_manual or cfg.sequence_parallel or mask is not None:
                raise NotImplementedError(
                    "latent attention runs plain causal attention on one "
                    "model shard (no manual TP, ring attention or padding "
                    "mask)")
            from .latent import attention_uncached

            return attention_uncached(cfg, pa, h, rope)
        if tp_manual:
            h = L.tp_copy(h, "model")  # completes dL/dh with a backward psum
        if "kernel" in pa["q"] and cfg.fused_qkv:
            # one fused qkv matmul (the reference's c_attn / fused qkv gemm):
            # concat of the kernels is a cheap copy next to the [tokens, d] x
            # [d, d+2kv] matmul it enables — wider N keeps the MXU busier than
            # three narrow matmuls. Bitwise-identical per output column.
            # Widths come from the kernels (not cfg) so a tp_manual caller can
            # hand in LOCAL head shards and everything below just works.
            q_w = pa["q"]["kernel"].shape[1]
            kv_w = pa["k"]["kernel"].shape[1]
            wqkv = jnp.concatenate(
                [pa["q"]["kernel"], pa["k"]["kernel"], pa["v"]["kernel"]], axis=1)
            qkv = h @ wqkv
            if "bias" in pa["q"]:
                qkv = qkv + jnp.concatenate(
                    [pa["q"]["bias"], pa["k"]["bias"], pa["v"]["bias"]])
            q, k, v = (qkv[..., :q_w], qkv[..., q_w:q_w + kv_w],
                       qkv[..., q_w + kv_w:])
        else:  # quantized serving path keeps per-matrix dequant
            q = L.linear_apply(pa["q"], h)
            k = L.linear_apply(pa["k"], h)
            v = L.linear_apply(pa["v"], h)
        q = q.reshape(b, s, q.shape[-1] // cfg.head_dim, cfg.head_dim)
        k = k.reshape(b, s, k.shape[-1] // cfg.head_dim, cfg.head_dim)
        v = v.reshape(b, s, v.shape[-1] // cfg.head_dim, cfg.head_dim)
        q = checkpoint_name(q, "q_proj")
        k = checkpoint_name(k, "k_proj")
        v = checkpoint_name(v, "v_proj")
        if rope is not None:
            cos, sin = rope
            q = L.apply_rotary(q, cos, sin, cfg.rotary_dim,
                               cfg.rotary_interleaved)
            k = L.apply_rotary(k, cos, sin, cfg.rotary_dim,
                               cfg.rotary_interleaved)
        n_rep = cfg.n_heads // cfg.kv_heads
        k = L._repeat_kv(k, n_rep)
        v = L._repeat_kv(v, n_rep)
        if cfg.sequence_parallel:
            from ..parallel.ring_attention import (ring_attention,
                                                   ring_attention_manual)

            if seq_manual:
                # already inside the pipeline's manual region over {pipe, seq}
                out = ring_attention_manual(q, k, v, kv_mask=kv_mask,
                                            causal=cfg.causal,
                                            scale=cfg.attn_scale,
                                            inner_block=cfg.ring_inner_block)
            else:
                out = ring_attention(q, k, v, cfg.mesh, kv_mask=kv_mask,
                                     causal=cfg.causal, scale=cfg.attn_scale,
                                     inner_block=cfg.ring_inner_block)
            out = checkpoint_name(out, "attn_out")
            return o_proj(out)
        # pallas paths: plain attention only — padding mask / alibi / dropout
        # take the dense path. Every kernel runs per shard of cfg.mesh
        # (ops/pallas shard_kernel): GSPMD cannot partition a Mosaic call.
        kernel_ok = (alibi is None and mask is None
                     and (deterministic or cfg.attn_dropout == 0.0))
        if cfg.attention_impl == "block_sparse" and kernel_ok:
            from ..ops.flash_attention import shard_attention

            out = shard_attention(_block_sparse_attn(cfg, s), cfg.mesh,
                                  q, k, v)
            out = checkpoint_name(out, "attn_out")
            return o_proj(out)
        flash_ok = cfg.attention_impl in ("flash", "jax_flash") and kernel_ok
        if flash_ok:
            if cfg.attention_impl == "jax_flash":
                from ..ops.flash_attention import jax_flash_attention

                out = jax_flash_attention(q, k, v, causal=cfg.causal,
                                          scale=cfg.attn_scale, mesh=cfg.mesh)
            else:
                from ..ops.flash_attention import flash_attention

                out = flash_attention(q, k, v, causal=cfg.causal,
                                      scale=cfg.attn_scale,
                                      block_q=cfg.flash_block_q,
                                      block_kv=cfg.flash_block_kv,
                                      block_q_bwd=cfg.flash_block_q_bwd,
                                      block_kv_bwd=cfg.flash_block_kv_bwd,
                                      interpret=cfg.attention_interpret,
                                      mesh=cfg.mesh)
        else:
            dense_mask = mask if mask is not None else (
                L.causal_mask(s, s) if cfg.causal else None)
            drop_rng = None
            if not deterministic and dropout_rng is not None and cfg.attn_dropout > 0:
                drop_rng = jax.random.fold_in(dropout_rng, 1)
            out = L.dot_product_attention(
                q, k, v, mask=dense_mask, scale=cfg.attn_scale,
                dropout_rate=0.0 if deterministic else cfg.attn_dropout,
                dropout_rng=drop_rng, alibi_bias=alibi,
                logits_dtype=cfg.attn_logits_jnp_dtype,
            )
        out = checkpoint_name(out, "attn_out")
        return o_proj(out)

    def o_proj(out):
        out = out.reshape(b, s, -1)  # local width under tp_manual
        if tp_manual:
            return L.linear_apply_rowparallel(p["attn"]["o"], out, "model")
        return L.linear_apply(p["attn"]["o"], out)

    def maybe_drop(h, salt):
        if deterministic or cfg.dropout == 0.0 or dropout_rng is None:
            return h
        return L.dropout(jax.random.fold_in(dropout_rng, salt), h, cfg.dropout, False)

    aux = jnp.zeros((), jnp.float32)

    def mlp(h):
        nonlocal aux
        if cfg.n_experts > 0:
            if tp_manual:
                raise NotImplementedError(
                    "MoE layers do not compose with the manual-TP block "
                    "(1F1B x TP); use the GPipe schedule for MoE pipelines")
            if cfg.moe_routing == "dropfree":
                from ..moe.dropfree import dropfree_moe_apply

                return dropfree_moe_apply(cfg, p["mlp"], h)[0]
            from ..moe import moe_mlp_apply

            moe_rng = (jax.random.fold_in(dropout_rng, 4)
                       if dropout_rng is not None else None)
            out, aux_i = moe_mlp_apply(cfg, p["mlp"], h, deterministic=deterministic,
                                       rng=moe_rng)
            aux = aux + aux_i
            return out
        return _mlp_apply(cfg, p["mlp"], h, tp_manual=tp_manual)

    def qact(h):
        # activation fake-quant on the residual branches (QuantAct role,
        # compression/basic_layer.py:17) — dynamic symmetric groupwise range,
        # straight-through gradient; fuses into the surrounding elementwise ops
        if not cfg.activation_quant_bits:
            return h
        from ..ops.quantizer import fake_quantize

        return fake_quantize(h, bits=cfg.activation_quant_bits,
                             group_size=cfg.activation_quant_group)

    if cfg.parallel_attn_mlp:
        h = _norm_apply(cfg, p["ln_1"], x)
        h_mlp = _norm_apply(cfg, p["ln_2"], x) if cfg.parallel_norm_split else h
        return x + maybe_drop(qact(attn(h)), 2) + maybe_drop(qact(mlp(h_mlp)), 3), aux
    elif cfg.prenorm:
        x = x + maybe_drop(qact(attn(_norm_apply(cfg, p["ln_1"], x))), 2)
        x = x + maybe_drop(qact(mlp(_norm_apply(cfg, p["ln_2"], x))), 3)
        return x, aux
    else:
        # post-norm (BERT)
        x = _norm_apply(cfg, p["ln_1"], x + maybe_drop(qact(attn(x)), 2))
        x = _norm_apply(cfg, p["ln_2"], x + maybe_drop(qact(mlp(x)), 3))
        return x, aux


def _remat_policy(cfg):
    """Named checkpoint policies. "minimal" saves only the cheap named activations
    (projections, mlp hidden) and recomputes the O(s^2) attention internals in bwd —
    the reference's "selective activation checkpointing" sweet spot."""
    return {
        "nothing_saveable": jax.checkpoint_policies.nothing_saveable,
        "dots_with_no_batch_dims": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        "everything_saveable": jax.checkpoint_policies.everything_saveable,
        "minimal": jax.checkpoint_policies.save_only_these_names(
            # attn_lse: the flash kernel's softmax statistics ([tokens, 1] —
            # trivial HBM) — without it the backward re-runs the whole forward
            # flash kernel per layer just to regenerate the lse residual
            "q_proj", "k_proj", "v_proj", "attn_out", "attn_lse", "mlp_hidden"
        ),
        # minimal minus mlp_hidden: the [tokens, d_ff] save is ~60% of
        # "minimal"'s per-layer HBM; dropping it costs one fc GEMM recompute
        # in the backward — unlocks larger micro-batches on a 16 GB chip
        "minimal_nomlp": jax.checkpoint_policies.save_only_these_names(
            "q_proj", "k_proj", "v_proj", "attn_out", "attn_lse"
        ),
    }[cfg.remat_policy]


def stack_init(rng, cfg):
    """Init all blocks stacked along a leading "layers" dim via vmap — the pytree has
    one leaf per block param with shape [n_layers, ...]. This is what makes
    scan-over-layers (and per-layer ZeRO-3 gathering) natural."""
    rngs = jax.random.split(rng, cfg.n_layers)
    # under first_k_dense these are the expert layers alone (the leading
    # dense ones: dense_stack_init); the keys stay one a layer of the model
    stacked = jax.vmap(lambda r: block_init(r, cfg))(
        rngs[cfg.first_k_dense:])

    def prepend_layers(param):
        return Param(param.value, ("layers",) + param.axes)

    return jax.tree_util.tree_map(
        prepend_layers, stacked, is_leaf=lambda x: isinstance(x, Param)
    )


def dense_stack_init(rng, cfg):
    """The ``first_k_dense`` leading layers of a model whose later layers
    are experts: the same block with a dense FFN of width ``d_ff``, stacked
    on their own (``params["dense_blocks"]``) because their leaves are not
    the expert layers' leaves. Keys as ``stack_init`` splits them."""
    from .latent import dense_cfg

    rngs = jax.random.split(rng, cfg.n_layers)[:cfg.first_k_dense]
    stacked = jax.vmap(lambda r: block_init(r, dense_cfg(cfg)))(rngs)
    return jax.tree_util.tree_map(
        lambda p: Param(p.value, ("layers",) + p.axes), stacked,
        is_leaf=lambda x: isinstance(x, Param))


_SPARSE_ATTN_CACHE = {}


def _block_sparse_attn(cfg, seq):
    """Config-driven block-sparse attention kernel, cached per shape/pattern
    (layout preprocessing is host-side numpy; the kernel itself is traced).
    The reference reaches this through ``SparseAttentionUtils`` model surgery;
    here it is an ``attention_impl`` choice."""
    from ..ops import sparse_attention as SA
    from ..ops.pallas.block_sparse_attention import BlockSparseAttention

    key = (cfg.sparse_pattern, cfg.sparse_block,
           repr(cfg.sparse_pattern_config), seq, cfg.causal,
           cfg.attn_scale, cfg.attention_interpret)
    if key not in _SPARSE_ATTN_CACHE:
        cls = {
            "dense": SA.DenseSparsityConfig,
            "fixed": SA.FixedSparsityConfig,
            "bigbird": SA.BigBirdSparsityConfig,
            "bslongformer": SA.BSLongformerSparsityConfig,
            "variable": SA.VariableSparsityConfig,
        }[cfg.sparse_pattern]
        sp = cls(block=cfg.sparse_block, **dict(cfg.sparse_pattern_config or {}))
        _SPARSE_ATTN_CACHE[key] = BlockSparseAttention(
            sp, seq, causal=cfg.causal, scale=cfg.attn_scale,
            interpret=cfg.attention_interpret)
    return _SPARSE_ATTN_CACHE[key]


def local_attention_flags(cfg):
    """Per-layer is-local booleans for banded local attention (HF GPT-Neo
    attention_types cycling). The ONE place the pattern expands — shared by
    the training masks and the KV-cache decode path so they cannot drift."""
    pat = cfg.attention_layers or ("global", "local")
    return [pat[i % len(pat)] == "local" for i in range(cfg.n_layers)]


def stack_apply(cfg, stacked_params, x, mask=None, rope=None, alibi=None,
                deterministic=True, dropout_rng=None, kv_mask=None,
                pld_theta=None, dense_params=None):
    """Run the L blocks; returns ``(x, aux_loss)``. scan_layers=True: one compiled
    block iterated L times (compile-time constant in depth); False: unrolled python
    loop (better for very shallow nets / per-layer sharding experiments).
    ``dense_params``: the stacked ``first_k_dense`` leading dense blocks of a
    model whose layers are not alike; they run unrolled before the scan."""
    if cfg.first_k_dense:
        if cfg.pipeline_stages > 1 or cfg.zero3_per_layer_gather \
                or cfg.local_attention_window > 0 or pld_theta is not None:
            raise NotImplementedError(
                "first_k_dense (a stack of unlike layers) does not compose "
                "with pipeline stages, the per-layer ZeRO-3 gather, banded "
                "local attention or progressive layer drop")
        from .latent import dense_cfg

        dcfg = dense_cfg(cfg)
        for i in range(cfg.first_k_dense):
            p_i = jax.tree_util.tree_map(lambda a: a[i], dense_params)
            dense_block = lambda p, h: block_apply(
                dcfg, p, h, mask=mask, rope=rope, alibi=alibi,
                deterministic=deterministic, kv_mask=kv_mask,
                dropout_rng=jax.random.fold_in(dropout_rng, i)
                if dropout_rng is not None else None)[0]
            if cfg.remat:
                dense_block = jax.checkpoint(dense_block,
                                             policy=_remat_policy(cfg))
            x = dense_block(p_i, x)
    if cfg.sequence_parallel:
        if cfg.mesh is None:
            raise ValueError("sequence_parallel requires cfg.mesh to be set")
        if cfg.pipeline_stages > 1 and kv_mask is not None:
            raise NotImplementedError(
                "padding kv_mask not supported with sequence_parallel + pipeline"
            )
        if cfg.position_embedding == "alibi":
            raise NotImplementedError("alibi bias not supported with ring attention")
        if cfg.attn_dropout > 0 and not deterministic:
            raise NotImplementedError("attention dropout not supported with ring attention")
    if cfg.pipeline_stages > 1:
        if cfg.local_attention_window > 0:
            raise NotImplementedError(
                "local_attention_window not supported with pipeline parallelism")
        if pld_theta is not None:
            raise NotImplementedError(
                "progressive layer drop not supported with pipeline parallelism")
        return _pipeline_stack(cfg, stacked_params, x, mask, rope, alibi,
                               deterministic, dropout_rng)

    # GPT-Neo-style banded local attention: per-layer global/local masks
    # (HF GPTNeoConfig.attention_types; reference container containers/gptneo.py)
    local_pattern = None
    local_mask = None
    if cfg.local_attention_window > 0:
        if cfg.sequence_parallel or not cfg.causal:
            raise NotImplementedError(
                "local_attention_window requires a causal, non-SP model")
        s = x.shape[1]
        qi = jnp.arange(s)[:, None]
        ki = jnp.arange(s)[None, :]
        band = (qi >= ki) & (qi - ki < cfg.local_attention_window)
        gmask = mask if mask is not None else L.causal_mask(s, s)
        local_mask = gmask & band
        local_pattern = local_attention_flags(cfg)

    def _constrain(p, specs):
        from jax.sharding import NamedSharding

        return jax.tree_util.tree_map(
            lambda a, s: jax.lax.with_sharding_constraint(
                a, NamedSharding(cfg.mesh, s)),
            p, specs)

    def body(p, h, rng, m):
        # ZeRO-3 per_layer gather, INSIDE the remat region: the bwd
        # re-gathers instead of saving 40 layers of gathered weights as scan
        # residuals (measured +50 GB/chip on the OPT-13B/256 projection when
        # the gather sat outside jax.checkpoint).
        if cfg.zero3_per_layer_gather and cfg.zero3_gather_specs is not None:
            if (cfg.zero3_gather_impl == "shard_map"
                    and cfg.zero3_sharded_specs is not None):
                # explicit all_gather island with the wire dtype pinned
                # BEFORE the collective (compute-dtype cast or int8
                # quantization) — half/quarter the wire of gathering the
                # fp32 master (which is all the constraint impl below can
                # express — the partitioner reshards an elementwise op's
                # input to match its constrained output, and both
                # jax.sharding.reshard and an optimization_barrier broke
                # Shardy propagation for the surrounding scan)
                if cfg.zero3_gather_dtype == "fp32":
                    # explicit-but-fp32 wire: gather the masters, cast after
                    p = _cast_block_params(cfg, _shard_map_gather(cfg, p))
                else:
                    p = _shard_map_gather(cfg, _cast_block_params(cfg, p))
            else:
                # "constraint": fp32-sized gather wire, a known 2x
                # (PARITY.md known gaps); overlap headroom absorbs it
                # (scale_projection: 3.3x at OPT-13B/v4-256 micro=1)
                p = _constrain(_cast_block_params(cfg, p),
                               cfg.zero3_gather_specs)
        return block_apply(
            cfg, p, h, mask=m, rope=rope, alibi=alibi,
            deterministic=deterministic, dropout_rng=rng, kv_mask=kv_mask,
        )

    if cfg.remat:
        body = jax.checkpoint(body, policy=_remat_policy(cfg), static_argnums=())

    def pld_select(i, h_new, h_prev, aux_i, rng_i):
        """Progressive layer drop (reference ``progressive_layer_drop.py``):
        keep layer i with prob 1 - (i/L)(1 - theta); a dropped layer passes
        the residual stream through untouched (no rescale, as in the paper).
        """
        if pld_theta is None or deterministic or dropout_rng is None:
            return h_new, aux_i
        keep_p = 1.0 - (i.astype(jnp.float32) / cfg.n_layers) * (1.0 - pld_theta)
        keep = jax.random.bernoulli(jax.random.fold_in(rng_i, 9), keep_p)
        return (jnp.where(keep, h_new, h_prev),
                jnp.where(keep, aux_i, jnp.zeros_like(aux_i)))

    aux = jnp.zeros((), jnp.float32)
    # Banded local attention scans too: the per-layer global/local choice is a
    # traced boolean scanned alongside the stacked weights, selecting between
    # the two precomputed [s, s] masks in-graph — compile time stays constant
    # in depth. Only pallas attention keeps the unrolled loop (an explicit
    # mask forces the kernels' dense fallback, so the python-level mask=None
    # on global layers is what keeps them kernel-eligible there).
    unrolled = not cfg.scan_layers or (
        local_pattern is not None
        and cfg.attention_impl in ("flash", "jax_flash", "block_sparse"))
    if unrolled:
        for i in range(cfg.n_layers - cfg.first_k_dense):
            p_i = jax.tree_util.tree_map(lambda a: a[i], stacked_params)
            rng_i = jax.random.fold_in(dropout_rng, cfg.first_k_dense + i) \
                if dropout_rng is not None else None
            m_i = local_mask if (local_pattern is not None and local_pattern[i]) \
                else mask
            h_new, aux_i = body(p_i, x, rng_i, m_i)
            x, aux_i = pld_select(jnp.asarray(i), h_new, x, aux_i, rng_i)
            aux = aux + aux_i
        return x, aux

    def scan_step(h, i, aux, p, m_i):
        rng_i = jax.random.fold_in(dropout_rng, i) if dropout_rng is not None else None
        h_new, aux_i = body(p, h, rng_i, m_i)
        h, aux_i = pld_select(i, h_new, h, aux_i, rng_i)
        return h, i + 1, aux + aux_i

    if local_pattern is not None:
        # gmask was built alongside local_mask above; the per-layer choice is
        # a traced flag scanned with the weights
        def scan_fn(carry, xs):
            p, is_local = xs
            return scan_step(*carry, p, jnp.where(is_local, local_mask, gmask)), None

        xs_in = (stacked_params, jnp.asarray(local_pattern))
    else:
        def scan_fn(carry, xs):
            return scan_step(*carry, xs, mask), None

        xs_in = stacked_params

    (x, _, aux), _ = jax.lax.scan(
        scan_fn, (x, jnp.full((), cfg.first_k_dense, jnp.int32), aux), xs_in
    )
    return x, aux


def _pipeline_stack(cfg, stacked_params, x, mask, rope, alibi, deterministic,
                    dropout_rng):
    """Pipeline-parallel path of ``stack_apply`` (see parallel/pipeline.py)."""
    from ..parallel.pipeline import pipeline_stack_apply

    if cfg.mesh is None:
        raise ValueError("pipeline_stages > 1 requires cfg.mesh to be set")

    # Batched side inputs must travel with their microbatch through the pipe
    # rotation; unbatched ones ride the closure. Shapes from CausalLM.apply:
    # mask [b,1,q,kv] (causal-only masks are [1,1,q,kv]), rope cos/sin [b,s,hd/2].
    b = x.shape[0]
    seq_manual = cfg.sequence_parallel
    side = {}
    if mask is not None and mask.ndim == 4 and mask.shape[0] == b and b > 1:
        if seq_manual:
            raise NotImplementedError(
                "batched attention masks not supported with sequence_parallel "
                "+ pipeline (ring attention computes causal masking itself)")
        side["mask"] = mask
    if rope is not None and rope[0].ndim == 3 and rope[0].shape[0] == b:
        side["rope_cos"], side["rope_sin"] = rope

    def pipe_block(p, h, side_mb, rng):
        m = side_mb["mask"] if "mask" in side_mb else mask
        r = ((side_mb["rope_cos"], side_mb["rope_sin"])
             if "rope_cos" in side_mb else rope)
        return block_apply(cfg, p, h, mask=m, rope=r, alibi=alibi,
                           deterministic=deterministic, dropout_rng=rng,
                           seq_manual=seq_manual)

    if cfg.remat:
        pipe_block = jax.checkpoint(pipe_block, policy=_remat_policy(cfg))

    def block_fn(p, h, side_mb, layer_idx, mb_idx):
        # fold in both layer and microbatch so dropout masks are independent
        # across the accumulation window (non-pipeline grad-accum draws a fresh
        # step rng per micro-step)
        rng_i = None
        if dropout_rng is not None:
            rng_i = jax.random.fold_in(
                jax.random.fold_in(dropout_rng, layer_idx), mb_idx
            )
        return pipe_block(p, h, side_mb, rng_i)

    return pipeline_stack_apply(
        cfg, stacked_params, x, mesh=cfg.mesh,
        n_microbatches=cfg.pipeline_microbatches, block_fn=block_fn, side=side,
        seq_manual=seq_manual,
    )


class CausalLM:
    """Decoder-only LM over the generic backbone. The concrete model families
    (GPT-2, OPT, BLOOM, LLaMA-style) are TransformerConfig presets in
    ``models/registry.py`` — they differ only in config, not code."""

    def __init__(self, config: TransformerConfig):
        self.config = config

    def _gather_toplevel(self, params):
        """ZeRO-3 per_layer mode: constrain top-level params to their gathered
        (data-unsharded) layout before use — gather-weights-compute-release,
        mirroring the per-block constraint inside the layer scan."""
        cfg = self.config
        specs = getattr(cfg, "zero3_toplevel_gather_specs", None)
        if not (getattr(cfg, "zero3_per_layer_gather", False) and specs):
            return params
        from jax.sharding import NamedSharding

        out = dict(params)
        for k, sub in specs.items():
            if k in out:
                out[k] = jax.tree_util.tree_map(
                    lambda a, s: jax.lax.with_sharding_constraint(
                        a, NamedSharding(cfg.mesh, s)),
                    out[k], sub)
        return out

    # -- init ---------------------------------------------------------------------
    def init(self, rng):
        cfg = self.config
        if cfg.hybrid_layers:
            from .hybrid import init_params

            return init_params(cfg, rng)
        k_emb, k_pos, k_blocks, k_head = jax.random.split(rng, 4)
        params = {
            "wte": L.embedding_init(k_emb, cfg.vocab_size, cfg.d_model, cfg.initializer_range),
            "blocks": stack_init(k_blocks, cfg),
        }
        if cfg.first_k_dense:
            params["dense_blocks"] = dense_stack_init(k_blocks, cfg)
        if cfg.sink_window:
            from .window_moe import kv_by_kind_init

            params.update(kv_by_kind_init(k_blocks, cfg))
        if cfg.final_layernorm:
            params["ln_f"] = _norm_init(cfg)
        if cfg.position_embedding == "learned":
            params["wpe"] = {
                "weight": Param(
                    L.normal_init(k_pos, (cfg.max_seq_len, cfg.d_model), cfg.initializer_range),
                    ("seq_table", "embed"),
                )
            }
        if cfg.type_vocab_size:
            params["wtt"] = {
                "weight": Param(
                    L.normal_init(jax.random.fold_in(k_pos, 1),
                                  (cfg.type_vocab_size, cfg.d_model),
                                  cfg.initializer_range),
                    (None, "embed"),
                )
            }
        if cfg.embed_layernorm:
            params["ln_emb"] = _norm_init(cfg)
        if not cfg.tie_embeddings:
            params["lm_head"] = L.linear_init(
                k_head, cfg.d_model, cfg.vocab_size, ("embed", "vocab"),
                bias=cfg.head_bias, stddev=cfg.initializer_range,
            )
        return params

    # -- forward ------------------------------------------------------------------
    def backbone(self, params, input_ids, positions=None, attention_mask=None,
                 deterministic=True, dropout_rng=None, token_type_ids=None,
                 pld_theta=None):
        """Embedding + blocks + final norm -> ([batch, seq, d_model], aux)."""
        cfg = self.config
        if cfg.window_layers or cfg.hybrid_layers:
            if attention_mask is not None or token_type_ids is not None \
                    or pld_theta is not None or not deterministic:
                raise NotImplementedError(
                    "window and full attention layers (layer_types) and "
                    "hybrid stacks (hybrid_pattern) run the plain causal "
                    "forward: no padding mask, token types, dropout or "
                    "progressive layer drop")
            if cfg.hybrid_layers:
                from .hybrid import backbone
            else:
                from .window_moe import backbone

            return backbone(self, params, input_ids, positions), \
                jnp.zeros((), jnp.float32)
        params = self._gather_toplevel(params)
        b, s = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

        x = L.embedding_apply(params["wte"], input_ids, cfg.compute_dtype)
        if cfg.position_embedding == "learned":
            x = x + jnp.take(params["wpe"]["weight"].astype(cfg.compute_dtype), positions, axis=0)
        if cfg.type_vocab_size and token_type_ids is not None:
            x = x + jnp.take(params["wtt"]["weight"].astype(cfg.compute_dtype),
                             token_type_ids, axis=0)
        if cfg.embed_layernorm:
            x = _norm_apply(cfg, params["ln_emb"], x)

        # mask=None means "plain causal (or fully bidirectional for encoders)"
        # — lets the flash kernel run; an explicit padding mask forces the
        # dense path. Under sequence parallelism the padding mask stays in
        # [b, s] form and rides the ring with K/V.
        mask = None
        kv_mask = None
        if attention_mask is not None:
            if cfg.sequence_parallel:
                kv_mask = attention_mask.astype(bool)
            else:
                pad = attention_mask[:, None, None, :].astype(bool)
                mask = (L.causal_mask(s, s) & pad) if cfg.causal else \
                    jnp.broadcast_to(pad, (b, 1, s, s))

        rope = None
        if cfg.position_embedding == "rope":
            rope = L.rotary_embedding(
                positions, cfg.qk_rope_head_dim if cfg.latent_attention
                else cfg.rotary_dim or cfg.head_dim, cfg.rope_base)
        alibi = None
        if cfg.position_embedding == "alibi":
            alibi = L.alibi_bias(cfg.n_heads, s, s)

        x, aux = stack_apply(cfg, params["blocks"], x, mask=mask, rope=rope,
                             alibi=alibi, deterministic=deterministic,
                             dropout_rng=dropout_rng, kv_mask=kv_mask,
                             pld_theta=pld_theta,
                             dense_params=params.get("dense_blocks"))
        if cfg.final_layernorm:
            x = _norm_apply(cfg, params["ln_f"], x)
        return x, aux

    def head(self, params, x):
        """Hidden states -> logits [batch, seq, vocab] (compute dtype)."""
        params = self._gather_toplevel(params)
        if self.config.tie_embeddings:
            return L.embedding_attend(params["wte"], x)
        return L.linear_apply(params["lm_head"], x)

    def head_ce(self, params, x, labels):
        """Cross entropy from post-final-norm hidden states; picks the fused
        vocab-chunked path or the materialized-logits path per config. ``params``
        needs only the head leaves (wte / lm_head), so pipeline stages can pass
        a head-only subtree."""
        cfg = self.config
        params = self._gather_toplevel(params)
        if cfg.fused_ce:
            from ..ops.cross_entropy import fused_cross_entropy

            if cfg.tie_embeddings:
                emb, bias = params["wte"]["weight"], None
            else:
                emb = params["lm_head"]["kernel"].T
                bias = params["lm_head"].get("bias")  # GPT-J biased head
            return fused_cross_entropy(
                x.reshape(-1, cfg.d_model), emb, labels.reshape(-1), bias,
                n_chunks=cfg.fused_ce_chunks, impl=cfg.fused_ce_impl,
                interpret=cfg.attention_interpret, mesh=cfg.mesh)
        return cross_entropy_loss(self.head(params, x), labels)

    def apply(self, params, input_ids, positions=None, attention_mask=None,
              deterministic=True, dropout_rng=None, return_aux=False):
        """input_ids: [batch, seq] int32 -> logits [batch, seq, vocab] (compute
        dtype); with ``return_aux`` also the MoE auxiliary loss."""
        x, aux = self.backbone(params, input_ids, positions=positions,
                               attention_mask=attention_mask,
                               deterministic=deterministic, dropout_rng=dropout_rng)
        logits = self.head(params, x)
        return (logits, aux) if return_aux else logits

    # -- loss ---------------------------------------------------------------------
    def loss(self, params, batch, deterministic=True, dropout_rng=None,
             pld_theta=None):
        """Next-token cross entropy. batch: {input_ids, labels?, attention_mask?};
        labels default to input_ids shifted; label -100 = ignored (HF convention).
        ``pld_theta``: traced progressive-layer-drop keep parameter (engine)."""
        cfg = self.config
        input_ids = batch["input_ids"]
        labels = batch.get("labels")
        if labels is None:
            labels = jnp.concatenate(
                [input_ids[:, 1:], jnp.full_like(input_ids[:, :1], -100)], axis=1
            )
        x, aux = self.backbone(
            params, input_ids, attention_mask=batch.get("attention_mask"),
            positions=batch.get("position_ids"), deterministic=deterministic,
            dropout_rng=dropout_rng, pld_theta=pld_theta,
        )
        return self.head_ce(params, x, labels) + aux


class MaskedLM(CausalLM):
    """Encoder (BERT-family) over the same backbone: bidirectional attention
    (``causal=False``), post-norm blocks, token-type embeddings, and the BERT
    MLM prediction head (dense + gelu + LN + tied decoder with its own bias —
    the reference's kernel-accelerated BERT training target,
    ``docs/_tutorials/bert-pretraining.md`` / ``tests/unit/modeling.py``).

    batch: {input_ids, labels, attention_mask?, token_type_ids?}; labels use
    the HF convention (-100 everywhere except the masked positions).
    """

    def init(self, rng):
        cfg = self.config
        if cfg.causal:
            raise ValueError("MaskedLM requires causal=False (a bert_config "
                             "preset from models/registry.py)")
        params = super().init(rng)
        k1, k2 = jax.random.split(jax.random.fold_in(rng, 17))
        params["mlm_transform"] = L.linear_init(
            k1, cfg.d_model, cfg.d_model, ("embed", None),
            stddev=cfg.initializer_range)
        params["mlm_ln"] = L.layernorm_init(cfg.d_model)
        # decoder reuses wte (tied) but keeps a separate output bias
        params["mlm_bias"] = {
            "bias": Param(jnp.zeros((cfg.vocab_size,)), ("vocab",))}
        return params

    def _mlm_transform(self, params, x):
        cfg = self.config
        h = L.linear_apply(params["mlm_transform"], x)
        h = L.ACTIVATIONS[cfg.activation](h)  # BERT: exact-erf gelu
        return L.layernorm_apply(params["mlm_ln"], h, eps=cfg.layernorm_eps)

    def head(self, params, x):
        params = self._gather_toplevel(params)
        h = self._mlm_transform(params, x)
        logits = L.embedding_attend(params["wte"], h)
        return logits + params["mlm_bias"]["bias"].astype(logits.dtype)

    def head_ce(self, params, x, labels):
        cfg = self.config
        params = self._gather_toplevel(params)
        h = self._mlm_transform(params, x)
        if cfg.fused_ce:
            from ..ops.cross_entropy import fused_cross_entropy

            return fused_cross_entropy(
                h.reshape(-1, cfg.d_model), params["wte"]["weight"],
                labels.reshape(-1), params["mlm_bias"]["bias"],
                n_chunks=cfg.fused_ce_chunks, impl=cfg.fused_ce_impl,
                interpret=cfg.attention_interpret, mesh=cfg.mesh)
        logits = L.embedding_attend(params["wte"], h) \
            + params["mlm_bias"]["bias"].astype(cfg.compute_dtype)
        return cross_entropy_loss(logits, labels)

    def apply(self, params, input_ids, positions=None, attention_mask=None,
              deterministic=True, dropout_rng=None, return_aux=False,
              token_type_ids=None):
        cfg = self.config
        if token_type_ids is None and cfg.type_vocab_size:
            token_type_ids = jnp.zeros_like(input_ids)  # HF default segment 0
        x, aux = self.backbone(params, input_ids, positions=positions,
                               attention_mask=attention_mask,
                               token_type_ids=token_type_ids,
                               deterministic=deterministic,
                               dropout_rng=dropout_rng)
        logits = self.head(params, x)
        return (logits, aux) if return_aux else logits

    def loss(self, params, batch, deterministic=True, dropout_rng=None,
             pld_theta=None):
        """Masked-token cross entropy; no label shifting (denoising, not AR)."""
        if "labels" not in batch:
            raise ValueError("MaskedLM.loss needs explicit 'labels' "
                             "(-100 outside masked positions)")
        token_type_ids = batch.get("token_type_ids")
        if token_type_ids is None and self.config.type_vocab_size:
            token_type_ids = jnp.zeros_like(batch["input_ids"])
        x, aux = self.backbone(
            params, batch["input_ids"],
            attention_mask=batch.get("attention_mask"),
            positions=batch.get("position_ids"),
            token_type_ids=token_type_ids,
            deterministic=deterministic, dropout_rng=dropout_rng,
            pld_theta=pld_theta,
        )
        return self.head_ce(params, x, batch["labels"]) + aux


class TextEncoder(CausalLM):
    """Headless conditioning encoder (CLIP text model shape): causal prenorm
    transformer whose OUTPUT is the final hidden states, consumed by a
    diffusion UNet's cross-attention (reference container:
    ``module_inject/containers/clip.py`` for the stable-diffusion text
    encoder). No LM head; ``tie_embeddings`` keeps init head-free."""

    def apply(self, params, input_ids, positions=None, attention_mask=None,
              deterministic=True, dropout_rng=None, return_aux=False):
        x, aux = self.backbone(params, input_ids, positions=positions,
                               attention_mask=attention_mask,
                               deterministic=deterministic,
                               dropout_rng=dropout_rng)
        return (x, aux) if return_aux else x  # hidden states, not logits

    def loss(self, params, batch, deterministic=True, dropout_rng=None):
        raise NotImplementedError(
            "TextEncoder is a conditioning encoder (no LM objective); train "
            "the underlying backbone as a CausalLM if you need an LM loss")


def cross_entropy_loss(logits, labels, ignore_index=-100):
    """Token-mean cross entropy in fp32; -100 labels masked out."""
    logits = logits.astype(jnp.float32)
    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    token_ll = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)[..., 0]
    nll = (logz - token_ll) * valid
    return jnp.sum(nll) / jnp.maximum(jnp.sum(valid), 1)
