"""Model zoo registry: the reference's per-architecture injection policies
(``module_inject/containers/{gpt2,opt,bloom,gptj,gptneox,...}.py``) become
TransformerConfig presets — the families differ in config, not code.

Size presets follow the published architectures (GPT-2 paper table 2; OPT paper
table 1; BLOOM config; LLaMA paper table 2).
"""

import jax.numpy as jnp

from .transformer import CausalLM, TransformerConfig


def gpt2_config(size="small", **overrides):
    presets = {
        "tiny": dict(n_layers=2, d_model=128, n_heads=2, d_ff=512, max_seq_len=256),
        "small": dict(n_layers=12, d_model=768, n_heads=12, d_ff=3072),
        "medium": dict(n_layers=24, d_model=1024, n_heads=16, d_ff=4096),
        "large": dict(n_layers=36, d_model=1280, n_heads=20, d_ff=5120),
        "xl": dict(n_layers=48, d_model=1600, n_heads=25, d_ff=6400),
    }
    base = dict(
        vocab_size=50257, max_seq_len=1024, activation="gelu_new", norm="layernorm",
        position_embedding="learned", tie_embeddings=True, use_bias=True, prenorm=True,
    )
    base.update(presets[size])
    base.update(overrides)
    return TransformerConfig(**base)


def opt_config(size="125m", **overrides):
    presets = {
        "125m": dict(n_layers=12, d_model=768, n_heads=12, d_ff=3072),
        "350m": dict(n_layers=24, d_model=1024, n_heads=16, d_ff=4096),
        "1.3b": dict(n_layers=24, d_model=2048, n_heads=32, d_ff=8192),
        "2.7b": dict(n_layers=32, d_model=2560, n_heads=32, d_ff=10240),
        "6.7b": dict(n_layers=32, d_model=4096, n_heads=32, d_ff=16384),
        "13b": dict(n_layers=40, d_model=5120, n_heads=40, d_ff=20480),
        "30b": dict(n_layers=48, d_model=7168, n_heads=56, d_ff=28672),
    }
    base = dict(
        vocab_size=50272, max_seq_len=2048, activation="relu", norm="layernorm",
        position_embedding="learned", tie_embeddings=True, use_bias=True, prenorm=True,
    )
    base.update(presets[size])
    base.update(overrides)
    return TransformerConfig(**base)


def bloom_config(size="560m", **overrides):
    presets = {
        "560m": dict(n_layers=24, d_model=1024, n_heads=16, d_ff=4096),
        "1.7b": dict(n_layers=24, d_model=2048, n_heads=16, d_ff=8192),
        "3b": dict(n_layers=30, d_model=2560, n_heads=32, d_ff=10240),
        "7b": dict(n_layers=30, d_model=4096, n_heads=32, d_ff=16384),
    }
    base = dict(
        vocab_size=250880, max_seq_len=2048, activation="gelu", norm="layernorm",
        position_embedding="alibi", tie_embeddings=True, use_bias=True, prenorm=True,
        embed_layernorm=True,
    )
    base.update(presets[size])
    base.update(overrides)
    return TransformerConfig(**base)


def llama_config(size="7b", **overrides):
    presets = {
        "tiny": dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, d_ff=352,
                     max_seq_len=256, vocab_size=1024),
        "7b": dict(n_layers=32, d_model=4096, n_heads=32, d_ff=11008),
        "13b": dict(n_layers=40, d_model=5120, n_heads=40, d_ff=13824),
    }
    base = dict(
        vocab_size=32000, max_seq_len=2048, activation="swiglu", norm="rmsnorm",
        position_embedding="rope", tie_embeddings=False, use_bias=False, prenorm=True,
        layernorm_eps=1e-6,
    )
    base.update(presets[size])
    base.update(overrides)
    return TransformerConfig(**base)


def mistral_config(size="7b", **overrides):
    """LLaMA-shaped with GQA + 32k rope base (Mistral paper)."""
    presets = {
        "tiny": dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                     d_ff=352, max_seq_len=256, vocab_size=1024),
        "7b": dict(n_layers=32, d_model=4096, n_heads=32, n_kv_heads=8,
                   d_ff=14336, max_seq_len=32768),
    }
    base = dict(
        vocab_size=32000, activation="swiglu", norm="rmsnorm",
        position_embedding="rope", rope_base=10000.0, tie_embeddings=False,
        use_bias=False, prenorm=True, layernorm_eps=1e-5,
    )
    base.update(presets[size])
    base.update(overrides)
    return TransformerConfig(**base)


def qwen2_config(size="7b", **overrides):
    """LLaMA-shaped with GQA and attention bias on q/k/v only (o and the MLP
    stay unbiased) — mirrors module_inject/hf.py's qwen2 mapping so a
    from-scratch model and an imported checkpoint share one architecture."""
    presets = {
        "tiny": dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                     d_ff=352, max_seq_len=256, vocab_size=1024),
        "7b": dict(n_layers=28, d_model=3584, n_heads=28, n_kv_heads=4,
                   d_ff=18944, max_seq_len=32768, vocab_size=152064),
    }
    base = dict(
        vocab_size=151936, activation="swiglu", norm="rmsnorm",
        position_embedding="rope", rope_base=1000000.0, tie_embeddings=False,
        use_bias=True, mlp_bias=False, prenorm=True, layernorm_eps=1e-6,
    )
    base.update(presets[size])
    base.update(overrides)
    return TransformerConfig(**base)


def gptj_config(size="6b", **overrides):
    """Parallel attn+mlp, shared LN, partial rotary, biased untied head."""
    presets = {
        "tiny": dict(n_layers=2, d_model=128, n_heads=4, d_ff=512,
                     max_seq_len=256, vocab_size=1024, rotary_dim=16),
        "6b": dict(n_layers=28, d_model=4096, n_heads=16, d_ff=16384,
                   rotary_dim=64),
    }
    base = dict(
        vocab_size=50400, max_seq_len=2048, activation="gelu_new",
        norm="layernorm", position_embedding="rope", rotary_interleaved=True,
        tie_embeddings=False, head_bias=True, use_bias=False, mlp_bias=True,
        prenorm=True, parallel_attn_mlp=True,
    )
    base.update(presets[size])
    base.update(overrides)
    return TransformerConfig(**base)


def neox_config(size="20b", **overrides):
    """GPT-NeoX: parallel residual with separate norms, partial rotary."""
    presets = {
        "tiny": dict(n_layers=2, d_model=128, n_heads=4, d_ff=512,
                     max_seq_len=256, vocab_size=1024, rotary_dim=8),
        "20b": dict(n_layers=44, d_model=6144, n_heads=64, d_ff=24576,
                    rotary_dim=24),
    }
    base = dict(
        vocab_size=50432, max_seq_len=2048, activation="gelu_exact",
        norm="layernorm", position_embedding="rope", tie_embeddings=False,
        use_bias=True, prenorm=True, parallel_attn_mlp=True,
        parallel_norm_split=True,
    )
    base.update(presets[size])
    base.update(overrides)
    return TransformerConfig(**base)


def falcon_config(size="7b", **overrides):
    """Falcon-7b geometry: parallel attn, one shared LN, multi-query, rope."""
    presets = {
        "tiny": dict(n_layers=2, d_model=128, n_heads=4, d_ff=512,
                     max_seq_len=256, vocab_size=1024),
        "7b": dict(n_layers=32, d_model=4544, n_heads=71, d_ff=18176),
    }
    base = dict(
        vocab_size=65024, max_seq_len=2048, activation="gelu_exact",
        norm="layernorm", position_embedding="rope", n_kv_heads=1,
        tie_embeddings=True, use_bias=False, prenorm=True,
        parallel_attn_mlp=True,
    )
    base.update(presets[size])
    base.update(overrides)
    return TransformerConfig(**base)


def gpt_neo_config(size="1.3b", **overrides):
    """GPT-Neo: GPT-2-shaped with alternating banded local attention and
    UNSCALED attention logits."""
    presets = {
        "tiny": dict(n_layers=2, d_model=128, n_heads=4, d_ff=512,
                     max_seq_len=256, vocab_size=1024,
                     local_attention_window=64),
        "1.3b": dict(n_layers=24, d_model=2048, n_heads=16, d_ff=8192),
        "2.7b": dict(n_layers=32, d_model=2560, n_heads=20, d_ff=10240),
    }
    base = dict(
        vocab_size=50257, max_seq_len=2048, activation="gelu_new",
        norm="layernorm", position_embedding="learned", tie_embeddings=True,
        use_bias=True, mlp_bias=True, prenorm=True,
        local_attention_window=256, attention_layers=("global", "local"),
        attn_scale=1.0,
    )
    base.update(presets[size])
    base.update(overrides)
    return TransformerConfig(**base)


def gpt2_moe_config(size="tiny", **overrides):
    """PR-MoE presets over the GPT-2 backbone (reference MoE tutorial
    configuration: GPT-style dense backbone + MoE FFNs with residual experts,
    ``moe/layer.py:16`` use_residual + noisy top-1 gating)."""
    presets = {
        "tiny": dict(n_layers=2, d_model=128, n_heads=2, d_ff=512,
                     max_seq_len=256, n_experts=4),
        "small": dict(n_layers=12, d_model=768, n_heads=12, d_ff=3072,
                      n_experts=8),
        "medium": dict(n_layers=24, d_model=1024, n_heads=16, d_ff=4096,
                       n_experts=16),
    }
    base = dict(
        vocab_size=50257, max_seq_len=1024, activation="gelu_new",
        norm="layernorm", position_embedding="learned", tie_embeddings=True,
        use_bias=True, prenorm=True,
        moe_top_k=1, moe_use_residual=True, moe_use_rts=True,
        moe_noisy_gate_policy="rsample",
    )
    base.update(presets[size])
    base.update(overrides)
    return TransformerConfig(**base)


def kanana2_config(size="30b-a3b", **overrides):
    """kakaocorp/kanana-2-30b-a3b-instruct-2601 (``model_type`` deepseek_v3;
    huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601 config.json):
    latent attention (MLA) without a q LoRA, decoupled interleaved RoPE on 64
    dims, one leading dense SwiGLU layer, then layers of 128 sigmoid-routed
    experts (top-6 of ``s + b``, weights from ``s``, normalised, x 2.448)
    beside 2 shared experts; untied head; RMSNorm eps 1e-6; no biases."""
    presets = {
        "tiny": dict(n_layers=3, d_model=64, n_heads=4, d_ff=128,
                     moe_d_ff=32, n_experts=8, moe_top_k=2,
                     n_shared_experts=2, kv_lora_rank=32,
                     qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                     max_seq_len=256, vocab_size=512),
        "30b-a3b": dict(n_layers=48, d_model=2048, n_heads=32, d_ff=6144,
                        moe_d_ff=768, n_experts=128, moe_top_k=6,
                        n_shared_experts=2, kv_lora_rank=512,
                        qk_nope_head_dim=128, qk_rope_head_dim=64,
                        v_head_dim=128),
    }
    base = dict(
        vocab_size=128256, max_seq_len=32768, activation="swiglu",
        norm="rmsnorm", position_embedding="rope", rope_base=1000000.0,
        rotary_interleaved=True, tie_embeddings=False, use_bias=False,
        prenorm=True, layernorm_eps=1e-6, first_k_dense=1,
        moe_routing="dropfree", moe_routed_scale=2.448,
    )
    base.update(presets[size])
    base.update(overrides)
    return TransformerConfig(**base)


def trinity_config(size="mini", **overrides):
    """arcee-ai/Trinity-Mini (``model_type`` afmoe; huggingface.co/arcee-ai/
    Trinity-Mini config.json): grouped-query attention (32 heads over 4 K/V
    heads of 128) in two kinds of layer, three that see the last 2048
    positions and rotate q and k to one that sees all and rotates nothing;
    RMS norms on q and k per head, a sigmoid output gate, four norms a
    layer; two leading dense SwiGLU layers, then layers of 128
    sigmoid-routed experts (top-8 of ``s + b``, weights from ``s``,
    normalised, x 2.826) beside one shared expert; the embedding scaled by
    sqrt(hidden) (``mup_enabled``); untied head; RMSNorm eps 1e-5; no
    biases. ``layer_types`` follows ``n_layers`` in the published period
    (sliding, sliding, sliding, full) unless given."""
    presets = {
        "tiny": dict(n_layers=6, d_model=64, n_heads=4, n_kv_heads=2,
                     head_dim_override=16, d_ff=128, moe_d_ff=32,
                     n_experts=8, moe_top_k=2, sliding_window=24,
                     max_seq_len=256, vocab_size=512),
        "mini": dict(n_layers=32, d_model=2048, n_heads=32, n_kv_heads=4,
                     head_dim_override=128, d_ff=6144, moe_d_ff=1024,
                     n_experts=128, moe_top_k=8, sliding_window=2048),
    }
    base = dict(
        vocab_size=200192, max_seq_len=131072, activation="swiglu",
        norm="rmsnorm", position_embedding="rope", rope_base=10000.0,
        tie_embeddings=False, use_bias=False, prenorm=True,
        layernorm_eps=1e-5, first_k_dense=2, n_shared_experts=1,
        moe_routing="dropfree", moe_routed_scale=2.826,
    )
    base.update(presets[size])
    base.update(overrides)
    period = ("sliding_attention",) * 3 + ("full_attention",)
    base.setdefault("layer_types", tuple(
        period[i % 4] for i in range(base["n_layers"])))
    base.setdefault("embed_scale", float(base["d_model"]) ** 0.5)
    return TransformerConfig(**base)


def bert_config(size="base", **overrides):
    """Encoder presets (BERT paper table 1 geometry): post-norm, bidirectional,
    learned positions + segment embeddings, gelu, embed LN."""
    presets = {
        "tiny": dict(n_layers=2, d_model=128, n_heads=2, d_ff=512, max_seq_len=256),
        "base": dict(n_layers=12, d_model=768, n_heads=12, d_ff=3072),
        "large": dict(n_layers=24, d_model=1024, n_heads=16, d_ff=4096),
    }
    base = dict(
        vocab_size=30528,  # wordpiece 30522 padded to a multiple of 64
        max_seq_len=512, activation="gelu_exact", norm="layernorm",
        position_embedding="learned", tie_embeddings=True, use_bias=True,
        prenorm=False, causal=False, embed_layernorm=True, type_vocab_size=2,
        final_layernorm=False,  # post-norm blocks end with LN; BERT has no ln_f
    )
    base.update(presets[size])
    base.update(overrides)
    return TransformerConfig(**base)


def mimo_v2_config(size="flash", **overrides):
    """XiaomiMiMo/MiMo-V2-Flash (``model_type`` mimo_v2_flash; huggingface.co/
    XiaomiMiMo/MiMo-V2-Flash config.json): 64 query heads of 192 over K heads
    of 192 and V heads of 128, in two kinds of layer, five that see the last
    128 positions (8 K/V heads, rotary base 10,000, a learned sink a head in
    the softmax) to one that sees all (4 K/V heads, base 5,000,000); q and k
    rotated over the first 64 dims (``partial_rotary_factor`` 0.334), V
    scaled by 0.707; two RMS norms a layer, eps 1e-5, no biases, no q/k
    norms; layer 0 a dense SwiGLU of 16,384, then layers of 256
    sigmoid-routed experts of 2,048 (top-8 of ``s + b``, weights from ``s``,
    normalised, no factor, no shared expert); untied head. The three MTP
    layers of the release are not built. ``layer_types`` follows ``n_layers``
    in the published ``hybrid_layer_pattern`` (layer 0 and every sixth layer
    from layer 5 full, the others window) unless given. ``moe_local_experts`` / ``moe_expert_offset`` give a
    program one chip's share of the experts (``moe/dropfree.py``)."""
    presets = {
        "tiny": dict(n_layers=7, d_model=64, n_heads=8, n_kv_heads=2,
                     n_kv_heads_window=4, head_dim_override=24,
                     v_head_dim=16, rotary_dim=8, d_ff=128, moe_d_ff=32,
                     n_experts=16, moe_top_k=4, sliding_window=8,
                     max_seq_len=256, vocab_size=512,
                     # published layer 0 and layers 6-11: one whole period
                     layer_types=("full_attention",)
                     + ("sliding_attention",) * 5 + ("full_attention",)),
        "flash": dict(n_layers=48, d_model=4096, n_heads=64, n_kv_heads=4,
                      n_kv_heads_window=8, head_dim_override=192,
                      v_head_dim=128, rotary_dim=64, d_ff=16384,
                      moe_d_ff=2048, n_experts=256, moe_top_k=8,
                      sliding_window=128),
    }
    base = dict(
        vocab_size=152576, max_seq_len=262144, activation="swiglu",
        norm="rmsnorm", position_embedding="rope", rope_base=5000000.0,
        rope_base_window=10000.0, attn_value_scale=0.707,
        window_block="sink", tie_embeddings=False, use_bias=False,
        prenorm=True, layernorm_eps=1e-5, first_k_dense=1,
        moe_routing="dropfree", moe_routed_scale=1.0,
    )
    base.update(presets[size])
    base.update(overrides)
    base.setdefault("layer_types", tuple(
        "full_attention" if i == 0 or i % 6 == 5 else "sliding_attention"
        for i in range(base["n_layers"])))
    return TransformerConfig(**base)


NEMOTRON_3_SUPER_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEM*EMEMEMEME")


def nemotron_h_config(size="3-super", **overrides):
    """nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 (``model_type``
    nemotron_h; huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16
    config.json): 88 layers in ``hybrid_override_pattern``, 40 Mamba-2
    mixers (128 heads of 64, 8 groups of B and C of state 128, conv 4,
    chunked scan in blocks of 128), 40 LatentMoE layers (512 sigmoid-routed
    experts of 2688, top-22 of ``s + b``, weights from ``s``, normalised, x
    5, run in a 1024-wide latent; one shared expert of 5376 at full width;
    squared-ReLU experts, not gated) and 8 attention layers (32 heads over 2
    K/V heads of 128, no positions); each layer ``x += mixer(RMSNorm(x))``,
    eps 1e-5; untied head over 131,072 rows; no biases. The MTP layers of
    the release are not built. ``n_layers`` takes the first layers of the
    published pattern unless ``hybrid_pattern`` is given;
    ``moe_local_experts`` / ``moe_expert_offset`` give a program one chip's
    share of the experts (``moe/dropfree.py``)."""
    presets = {
        "tiny": dict(n_layers=5, d_model=64, n_heads=4, n_kv_heads=2,
                     head_dim_override=16, d_ff=32, moe_d_ff=32,
                     n_experts=16, moe_top_k=4, moe_latent_size=32,
                     moe_shared_d_ff=48, ssm_heads=4, ssm_head_dim=16,
                     ssm_state=16, ssm_groups=2, ssm_chunk=8,
                     max_seq_len=256, vocab_size=512, hybrid_pattern="MEM*E"),
        "3-super": dict(n_layers=88, d_model=4096, n_heads=32, n_kv_heads=2,
                        head_dim_override=128, d_ff=2688, moe_d_ff=2688,
                        n_experts=512, moe_top_k=22, moe_latent_size=1024,
                        moe_shared_d_ff=5376, ssm_heads=128, ssm_head_dim=64,
                        ssm_state=128, ssm_groups=8, ssm_chunk=128),
    }
    base = dict(
        vocab_size=131072, max_seq_len=262144, activation="relu2",
        norm="rmsnorm", position_embedding="none", tie_embeddings=False,
        use_bias=False, prenorm=True, layernorm_eps=1e-5, ssm_conv=4,
        n_shared_experts=1, moe_routing="dropfree", moe_routed_scale=5.0,
    )
    base.update(presets[size])
    base.update(overrides)
    base.setdefault("hybrid_pattern",
                    NEMOTRON_3_SUPER_PATTERN[:base["n_layers"]])
    return TransformerConfig(**base)


MODEL_CONFIGS = {
    "gpt2": gpt2_config,
    "opt": opt_config,
    "bloom": bloom_config,
    "llama": llama_config,
    "mistral": mistral_config,
    "qwen2": qwen2_config,
    "gptj": gptj_config,
    "gpt_neox": neox_config,
    "gpt_neo": gpt_neo_config,
    "falcon": falcon_config,
    "bert": bert_config,
    "gpt2_moe": gpt2_moe_config,
    "kanana2": kanana2_config,
    "trinity": trinity_config,
    "mimo_v2": mimo_v2_config,
    "nemotron_h": nemotron_h_config,
}


def get_model(family, size=None, **overrides):
    """Build a model by family name, e.g. get_model('gpt2', 'medium').
    Encoder families (bert) return a MaskedLM; the rest a CausalLM."""
    from .transformer import MaskedLM

    if family not in MODEL_CONFIGS:
        raise ValueError(f"Unknown model family '{family}'. Available: {sorted(MODEL_CONFIGS)}")
    kwargs = {} if size is None else {"size": size}
    cfg = MODEL_CONFIGS[family](**kwargs, **overrides)
    cls = MaskedLM if not cfg.causal else CausalLM
    return cls(cfg)
