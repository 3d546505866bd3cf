"""Operations and bytes of the latent-attention expert decoder, computed
from its shapes (the yardstick's own arithmetic, as ``shapes.py`` is for the
dense decoder; nothing is read from the program).

``arch`` is the ``arch`` group of the configuration file: ``n_layers``,
``first_k_dense``, ``d_model``, ``n_heads``, ``d_ff`` (the dense layers'
width), ``moe_d_ff`` (one routed expert's), ``n_experts``, ``moe_top_k``,
``n_shared_experts``, ``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``, ``vocab_size``.
"""


def attention_params(arch):
    """q, kv_a (latent + shared rope key), the latent's norm, kv_b, o."""
    d, H, r = arch["d_model"], arch["n_heads"], arch["kv_lora_rank"]
    dn, dr, dv = (arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
                  arch["v_head_dim"])
    return (d * H * (dn + dr) + d * (r + dr) + r + r * H * (dn + dv)
            + H * dv * d)


def expert_params(arch):
    """One routed expert: gate, up, down."""
    return 3 * arch["d_model"] * arch["moe_d_ff"]


def expert_layer_fixed_params(arch):
    """What an expert layer reads whatever is routed: attention, shared
    experts, router with its bias, two norms."""
    d = arch["d_model"]
    return (attention_params(arch)
            + arch["n_shared_experts"] * expert_params(arch)
            + d * arch["n_experts"] + arch["n_experts"] + 2 * d)


def dense_layer_params(arch):
    d = arch["d_model"]
    return attention_params(arch) + 3 * d * arch["d_ff"] + 2 * d


def param_count(arch):
    """Every parameter held: dense layers, expert layers with all their
    experts, embedding, untied head, final norm."""
    kd = arch["first_k_dense"]
    d, v = arch["d_model"], arch["vocab_size"]
    return (kd * dense_layer_params(arch)
            + (arch["n_layers"] - kd) * (
                expert_layer_fixed_params(arch)
                + arch["n_experts"] * expert_params(arch))
            + 2 * v * d + d)


def latent_bytes_per_token(arch, itemsize=2):
    """One cached token over all layers: a latent row and a rope key."""
    return arch["n_layers"] * (arch["kv_lora_rank"]
                               + arch["qk_rope_head_dim"]) * itemsize


def decode_step_bytes(arch, live_tokens, experts_hit, itemsize=2):
    """Bytes one decode step must read: every weight outside the routed
    experts once (the embedding's rows are a few KB and left out; the head
    is read whole), the routed experts that were HIT (``experts_hit``:
    distinct experts with work, summed over the expert layers, from the
    program's counter: never all of them unless all were hit), and the
    latent rows of the tokens its active slots hold."""
    kd = arch["first_k_dense"]
    d, v = arch["d_model"], arch["vocab_size"]
    fixed = (kd * dense_layer_params(arch)
             + (arch["n_layers"] - kd) * expert_layer_fixed_params(arch)
             + v * d + d)
    return ((fixed + experts_hit * expert_params(arch)) * itemsize
            + live_tokens * latent_bytes_per_token(arch, itemsize))


def grouped_product_bytes(arch, experts_hit, itemsize=2):
    """Weights the grouped expert products (gate/up, then down) must read
    for ``experts_hit`` experts with work (summed over layers)."""
    return experts_hit * expert_params(arch) * itemsize


def grouped_product_flops(arch, pairs):
    """Operations of the two grouped products for ``pairs`` token-expert
    pairs: 2 per multiply-add over gate, up and down."""
    return 2 * pairs * expert_params(arch)


def absorbed_attention_bytes(arch, live_tokens, itemsize=2):
    """The absorbed decode attention reads each live latent row once for
    all heads, in every layer."""
    return live_tokens * latent_bytes_per_token(arch, itemsize)


def chunk_flops(arch, tokens, start):
    """Operations of one prefill chunk of ``tokens`` positions written at
    ``start`` (so it attends to ``start + tokens`` rows, causally): 2 per
    parameter a token passes through (attention projections, dense FFN or
    top_k routed experts beside the shared ones, router), the expansion of
    the attended latents into K and V, scores and weighted sums over the
    causal part, and the head for the one row that is sampled."""
    d, H, r = arch["d_model"], arch["n_heads"], arch["kv_lora_rank"]
    dn, dr, dv = (arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
                  arch["v_head_dim"])
    kd, L = arch["first_k_dense"], arch["n_layers"]
    # kv_b is applied to the attended rows, not to the chunk's tokens
    proj = attention_params(arch) - r * H * (dn + dv) - r
    per_token = (L * proj + kd * 3 * d * arch["d_ff"]
                 + (L - kd) * ((arch["moe_top_k"] + arch["n_shared_experts"])
                               * expert_params(arch) + d * arch["n_experts"]))
    context = start + tokens
    expand = L * context * r * H * (dn + dv)
    # query t (0-based in the chunk) sees start + t + 1 rows
    seen = tokens * start + tokens * (tokens + 1) // 2
    attend = L * seen * H * (dn + dr + dv)
    return 2 * (tokens * per_token + expand + attend
                + d * arch["vocab_size"])
