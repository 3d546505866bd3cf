"""Operations and bytes of the Nemotron-H decoder (Mamba-2 mixers, LatentMoE
expert layers of which a chip holds a share, attention layers without
positions), computed from its shapes (the yardstick's own arithmetic, as
``shapes_sink_moe.py`` is for MiMo-V2; nothing is read from the program).

``arch`` is the ``arch`` group of the configuration file: ``d_model``,
``n_heads``, ``n_kv_heads``, ``head_dim``, ``ssm_heads``, ``ssm_head_dim``,
``ssm_state``, ``ssm_groups``, ``ssm_conv``, ``ssm_chunk``, ``n_experts``
(the router's outputs), ``moe_local_experts`` (the experts HELD here),
``moe_top_k``, ``moe_d_ff`` (one routed expert's width), ``moe_latent_size``,
``moe_shared_d_ff``, ``vocab_size`` and ``layer_kinds``, one character a
layer (M, E, *).
"""

MAMBA, EXPERT, ATTENTION = "M", "E", "*"


def count(arch, kind):
    return arch["layer_kinds"].count(kind)


def held_experts(arch):
    return arch.get("moe_local_experts") or arch["n_experts"]


def ssm_widths(arch):
    """``(d_inner, conv channels, input projection outputs)``."""
    d_in = arch["ssm_heads"] * arch["ssm_head_dim"]
    conv = d_in + 2 * arch["ssm_groups"] * arch["ssm_state"]
    return d_in, conv, d_in + conv + arch["ssm_heads"]


def mamba_params(arch):
    """W_in, the conv and its bias, dt_bias, A_log, D, the gated norm's
    weight, W_out."""
    d = arch["d_model"]
    d_in, conv, proj = ssm_widths(arch)
    return d * proj + arch["ssm_conv"] * conv + conv \
        + 3 * arch["ssm_heads"] + d_in + d_in * d


def attention_params(arch):
    d, H, G, dh = (arch["d_model"], arch["n_heads"], arch["n_kv_heads"],
                   arch["head_dim"])
    return 2 * d * H * dh + 2 * d * G * dh


def expert_params(arch):
    """One routed expert in the latent: up and down, not gated."""
    return 2 * arch["moe_latent_size"] * arch["moe_d_ff"]


def expert_layer_fixed_params(arch):
    """What an expert layer reads whatever is routed: the whole router with
    its bias, the two latent projections and the shared expert."""
    d, E = arch["d_model"], arch["n_experts"]
    return d * E + E + 2 * d * arch["moe_latent_size"] \
        + 2 * d * arch["moe_shared_d_ff"]


def fixed_params(arch):
    """What a decode step reads whatever is routed: every layer's norm and
    mixer but the routed experts, the final norm and the head. (The
    embedding's rows read are a few KB and left out.)"""
    d = arch["d_model"]
    per = {MAMBA: mamba_params(arch), ATTENTION: attention_params(arch),
           EXPERT: expert_layer_fixed_params(arch)}
    return sum(per[k] + d for k in arch["layer_kinds"]) + d \
        + d * arch["vocab_size"]


def param_count(arch):
    """Every parameter held: ``fixed_params``, the embedding, and the
    experts held in every expert layer."""
    return fixed_params(arch) + arch["d_model"] * arch["vocab_size"] \
        + count(arch, EXPERT) * held_experts(arch) * expert_params(arch)


def state_bytes(arch, slots, tail_itemsize=2):
    """One decode step's recurrent state for ``slots`` slots, read AND
    written, in every Mamba layer: S float32 ``[heads, head_dim, state]``
    and the conv tail ``[conv - 1, channels]`` in the compute dtype."""
    _, conv, _ = ssm_widths(arch)
    s = arch["ssm_heads"] * arch["ssm_head_dim"] * arch["ssm_state"] * 4
    tail = (arch["ssm_conv"] - 1) * conv * tail_itemsize
    return 2 * count(arch, MAMBA) * slots * (s + tail)


def kv_row_bytes(arch, itemsize=2):
    """One cached token in one attention layer: its K row and its V row."""
    return 2 * arch["n_kv_heads"] * arch["head_dim"] * itemsize


def decode_step_bytes(arch, full_rows, experts_hit, slots, itemsize=2):
    """Bytes one decode step must move: ``fixed_params`` once, the HELD
    routed experts that were hit (distinct held experts with work, summed
    over the expert layers: the program's counter), the recurrent state of
    the ``slots`` slots decoded, read and written, and the live K/V rows
    (``full_rows``) in every attention layer."""
    return (fixed_params(arch) + experts_hit * expert_params(arch)) \
        * itemsize + state_bytes(arch, slots, itemsize) \
        + count(arch, ATTENTION) * full_rows * kv_row_bytes(arch, itemsize)


def grouped_product_bytes(arch, experts_hit, itemsize=2):
    """The routed experts' weights a decode step's grouped products read."""
    return experts_hit * expert_params(arch) * itemsize


def grouped_product_flops(arch, pairs):
    """Operations of ``pairs`` token-expert pairs in the latent."""
    return 2 * pairs * expert_params(arch)


def ssd_flops(arch, tokens):
    """Useful operations of the chunked scan over ``tokens`` positions in
    every Mamba layer (blocks of ``ssm_chunk``; per block of L positions:
    C B^T over each group's state, its decay-weighted sum of dt x within the
    block, each block's own end state and what the state entering it adds
    to its outputs): ``2 T (L G N + L H P + 2 H P N)`` a layer."""
    L, G, N = arch["ssm_chunk"], arch["ssm_groups"], arch["ssm_state"]
    HP = arch["ssm_heads"] * arch["ssm_head_dim"]
    return 2 * count(arch, MAMBA) * tokens * (L * G * N + L * HP + 2 * HP * N)


def ssd_bytes(arch, tokens):
    """What the chunked scan must move at least in every Mamba layer: x,
    B, C and dt in and y out, float32, and the state in and out."""
    HP = arch["ssm_heads"] * arch["ssm_head_dim"]
    GN = arch["ssm_groups"] * arch["ssm_state"]
    per_token = (2 * HP + 2 * GN + arch["ssm_heads"]) * 4
    state = 2 * HP * arch["ssm_state"] * 4
    return count(arch, MAMBA) * (tokens * per_token + state)
