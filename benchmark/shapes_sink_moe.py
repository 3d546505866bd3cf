"""Operations and bytes of the sink-window / full attention expert decoder
of which a chip holds a share of the experts (MiMo-V2), computed from its
shapes (the yardstick's own arithmetic, as ``shapes_window_moe.py`` is for
the AFMoE decoder; nothing is read from the program).

``arch`` is the ``arch`` group of the configuration file: ``n_layers``,
``first_k_dense``, ``d_model``, ``n_heads``, ``n_kv_heads`` (a full layer's
K/V heads), ``n_kv_heads_window`` (a window layer's), ``head_dim`` (q and k),
``v_head_dim``, ``d_ff`` (the dense layer's width), ``moe_d_ff`` (one routed
expert's), ``n_experts`` (the router's outputs), ``moe_local_experts`` (the
experts HELD here), ``moe_top_k``, ``sliding_window``, ``vocab_size``, and
``layer_kinds``, each layer's kind.
"""

WINDOW = "sliding_attention"


def window_layers(arch):
    return sum(k == WINDOW for k in arch["layer_kinds"])


def full_layers(arch):
    return len(arch["layer_kinds"]) - window_layers(arch)


def kv_heads(arch, window):
    return arch["n_kv_heads_window"] if window else arch["n_kv_heads"]


def held_experts(arch):
    return arch.get("moe_local_experts") or arch["n_experts"]


def attention_params(arch, window):
    """q, the kind's k and v, o, and a window layer's sinks."""
    d, H = arch["d_model"], arch["n_heads"]
    dk, dv = arch["head_dim"], arch["v_head_dim"]
    return d * H * dk + d * kv_heads(arch, window) * (dk + dv) \
        + H * dv * d + (H if window else 0)


def expert_params(arch):
    """One routed expert: gate, up, down."""
    return 3 * arch["d_model"] * arch["moe_d_ff"]


def fixed_params(arch):
    """What a step reads whatever is routed: every layer's attention and
    two norms, the dense layers' feed-forward, the expert layers' whole
    router with its bias; the final norm and the head. (The embedding's rows
    read are a few KB and left out.)"""
    d, kd = arch["d_model"], arch["first_k_dense"]
    total = d * arch["vocab_size"] + d
    for i, kind in enumerate(arch["layer_kinds"]):
        total += attention_params(arch, kind == WINDOW) + 2 * d
        total += 3 * d * arch["d_ff"] if i < kd \
            else d * arch["n_experts"] + arch["n_experts"]
    return total


def param_count(arch):
    """Every parameter held: ``fixed_params``, the embedding, and the
    experts held in every expert layer."""
    return (fixed_params(arch) + arch["d_model"] * arch["vocab_size"]
            + (arch["n_layers"] - arch["first_k_dense"])
            * held_experts(arch) * expert_params(arch))


def kv_row_bytes(arch, window, itemsize=2):
    """One cached token in one layer of a kind: its K row and its V row, at
    their own widths."""
    return kv_heads(arch, window) * (arch["head_dim"] + arch["v_head_dim"]) \
        * itemsize


def full_attention_bytes(arch, full_rows, itemsize=2):
    """K/V the full layers' decode attention must read in a step:
    ``full_rows`` (the live rows of the active slots) in each."""
    return full_layers(arch) * full_rows * kv_row_bytes(arch, False, itemsize)


def window_attention_bytes(arch, window_rows, itemsize=2):
    """K/V the window layers' decode attention must read in a step:
    ``window_rows`` (of each slot the rows in its band: min(live, window))
    in each."""
    return window_layers(arch) * window_rows \
        * kv_row_bytes(arch, True, itemsize)


def decode_step_bytes(arch, full_rows, window_rows, experts_hit, itemsize=2):
    """Bytes one decode step must read: ``fixed_params`` once, the HELD
    routed experts that were hit (distinct held experts with work, summed
    over the expert layers, from the program's counter), and the K/V rows of
    both kinds of layer at their own widths."""
    return ((fixed_params(arch) + experts_hit * expert_params(arch))
            * itemsize
            + full_attention_bytes(arch, full_rows, itemsize)
            + window_attention_bytes(arch, window_rows, itemsize))


def chunk_flops(arch, tokens, start):
    """Useful operations of one prefill chunk of ``tokens`` positions
    written at ``start``: 2 per parameter a token passes through (the
    attention projections of each layer's kind, the dense feed-forward, the
    whole router, and of the ``moe_top_k`` experts a token chooses the share
    that is held here: ``moe_top_k * held / n_experts`` pairs a token and
    layer, the routing's mean, since no counter splits a chunk's pairs from
    the decode step's), scores over K heads of ``head_dim`` and weighted
    sums over V heads of ``v_head_dim`` over what each query SEES (all
    ``start + t + 1`` rows in a full layer, at most ``sliding_window`` in a
    window layer: the band counted, not the blocks visited), and the head
    for the one row that is sampled."""
    d, H = arch["d_model"], arch["n_heads"]
    dk, dv, W = arch["head_dim"], arch["v_head_dim"], arch["sliding_window"]
    kd = arch["first_k_dense"]
    pairs = arch["moe_top_k"] * held_experts(arch) / arch["n_experts"]
    per_token = 0
    for i, kind in enumerate(arch["layer_kinds"]):
        window = kind == WINDOW
        per_token += attention_params(arch, window) - (H if window else 0)
        per_token += 3 * d * arch["d_ff"] if i < kd \
            else pairs * expert_params(arch) + d * arch["n_experts"]
    # query t (0-based in the chunk) sees start + t + 1 rows, a window
    # layer's query min(that, W)
    seen_full = tokens * start + tokens * (tokens + 1) // 2
    seen_window = sum(min(start + t + 1, W) for t in range(tokens))
    attend = (full_layers(arch) * seen_full
              + window_layers(arch) * seen_window) * H * (dk + dv)
    return 2 * (tokens * per_token + attend + d * arch["vocab_size"])
