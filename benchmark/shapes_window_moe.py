"""Operations and bytes of the window / full attention expert decoder,
computed from its shapes (the yardstick's own arithmetic, as ``shapes.py`` is
for the dense decoder and ``shapes_latent_moe.py`` for the latent one; nothing
is read from the program).

``arch`` is the ``arch`` group of the configuration file: ``n_layers``,
``first_k_dense``, ``d_model``, ``n_heads``, ``n_kv_heads``, ``head_dim``,
``d_ff`` (the dense layers' width), ``moe_d_ff`` (one routed expert's),
``n_experts``, ``moe_top_k``, ``n_shared_experts``, ``sliding_window``,
``vocab_size``, and ``layer_kinds``, each layer's kind.
"""

WINDOW = "sliding_attention"


def window_layers(arch):
    return sum(k == WINDOW for k in arch["layer_kinds"])


def full_layers(arch):
    return len(arch["layer_kinds"]) - window_layers(arch)


def attention_params(arch):
    """q, k, v, o, the output gate, and the q and k norms' weights."""
    d, H, G, dh = (arch["d_model"], arch["n_heads"], arch["n_kv_heads"],
                   arch["head_dim"])
    return d * H * dh + 2 * d * G * dh + H * dh * d + d * H * dh + 2 * dh


def expert_params(arch):
    """One routed expert: gate, up, down."""
    return 3 * arch["d_model"] * arch["moe_d_ff"]


def expert_layer_fixed_params(arch):
    """What an expert layer reads whatever is routed: attention, the shared
    expert, the router with its bias, four norms."""
    d = arch["d_model"]
    return (attention_params(arch)
            + arch["n_shared_experts"] * expert_params(arch)
            + d * arch["n_experts"] + arch["n_experts"] + 4 * d)


def dense_layer_params(arch):
    d = arch["d_model"]
    return attention_params(arch) + 3 * d * arch["d_ff"] + 4 * d


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


def kv_row_bytes(arch, itemsize=2):
    """One cached token in one layer: its K and its V row."""
    return 2 * arch["n_kv_heads"] * arch["head_dim"] * itemsize


def attention_bytes(arch, full_rows, window_rows, itemsize=2):
    """K/V the decode attention must read in a step: ``full_rows`` (the
    live rows of the active slots) in every full layer, ``window_rows`` (of
    each slot the rows in its band: min(live, window)) in every window
    layer."""
    return (full_layers(arch) * full_rows
            + window_layers(arch) * window_rows) * kv_row_bytes(arch,
                                                                itemsize)


def decode_step_bytes(arch, full_rows, window_rows, experts_hit, itemsize=2):
    """Bytes one decode step must read: every weight outside the routed
    experts once (the embedding's rows are a few KB and left out; the head
    is read whole), the routed experts that were HIT (distinct experts with
    work, summed over the expert layers, from the program's counter), and
    the K/V rows of ``attention_bytes``."""
    kd = arch["first_k_dense"]
    d, v = arch["d_model"], arch["vocab_size"]
    fixed = (kd * dense_layer_params(arch)
             + (arch["n_layers"] - kd) * expert_layer_fixed_params(arch)
             + v * d + d)
    return ((fixed + experts_hit * expert_params(arch)) * itemsize
            + attention_bytes(arch, full_rows, window_rows, itemsize))


def chunk_flops(arch, tokens, start):
    """Operations of one prefill chunk of ``tokens`` positions written at
    ``start``: 2 per parameter a token passes through (attention
    projections and gate, dense FFN or top_k routed experts beside the
    shared one, router), scores and weighted sums over what each query
    SEES (all ``start + t + 1`` rows before and at it in a full layer, at
    most ``sliding_window`` of them in a window layer: the band counted),
    and the head for the one row that is sampled."""
    d, H, dh = arch["d_model"], arch["n_heads"], arch["head_dim"]
    kd, L, W = arch["first_k_dense"], arch["n_layers"], arch["sliding_window"]
    proj = attention_params(arch) - 2 * dh
    per_token = (L * proj + kd * 3 * d * arch["d_ff"]
                 + (L - kd) * ((arch["moe_top_k"] + arch["n_shared_experts"])
                               * expert_params(arch) + d * arch["n_experts"]))
    # query t (0-based in the chunk) sees start + t + 1 rows, a window
    # layer's query min(that, W)
    seen_full = tokens * start + tokens * (tokens + 1) // 2
    seen_window = sum(min(start + t + 1, W) for t in range(tokens))
    attend = (full_layers(arch) * seen_full
              + window_layers(arch) * seen_window) * H * 2 * dh
    return 2 * (tokens * per_token + attend + d * arch["vocab_size"])
