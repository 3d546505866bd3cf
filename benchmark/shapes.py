"""Operations and bytes of the dense decoder, computed from its shapes.

The yardstick's own arithmetic: nothing here is read from the program
(``cost_analysis`` counts a scan body once and was 44x off on this repo).
``arch`` is the ``arch`` group of a configuration file: ``n_layers``,
``d_model``, ``n_heads``, ``d_ff``, ``vocab_size``, ``max_seq_len``.
"""


def param_count(arch):
    """Parameters of a GPT-2/OPT style decoder with biases, learned
    positions, tied embeddings and a final layer norm."""
    d, f = arch["d_model"], arch["d_ff"]
    per_layer = (4 * d * d + 4 * d      # q, k, v, o with biases
                 + 2 * d * f + f + d    # fc, proj with biases
                 + 4 * d)               # two layer norms
    return (arch["n_layers"] * per_layer
            + arch["vocab_size"] * d    # token table, also the output head
            + arch["max_seq_len"] * d   # learned positions
            + 2 * d)                    # final layer norm


def train_flops_per_token(arch, seq_len):
    """Forward plus backward operations a trained token requires: 6 per
    parameter (the convention ``bench.py`` uses, every parameter counted),
    plus causal attention's two matrix products, 2 * seq * d_model a layer
    forward (half of the full square), three times that with the backward
    pass. Recomputed operations are not counted."""
    attention = 6 * arch["n_layers"] * seq_len * arch["d_model"]
    return 6 * param_count(arch) + attention


def kv_bytes_per_token(arch, kv_itemsize=2):
    """K and V of one cached token over all layers."""
    return 2 * arch["n_layers"] * arch["d_model"] * kv_itemsize


def decode_step_bytes(arch, live_kv_tokens, weight_itemsize=2,
                      kv_itemsize=2):
    """Bytes one decode step must read from device memory: every weight
    once, and the cached K and V of the tokens its active slots hold."""
    return (param_count(arch) * weight_itemsize
            + live_kv_tokens * kv_bytes_per_token(arch, kv_itemsize))
