"""Plain reference of the GPT-2 / OPT decoder: forward pass and loss.

Written from the papers (Radford et al. 2019, section 2.3: pre-norm blocks
and a final layer norm after the last block; Zhang et al. 2022, section 2.1:
the same block with ReLU), in float32 ``jax.numpy`` at matmul precision
``highest``, with no kernels, no cache and no batching tricks:

    x   = token_table[ids] + position_table[0..s)
    x  += attn(layer_norm_1(x))      softmax(q k^T / sqrt(d_head) + causal) v
    x  += mlp(layer_norm_2(x))       act(h W_fc + b_fc) W_proj + b_proj
    logits = layer_norm_f(x) token_table^T           (tied embeddings)
    loss   = mean over positions 0..s-2 of -log softmax(logits)[next id]

It reads the engine's parameters as they are stored (tree ``wte``, ``wpe``,
``ln_f`` and ``blocks`` stacked over layers; float32 masters when trained,
bf16 when served, possibly sharded), and upcasts ONE layer at a time: a
float32 copy of OPT-1.3B is 5.3 GB, which does not fit beside a KV pool or
a ZeRO-3 step. Sharded leaves go through ``jit``, which gathers what one
layer needs. Long sequences compute attention in blocks of 512 queries so
the score matrix stays small.

Departures from the published models, which are the program's own and so the
reference's too: positions start at row 0 of the table (Hugging Face's OPT
skips two rows), and GPT-2's vocabulary is padded from 50257 to 50304 rows.
"""

import functools

import jax
import jax.numpy as jnp

ACTIVATIONS = {
    # GPT-2's "gelu_new": 0.5 x (1 + tanh(sqrt(2/pi) (x + 0.044715 x^3)))
    "gelu_new": lambda x: 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3))),
    "relu": lambda x: jnp.maximum(x, 0.0),
}
Q_BLOCK = 512
F32 = jnp.float32


def layer_norm(p, x, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def linear(p, x):
    return x @ p["kernel"] + p["bias"]


def attention(p, x, n_heads):
    b, s, d = x.shape
    dh = d // n_heads
    split = lambda t: t.reshape(b, s, n_heads, dh).transpose(0, 2, 1, 3)
    q, k, v = (split(linear(p[n], x)) for n in ("q", "k", "v"))
    out = []
    for start in range(0, s, Q_BLOCK):
        qb = q[:, :, start:start + Q_BLOCK]
        scores = qb @ k.transpose(0, 1, 3, 2) / jnp.sqrt(F32(dh))
        rows = start + jnp.arange(qb.shape[2])[:, None]
        scores = jnp.where(jnp.arange(s)[None, :] <= rows, scores, -jnp.inf)
        out.append(jax.nn.softmax(scores, axis=-1) @ v)
    out = jnp.concatenate(out, axis=2).transpose(0, 2, 1, 3).reshape(b, s, d)
    return linear(p["o"], out)


@functools.partial(jax.jit, static_argnames=("n_heads", "activation", "eps"))
def block(blocks, layer, x, *, n_heads, activation, eps):
    """One decoder block, with layer ``layer`` of the stacked parameters."""
    p = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, False).astype(F32),
        blocks)
    x = x + attention(p["attn"], layer_norm(p["ln_1"], x, eps), n_heads)
    h = linear(p["mlp"]["fc"], layer_norm(p["ln_2"], x, eps))
    return x + linear(p["mlp"]["proj"], ACTIVATIONS[activation](h))


@jax.jit
def embed(wte, wpe, ids):
    return wte.astype(F32)[ids] + wpe.astype(F32)[:ids.shape[1]][None]


@functools.partial(jax.jit, static_argnames=("eps",))
def head(ln_f, wte, x, *, eps):
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), ln_f)
    return layer_norm(p, x, eps) @ wte.astype(F32).T


def hidden_states(params, ids, arch):
    """[batch, seq] ids -> [batch, seq, d_model] before the final norm."""
    with jax.default_matmul_precision("highest"):
        x = embed(params["wte"]["weight"], params["wpe"]["weight"],
                  jnp.asarray(ids))
        for layer in range(arch["n_layers"]):
            x = block(params["blocks"], layer, x, n_heads=arch["n_heads"],
                      activation=arch["activation"],
                      eps=arch["layernorm_eps"])
    return x


def logits_at(params, ids, arch, start, length):
    """float32 logits of positions ``start .. start+length`` of ONE sequence
    (``ids`` is [1, seq])."""
    x = hidden_states(params, ids, arch)
    with jax.default_matmul_precision("highest"):
        return head(params["ln_f"], params["wte"]["weight"],
                    jax.lax.dynamic_slice_in_dim(x, start, length, 1),
                    eps=arch["layernorm_eps"])[0]


def loss(params, ids, arch):
    """Mean next-token cross entropy over a [batch, seq] batch, one sequence
    at a time so that the logits of one sequence are all that is held."""
    total, count = 0.0, 0
    for row in jnp.asarray(ids):
        lg = logits_at(params, row[None], arch, 0, row.shape[0])[:-1]
        nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
            lg, row[1:, None], axis=-1)[:, 0]
        total, count = total + float(nll.sum()), count + nll.shape[0]
    return total / count
