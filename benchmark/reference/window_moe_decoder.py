"""Plain reference of an AFMoE decoder as arcee-ai/Trinity-Mini publishes it
(``model_type`` afmoe): window and full attention layers mixed, grouped-query
heads with q/k norms and a sigmoid output gate, four norms a layer, leading
dense SwiGLU layers, then sigmoid-routed experts beside a shared expert;
forward pass only.

Written from the equations of ISSUE 34 (the catalog's config; what no key of
it states is from ``transformers``' ``models/afmoe/modeling_afmoe.py`` and
Arcee's description, and is listed under ``assumed`` in the configuration
file), in float32 ``jax.numpy`` at matmul precision ``highest``, with no
cache, no kernels and no batching tricks. ``x`` the residual stream, ``N*``
RMS norms with a learned weight:

    embedding     x = E[ids] * embed_scale              (sqrt(d): mup_enabled)
    attention     h = N1(x); q = Nq(reshape(h Wq, [H, dh])), k = Nk(reshape(
                  h Wk, [G, dh])) (RMS norm over dh, one weight of dh each),
                  v = reshape(h Wv, [G, dh]), g = sigmoid(h Wg) in [H * dh]
                  window layer: q, k rotated at the token's position (theta
                  rope_base, whole head, half-split pairs (x_i, x_{i+dh/2}),
                  as rotate_half), key j visible to query i iff
                  0 <= i - j < sliding_window
                  full layer: no rotation, j <= i
                  scores q k^T / sqrt(dh), float32 softmax, head h reads K/V
                  head h // (H / G); a = concat_h(softmax(.) v) * g
                  x = x + N2(a Wo)
    feed-forward  u = N3(x); leading layers y = (silu(u Wgate) * u Wup) Wdown
                  later: s = sigmoid(u Wr) in float32, chosen = top_k of
                  s + b, w = s[chosen] / (sum + 1e-20) * moe_routed_scale,
                  y = sum_e w_e SwiGLU_e(u) + SwiGLU_shared(u)
                  x = x + N4(y)
    head          logits = Nf(x) W_out, untied.

The band is a MASK here: every score of a block of queries against the whole
sequence is computed and the ones outside the band set to -inf. Every expert
is computed for every token and weighed by 0 where it was not chosen. What
this file shares with ``latent_moe_decoder`` (the expert layer, the norms,
layer picking, the routing comparisons) it imports from there.

``arch`` is the configuration file's ``arch`` group; a layer's kind is
``arch["layer_kinds"][layer]`` (the published ``layer_types`` of the layers
that are run).

It reads the engine's parameters as they are stored and upcasts one layer,
and inside it one expert, at a time. Departures from the published model,
the program's own and so the reference's too: the depth (the configuration
file's cut) and the selection bias ``b`` (trained when published, zero in the
program, drawn from the seed by the benchmark's runner).

``arch["break"]`` computes ONE thing wrongly, for the readings the cell's
limits are set between (the configuration file's ``checks``): ``"band"``
leaves the band out of the window layers, ``"rope"`` rotates in the full
layers too, ``"gate"`` leaves the output gate out. (The embedding's scale,
the routed factor and the precision are ``arch`` values themselves.)
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .latent_moe_decoder import (F32, SEQ_MULTIPLE, _frozen, _layer, embed,
                                 expert_ffn, head, lowered, rms_norm,
                                 routing_margins, swiglu, weight_errors)

__all__ = ["hidden_states", "logits_at", "routing_margins", "weight_errors"]

Q_BLOCK = 128
WINDOW = "sliding_attention"


def rotate_half(x, positions, base):
    """x [s, heads, dh], rotated at ``positions`` [s] in half-split pairs."""
    dh = x.shape[-1]
    inv_freq = 1.0 / (base ** (jnp.arange(0, dh, 2, dtype=F32) / dh))
    ang = positions.astype(F32)[:, None] * inv_freq          # [s, dh/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, u, arch, window):
    """u [s, d] (normed) -> [s, d]."""
    s = u.shape[0]
    H, G, dh = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    eps, broken = arch["layernorm_eps"], arch.get("break")
    pos = jnp.arange(s)
    q = rms_norm(p["q_norm"]["scale"],
                 (u @ p["q"]["kernel"]).reshape(s, H, dh), eps)
    k = rms_norm(p["k_norm"]["scale"],
                 (u @ p["k"]["kernel"]).reshape(s, G, dh), eps)
    v = (u @ p["v"]["kernel"]).reshape(s, G, dh)
    gate = jax.nn.sigmoid(u @ p["gate"]["kernel"])
    if window or broken == "rope":
        q = rotate_half(q, pos, arch["rope_base"])
        k = rotate_half(k, pos, arch["rope_base"])
    # head h = g * (H / G) + r reads K/V head g
    q = q.reshape(s, G, H // G, dh)
    band = window and broken != "band"

    def q_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, Q_BLOCK, 0)
        scores = jnp.einsum("qgrd,kgd->grqk", qb, k) / jnp.sqrt(F32(dh))
        diff = (start + jnp.arange(Q_BLOCK))[:, None] - pos[None, :]
        seen = diff >= 0
        if band:
            seen &= diff < arch["sliding_window"]
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(q_block, jnp.arange(0, s, Q_BLOCK)).reshape(s, H * dh)
    if broken != "gate":
        out = out * gate
    return out @ p["o"]["kernel"]


@functools.partial(jax.jit, static_argnames=("arch_items", "window", "dense"))
def block(stacked, i, x, forced, arch_items, window, dense):
    """Layer ``i`` of ``stacked`` (the dense or the expert stack) over x
    [s, d]. Returns (x, own choice, s + b, weights used), the last three
    None in a dense layer."""
    arch = dict(arch_items)
    p = _layer(stacked, i, arch, keep_narrow=("gate_up", "down"))
    eps = arch["layernorm_eps"]
    norm = lambda name, a: rms_norm(p[name]["scale"], a, eps)
    a = attention(p["attn"], lowered(norm("ln_1", x), arch), arch, window)
    x = x + norm("ln_1_post", a)
    u = lowered(norm("ln_2", x), arch)
    if dense:
        m = p["mlp"]
        y, routing = swiglu(m["gate"]["kernel"], m["up"]["kernel"],
                            m["down"]["kernel"], u), (None, None, None)
    else:
        y, *routing = expert_ffn(p["mlp"], u, arch, forced)
    return (x + norm("ln_2_post", y), *routing)


def hidden_states(params, ids, arch, forced=None):
    """ids [seq] -> (x [seq_padded, d] before the final norm, routing): per
    expert layer the reference's own choice [seq_padded, k], its ``s + b``
    [seq_padded, E] and the weights it gave the experts it used. ``forced``
    [L_moe, seq, k] forces the experts of every position."""
    ids = np.asarray(ids).reshape(-1)
    seq = ids.shape[0]
    padded = -(-seq // SEQ_MULTIPLE) * SEQ_MULTIPLE \
        if seq > Q_BLOCK else -(-seq // Q_BLOCK) * Q_BLOCK
    ids = np.pad(ids, (0, padded - seq))
    items = _frozen(arch)
    kd = arch["first_k_dense"]
    kinds = arch["layer_kinds"]
    routing = []
    with jax.default_matmul_precision("highest"):
        x = embed(params["wte"]["weight"], jnp.asarray(ids)) \
            * F32(arch["embed_scale"])
        for i in range(arch["n_layers"]):
            window = kinds[i] == WINDOW
            if i < kd:
                x = block(params["dense_blocks"], i, x, None, items, window,
                          True)[0]
                continue
            f = None
            if forced is not None:
                f = np.zeros((padded, arch["moe_top_k"]), np.int32)
                f[:seq] = forced[i - kd][:seq]
                f = jnp.asarray(f)
            x, own, select, w = block(params["blocks"], i - kd, x, f, items,
                                      window, False)
            routing.append((own, select, w))
    return x, routing


def logits_at(params, ids, arch, start, length, forced=None,
              return_routing=False):
    """float32 logits of positions ``start .. start+length`` of ONE sequence
    (``ids`` is [1, seq] or [seq])."""
    x, routing = hidden_states(params, ids, arch, forced)
    with jax.default_matmul_precision("highest"):
        out = head(params["ln_f"], params["lm_head"],
                   jax.lax.dynamic_slice_in_dim(x, start, length, 0),
                   eps=arch["layernorm_eps"])
    return (out, routing) if return_routing else out
