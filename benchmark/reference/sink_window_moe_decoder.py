"""Plain reference of a MiMo-V2 decoder as XiaomiMiMo/MiMo-V2-Flash publishes
it (``model_type`` mimo_v2_flash): window layers with a learned sink beside
full layers of another K/V head count, query and key heads wider than value
heads, rotation over part of the head with a base per layer kind, a value
scale, a leading dense SwiGLU layer, then sigmoid-routed experts of which
this chip holds a share; forward pass only.

Written from the equations of ISSUE 37 (the catalog's config and
``described_as``; what no key states is listed under ``assumed`` in the
configuration file), in float32 ``jax.numpy`` at matmul precision
``highest``, with no cache, no kernels and no batching tricks. ``x`` the
residual stream, ``N*`` RMS norms with a learned weight, ``t`` the layer's
kind (full / window), ``G_t`` its K/V heads, ``theta_t`` its rotary base:

    embedding     x = E[ids]
    attention     h = N1(x); q = reshape(h Wq, [H, dk]), k = reshape(h Wk_t,
                  [G_t, dk]), v = reshape(h Wv_t, [G_t, dv]) * value_scale
                  q, k rotated at the token's position over their FIRST
                  ``rotary_dim`` dims (half-split pairs (x_i, x_{i+rd/2})
                  within them, base theta_t); the other dims pass
                  scores q k^T / sqrt(dk), head h reads K/V head h // (H /
                  G_t); full layer: j <= i; window layer: 0 <= i - j <
                  sliding_window, and one more COLUMN a head, the sink b_h:
                  p = softmax([scores, b_h]) with that column dropped
                  a = concat_h(p v) in [H * dv];  x = x + a Wo
    feed-forward  u = N2(x); layer 0: y = (silu(u Wgate) * u Wup) Wdown
                  later: s = sigmoid(u Wr) in float32 over all E experts,
                  chosen = top_k of s + b, w = s[chosen] / (sum over the k
                  chosen + 1e-20); y = sum over the chosen e in [lo, lo + n)
                  of w_e SwiGLU_e(u): the experts this chip HOLDS, weighed as
                  the whole layer weighs them; what the absent experts would
                  add is left out and the partial y goes on
                  x = x + y
    head          logits = Nf(x) W_out, untied.

The band and the causal limit are MASKS here: every score of a block of
queries against the whole sequence is computed and the ones outside set to
-inf. Every held expert is computed for every token and weighed by 0 where it
was not chosen. The norms, SwiGLU, layer picking, the rounding used for the
limits' second reading and the routing comparisons are ``latent_moe_decoder``'s
(imported); nothing here comes from ``deepspeed_tpu.models`` or from
``window_moe_decoder``.

``arch`` is the configuration file's ``arch`` group; a layer's kind is
``arch["layer_kinds"][layer]``. It reads the engine's parameters as they are
stored (``blocks`` / ``dense_blocks``: norms, q, o and the feed-forward;
``kv_full`` / ``kv_window``: K, V and the sinks by kind, a layer at its index
among the layers of its kind) and upcasts one layer, and inside it one
expert, at a time. Departures from the published model, the program's own
and so the reference's too: the depth, the experts held and the vocabulary
slice (the configuration file's cut), no MTP layers, and the selection bias
``b`` and the sinks (trained when published; zero in the program, drawn from
the seed by the benchmark's runner).

``arch["break"]`` computes ONE thing wrongly, for the readings the cell's
limits are set between (the configuration file's ``checks``): ``"sink"``
leaves the sink out, ``"rope_whole"`` rotates over the whole head,
``"bases"`` swaps the two kinds' bases, ``"band"`` leaves the band out of the
window layers, ``"window_heads"`` reads the window layers with the full
layers' K/V head count (head h reads K/V head h // (H / G_full)),
``"share_norm"`` normalises the weights over the held chosen experts only.
(The value scale and the precision are ``arch`` values themselves.)
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .latent_moe_decoder import (F32, SEQ_MULTIPLE, _frozen, _layer, embed,
                                 head, lowered, rms_norm, routing_margins,
                                 swiglu, weight_errors)

__all__ = ["hidden_states", "logits_at", "routing_margins", "weight_errors"]

Q_BLOCK = 64
WINDOW = "sliding_attention"


def rotate_part(x, positions, base, rotary_dim):
    """x [s, heads, dh]: the first ``rotary_dim`` dims rotated at
    ``positions`` [s] in half-split pairs within them, the rest as it is."""
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    inv_freq = 1.0 / (base ** (jnp.arange(0, rotary_dim, 2, dtype=F32)
                               / rotary_dim))
    ang = positions.astype(F32)[:, None] * inv_freq          # [s, rd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = rot[..., :rotary_dim // 2], rot[..., rotary_dim // 2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def attention(p, kv, u, arch, window):
    """u [s, d] (normed) -> [s, d]. ``p`` the layer's q and o, ``kv`` its
    kind's k, v and (window) sink."""
    s = u.shape[0]
    H, dk, dv = arch["n_heads"], arch["head_dim"], arch["v_head_dim"]
    G = arch["n_kv_heads_window"] if window else arch["n_kv_heads"]
    broken = arch.get("break")
    pos = jnp.arange(s)
    q = (u @ p["q"]["kernel"]).reshape(s, H, dk)
    k = (u @ kv["k"]["kernel"]).reshape(s, G, dk)
    v = (u @ kv["v"]["kernel"]).reshape(s, G, dv) * F32(
        arch["attn_value_scale"])
    bases = (arch["rope_base_window"], arch["rope_base"])
    base = bases[(not window) ^ (broken == "bases")]
    rd = dk if broken == "rope_whole" else arch["rotary_dim"]
    q, k = rotate_part(q, pos, base, rd), rotate_part(k, pos, base, rd)
    if window and broken == "window_heads":
        G = arch["n_kv_heads"]
        k, v = k[:, :G], v[:, :G]
    # head h = g * (H / G) + r reads K/V head g
    q = q.reshape(s, G, H // G, dk)
    band = window and broken != "band"
    sink = kv["sink"].reshape(G, H // G) \
        if window and broken != "sink" else None

    def q_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, Q_BLOCK, 0)
        scores = jnp.einsum("qgrd,kgd->grqk", qb, k) / jnp.sqrt(F32(dk))
        diff = (start + jnp.arange(Q_BLOCK))[:, None] - pos[None, :]
        seen = diff >= 0
        if band:
            seen &= diff < arch["sliding_window"]
        scores = jnp.where(seen, scores, -jnp.inf)
        if sink is not None:
            scores = jnp.concatenate([scores, jnp.broadcast_to(
                sink[:, :, None, None], scores.shape[:3] + (1,))], -1)
        probs = jax.nn.softmax(scores, -1)[..., :s]
        return jnp.einsum("grqk,kgd->qgrd", probs, v)

    out = jax.lax.map(q_block, jnp.arange(0, s, Q_BLOCK)).reshape(s, H * dv)
    return out @ p["o"]["kernel"]


def expert_ffn(p, u, arch, forced):
    """u [s, d] -> (y [s, d], own choice [s, k], s + b [s, E], weights of
    the experts used [s, k]). With ``forced`` [s, k] the experts are those,
    else the layer's own. y sums the chosen experts this chip holds."""
    k, f = arch["moe_top_k"], arch["moe_d_ff"]
    lo = arch.get("moe_expert_offset", 0)
    n = arch.get("moe_local_experts") or arch["n_experts"]
    scores = jax.nn.sigmoid(u @ p["router"]["kernel"])
    select = scores + p["router"]["bias"]
    own = jax.lax.top_k(select, k)[1]
    chosen = own if forced is None else forced
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    total = picked.sum(-1, keepdims=True)
    if arch.get("break") == "share_norm":
        total = jnp.where((chosen >= lo) & (chosen < lo + n), picked,
                          0.0).sum(-1, keepdims=True)
    w = picked / (total + 1e-20)
    # weight of every expert for every token: 0 where it was not chosen
    dense_w = jnp.zeros_like(scores).at[
        jnp.arange(u.shape[0])[:, None], chosen].add(w)

    def one_expert(y, e):
        gu = lowered(jax.lax.dynamic_index_in_dim(
            p["gate_up"], e, 0, False).astype(F32), arch)
        dn = lowered(jax.lax.dynamic_index_in_dim(
            p["down"], e, 0, False).astype(F32), arch)
        out = swiglu(gu[:, :f], gu[:, f:], dn, u)
        return y + out * jax.lax.dynamic_index_in_dim(
            dense_w, lo + e, 1, True), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(u), jnp.arange(n))
    return y, own, select, w


@functools.partial(jax.jit, static_argnames=("arch_items", "window", "dense"))
def block(stacked, i, kv_stack, g, x, forced, arch_items, window, dense):
    """Layer ``i`` of ``stacked`` (the dense or the expert stack), with K, V
    and sink from entry ``g`` of its kind's stack, over x [s, d]. Returns (x,
    own choice, s + b, weights used), the last three None in a dense
    layer."""
    arch = dict(arch_items)
    p = _layer(stacked, i, arch, keep_narrow=("gate_up", "down"))
    kv = _layer(kv_stack, g, arch)
    eps = arch["layernorm_eps"]
    norm = lambda name, a: rms_norm(p[name]["scale"], a, eps)
    x = x + attention(p["attn"], kv, lowered(norm("ln_1", x), arch), arch,
                      window)
    u = lowered(norm("ln_2", x), arch)
    if dense:
        m = p["mlp"]
        return (x + swiglu(m["gate"]["kernel"], m["up"]["kernel"],
                           m["down"]["kernel"], u), None, None, None)
    y, *routing = expert_ffn(p["mlp"], u, arch, forced)
    return (x + y, *routing)


def hidden_states(params, ids, arch, forced=None):
    """ids [seq] -> (x [seq_padded, d] before the final norm, routing): per
    expert layer the reference's own choice [seq_padded, k], its ``s + b``
    [seq_padded, E] and the weights it gave the experts it used. ``forced``
    [L_moe, seq, k] forces the experts of every position."""
    ids = np.asarray(ids).reshape(-1)
    seq = ids.shape[0]
    padded = -(-seq // SEQ_MULTIPLE) * SEQ_MULTIPLE \
        if seq > Q_BLOCK else Q_BLOCK
    ids = np.pad(ids, (0, padded - seq))
    items = _frozen(arch)
    kd = arch["first_k_dense"]
    kinds = arch["layer_kinds"]
    seen = {True: 0, False: 0}      # layers of each kind so far
    routing = []
    with jax.default_matmul_precision("highest"):
        x = embed(params["wte"]["weight"], jnp.asarray(ids))
        for i in range(arch["n_layers"]):
            window = kinds[i] == WINDOW
            kv_stack = params["kv_window" if window else "kv_full"]
            g, seen[window] = seen[window], seen[window] + 1
            if i < kd:
                x = block(params["dense_blocks"], i, kv_stack, g, x, None,
                          items, window, True)[0]
                continue
            f = None
            if forced is not None:
                f = np.zeros((padded, arch["moe_top_k"]), np.int32)
                f[:seq] = forced[i - kd][:seq]
                f = jnp.asarray(f)
            x, own, select, w = block(params["blocks"], i - kd, kv_stack, g,
                                      x, f, items, window, False)
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
