"""Plain reference of a Nemotron-H decoder as nvidia/NVIDIA-Nemotron-3-Super
-120B-A12B-BF16 publishes it (``model_type`` nemotron_h): Mamba-2 mixers,
LatentMoE expert layers and attention layers without positions, one mixer a
layer; forward pass only.

Written from the published equations (the model's config; what no key
states is listed under ``assumed`` in the configuration file), in float32
``jax.numpy`` at matmul precision ``highest``, with no cache, no kernels and
no chunked scan. ``x`` the residual stream, ``N`` an RMS norm with a learned
weight (eps ``layernorm_eps``):

    embedding   x = E[ids]; every layer x = x + mixer(N(x)) = x + m(u)
    Mamba-2     [z | xBC | dt] = u W_in (widths H P | H P + 2 G N | H)
                xBC = silu(b + sum_k w_k xBC[t - K + 1 + k]) (depthwise,
                zeros before the first token); x = [H, P], B, C = [G, N]
                dt = softplus(dt + dt_bias), A = -exp(A_log); TOKEN BY TOKEN
                S_h = exp(dt_h A_h) S_h + dt_h x_h B_g^T  (g = h // (H / G))
                y_h = S_h C_g + D_h x_h
                y = N_by_group(y * silu(z)) over G groups of H P / G
                m = y W_out
    expert      s = sigmoid(u W_r) in float32 over all E experts; chosen =
                top_k of s + b; w = scale s[chosen] / (sum over the k chosen
                + 1e-20); l = u W_lat_in; r = sum over the chosen e in [lo,
                lo + n) of w_e relu(l W1_e)^2 W2_e (the experts this chip
                HOLDS, weighed as the whole layer weighs them); m = r W_lat_out
                + relu(u W_s1)^2 W_s2
    attention   q = [H, dh], k, v = [G, dh] from u; no rotation; scores q k^T
                / sqrt(dh), j <= i, head h reads K/V head h // (H / G); m =
                concat_h(softmax v) W_o
    head        logits = N_f(x) W_out, untied.

The recurrence runs one token after another (``lax.scan`` over positions),
independent of the served path's chunked form; every held expert is computed
for every token and weighed by 0 where it was not chosen; attention is a
causal MASK over blocks of Q_BLOCK queries. The RMS norm, the rounding used
for the limits' second reading and the routing comparisons are
``latent_moe_decoder``'s (imported); nothing here comes from
``deepspeed_tpu.models``.

It reads the engine's parameters as they are stored (``wte``, ``lm_head``,
``ln_f``, ``layers[i]["norm"]`` and ``["mixer"]``) and upcasts one layer,
and inside it one expert, at a time. Departures from the published model,
the program's own and so the reference's too: the depth, the experts held
and the vocabulary slice (the configuration file's cut), no MTP layers, and
the selection bias ``b`` (trained when published; zero in the program,
drawn from the seed by the benchmark's runner).

``arch["break"]`` computes ONE thing otherwise, for the readings the cell's
limits are set between: ``"state_bf16"`` rounds the state to bfloat16 after
every token, ``"norm_whole"`` norms y over all H P at once (the original
Mamba-2 layer of ``transformers``), ``"no_d"`` leaves the D term out.
(The precision is ``arch["round_to"]`` itself.)
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .latent_moe_decoder import (F32, SEQ_MULTIPLE, _frozen, embed, head,
                                 lowered, rms_norm, routing_margins,
                                 weight_errors)

__all__ = ["hidden_states", "logits_at", "routing_margins", "weight_errors"]

Q_BLOCK = 64


def _f32(tree, arch, keep_narrow=()):
    """A layer's weights in float32 (rounded where ``arch`` asks); the
    expert stacks stay as stored and are upcast an expert at a time."""
    def one(path, a):
        name = getattr(path[-1], "key", None)
        return a if name in keep_narrow else lowered(a.astype(F32), arch)
    return jax.tree_util.tree_map_with_path(one, tree)


def mamba(p, u, arch, n_real):
    """u [s, d] (normed) -> ([s, d], S after position ``n_real - 1`` [G, H /
    G, P, N]), the recurrence token by token; the positions from ``n_real``
    on (padding) leave S as it is."""
    s = u.shape[0]
    H, P, N = arch["ssm_heads"], arch["ssm_head_dim"], arch["ssm_state"]
    G, K = arch["ssm_groups"], arch["ssm_conv"]
    d_in, gn = H * P, G * N
    broken = arch.get("break")
    proj = u @ p["in_proj"]["kernel"]
    z, xbc, dt = proj[:, :d_in], proj[:, d_in:2 * d_in + 2 * gn], \
        proj[:, 2 * d_in + 2 * gn:]
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    w = p["conv"]["kernel"]
    conv = p["conv"]["bias"] + sum(padded[k:k + s] * w[k] for k in range(K))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d_in].reshape(s, G, H // G, P)
    B = xbc[:, d_in:d_in + gn].reshape(s, G, N)
    C = xbc[:, d_in + gn:].reshape(s, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"]).reshape(s, G, H // G)
    A = -jnp.exp(p["A_log"]).reshape(G, H // G)

    def one_token(S, t):
        xt, bt, ct, dtt, real = t
        new = jnp.exp(dtt * A)[..., None, None] * S \
            + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :]
        if broken == "state_bf16":
            # rounded to bf16's 7 bits of mantissa (a convert pair the TPU
            # compiler may drop as excess precision)
            new = jax.lax.reduce_precision(new, exponent_bits=8,
                                           mantissa_bits=7)
        return jnp.where(real, new, S), jnp.einsum("grpn,gn->grp", new, ct)

    final, y = jax.lax.scan(one_token, jnp.zeros((G, H // G, P, N), F32),
                            (x, B, C, dt, jnp.arange(s) < n_real))
    if broken != "no_d":
        y = y + p["D"].reshape(G, H // G, 1) * x
    y = y.reshape(s, d_in) * jax.nn.silu(z)
    if broken == "norm_whole":
        y = rms_norm(1.0, y, arch["layernorm_eps"])
    else:
        y = rms_norm(1.0, y.reshape(s, G, d_in // G),
                     arch["layernorm_eps"]).reshape(s, d_in)
    return (y * p["norm"]["scale"]) @ p["out_proj"]["kernel"], final


def attention(p, u, arch):
    """u [s, d] (normed) -> [s, d]."""
    s = u.shape[0]
    H, G, dh = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
    pos = jnp.arange(s)
    q = (u @ p["q"]["kernel"]).reshape(s, G, H // G, dh)
    k = (u @ p["k"]["kernel"]).reshape(s, G, dh)
    v = (u @ p["v"]["kernel"]).reshape(s, G, dh)

    def q_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, Q_BLOCK, 0)
        scores = jnp.einsum("qgrd,kgd->grqk", qb, k) / jnp.sqrt(F32(dh))
        seen = (start + jnp.arange(Q_BLOCK))[:, None] >= pos[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("grqk,kgd->qgrd", probs, v)

    out = jax.lax.map(q_block, jnp.arange(0, s, Q_BLOCK)).reshape(s, H * dh)
    return out @ p["o"]["kernel"]


def relu2(up, down, u):
    return jnp.square(jax.nn.relu(u @ up)) @ down


def expert_ffn(p, u, arch, forced):
    """u [s, d] -> (m [s, d], own choice [s, k], s + b [s, E], weights of
    the experts used [s, k]). With ``forced`` [s, k] the experts are those,
    else the layer's own."""
    k = arch["moe_top_k"]
    lo = arch.get("moe_expert_offset", 0)
    n = arch.get("moe_local_experts") or arch["n_experts"]
    scores = jax.nn.sigmoid(u @ p["router"]["kernel"])
    select = scores + p["router"]["bias"]
    own = jax.lax.top_k(select, k)[1]
    chosen = own if forced is None else forced
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    w = arch["moe_routed_scale"] * picked / (
        picked.sum(-1, keepdims=True) + 1e-20)
    dense_w = jnp.zeros_like(scores).at[
        jnp.arange(u.shape[0])[:, None], chosen].add(w)
    lat = u @ p["latent_in"]["kernel"]

    def one_expert(r, e):
        up = lowered(jax.lax.dynamic_index_in_dim(
            p["up"], e, 0, False).astype(F32), arch)
        down = lowered(jax.lax.dynamic_index_in_dim(
            p["down"], e, 0, False).astype(F32), arch)
        return r + relu2(up, down, lat) * jax.lax.dynamic_index_in_dim(
            dense_w, lo + e, 1, True), None

    r, _ = jax.lax.scan(one_expert, jnp.zeros_like(lat), jnp.arange(n))
    sh = p["shared"]
    m = r @ p["latent_out"]["kernel"] \
        + relu2(sh["up"]["kernel"], sh["down"]["kernel"], u)
    return m, own, select, w


@functools.partial(jax.jit, static_argnames=("arch_items", "kind"))
def layer(p, x, forced, n_real, arch_items, kind):
    """One layer over x [s, d], of which the first ``n_real`` positions are
    the sequence. Returns (x, own choice, s + b, weights used), the last
    three None but in an expert layer; in a Mamba layer (x, S after the
    sequence, None, None)."""
    arch = dict(arch_items)
    p = _f32(p, arch, keep_narrow=("up", "down") if kind == "E" else ())
    u = lowered(rms_norm(p["norm"]["scale"], x, arch["layernorm_eps"]), arch)
    if kind == "M":
        m, final = mamba(p["mixer"], u, arch, n_real)
        return x + m, final, None, None
    if kind == "*":
        return x + attention(p["mixer"], u, arch), None, None, None
    m, *routing = expert_ffn(p["mixer"], u, arch, forced)
    return (x + m, *routing)


def hidden_states(params, ids, arch, forced=None):
    """ids [seq] -> (x [seq_padded, d] before the final norm, routing,
    states): per expert layer the reference's own choice [seq_padded, k],
    its ``s + b`` [seq_padded, E] and the weights it gave the experts it
    used; per Mamba layer its S after the last position of the sequence [H,
    P, N]. ``forced`` [L_moe, seq, k] forces the experts of every position.
    (Padding comes after the sequence; nothing causal sees it.)"""
    ids = np.asarray(ids).reshape(-1)
    seq = ids.shape[0]
    padded = -(-seq // SEQ_MULTIPLE) * SEQ_MULTIPLE \
        if seq > Q_BLOCK else Q_BLOCK
    ids = np.pad(ids, (0, padded - seq))
    items = _frozen(arch)
    routing, states = [], []
    with jax.default_matmul_precision("highest"):
        x = embed(params["wte"]["weight"], jnp.asarray(ids))
        for kind, p in zip(arch["layer_kinds"], params["layers"]):
            f = None
            if kind == "E" and forced is not None:
                f = np.zeros((padded, arch["moe_top_k"]), np.int32)
                f[:seq] = forced[len(routing)][:seq]
                f = jnp.asarray(f)
            x, *r = layer(p, x, f, np.int32(seq), items, kind)
            if kind == "E":
                routing.append(tuple(r))
            if kind == "M":
                states.append(r[0].reshape(-1, *r[0].shape[2:]))
    return x, routing, states


def logits_at(params, ids, arch, start, length, forced=None,
              return_routing=False, return_states=False):
    """float32 logits of positions ``start .. start+length`` of ONE sequence
    (``ids`` is [1, seq] or [seq]); with ``return_routing`` and
    ``return_states``, ``hidden_states``'s routing and states after them."""
    x, routing, states = hidden_states(params, ids, arch, forced)
    with jax.default_matmul_precision("highest"):
        out = head(params["ln_f"], params["lm_head"],
                   jax.lax.dynamic_slice_in_dim(x, start, length, 0),
                   eps=arch["layernorm_eps"])
    out = (out,) + ((routing,) if return_routing else ()) \
        + ((states,) if return_states else ())
    return out if len(out) > 1 else out[0]
