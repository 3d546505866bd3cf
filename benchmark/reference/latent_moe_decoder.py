"""Plain reference of a DeepSeek-V3 style decoder as kanana-2-30b-a3b
publishes it (``model_type`` deepseek_v3): latent attention (MLA) without a
q LoRA, one leading dense SwiGLU layer, then layers of sigmoid-routed
experts beside shared experts; forward pass only.

Written from the published equations (ISSUE 29, section 1; DeepSeek-V2
arXiv:2405.04434 section 2.1 for MLA, DeepSeek-V3 arXiv:2412.19437 section
2.1.2 for the routing), in float32 ``jax.numpy`` at matmul precision
``highest``, with no cache, no kernels and no batching tricks:

    every layer   h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    attention     q = W_q u -> H heads of [q_nope (dn) | q_rope (dr)]
                  [c_raw (r) | k_rope_raw (dr)] = W_kva u;  c = RMSNorm_r(c_raw)
                  per head [k_nope_h (dn) | v_h (dv)] = W_kvb,h c
                  q_rope_h, k_rope_raw rotated at the token's position, base
                  rope_base over dr dims in INTERLEAVED pairs (x0,x1),(x2,x3)..
                  score_h(t,s) = (q_nope_h(t).k_nope_h(s) + q_rope_h(t).k_rope(s))
                                 / sqrt(dn + dr),  causal, softmax in float32
                  out = W_o concat_h(sum_s p v_h(s))
    FFN, layer 0  W_down (silu(W_gate u) * W_up u)
    FFN, later    s = sigmoid(W_g u) in float32; choose the top_k largest of
                  s + b; w_i = scale * s_i / (sum over the chosen of s + 1e-20);
                  y = sum_i w_i E_i(u) + S(u), every E_i and S a SwiGLU
    after the last layer: final RMSNorm, untied head.

Hugging Face's modeling_deepseek_v3 de-interleaves q_rope and k_rope into
[even dims | odd dims] and then rotates half against half; rotating the
interleaved pairs in place gives the SAME scores, because q and k are
permuted alike and a dot product does not see a common permutation. Here
the pairs are rotated in place.

Every expert is computed for every token and weighed by 0 where it was not
chosen (a loop over the experts): the plain form, 128/6 times the work the
served path does. Attention is computed in blocks of Q_BLOCK queries so the
score matrix of a 16k-token request stays small.

It reads the engine's parameters as they are stored (``wte``, ``lm_head``,
``ln_f``, ``dense_blocks`` stacked over the leading dense layers, ``blocks``
stacked over the expert layers; bf16 when served) and upcasts one layer, and
inside it one expert, at a time.

Departures from the published model, the program's own and so the
reference's too: the depth (``n_layers`` of 48; the layers left out would be
later pipeline stages) and the selection bias ``b``, which is trained in the
published model, zero when the program makes its weights, and drawn from the
seed by the benchmark's runner (``latent_serve_loop.seed_selection_bias``).

``forced`` ([L_moe, seq, k] expert ids) makes every expert layer use the
given choices (weights still from its own scores) and reports, beside, the
choice the reference would have made itself: see ``routing_margins``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 256
SEQ_MULTIPLE = 1024   # sequences are padded to a multiple: few programs


def rms_norm(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def lowered(x, arch):
    """``x`` as it is, or rounded through ``arch["round_to"]`` (a dtype's
    name) where a caller asks what the reference gives in a lower precision:
    the reading the cell's limits are set against (PERF.md). Applied to every
    weight and to every block's normed input."""
    dtype = arch.get("round_to")
    return x.astype(getattr(jnp, dtype)).astype(F32) if dtype else x


def rotate_pairs(x, positions, base):
    """x [s, ..., dr], rotated at ``positions`` [s] in interleaved pairs."""
    dr = x.shape[-1]
    inv_freq = 1.0 / (base ** (jnp.arange(0, dr, 2, dtype=F32) / dr))
    ang = positions.astype(F32)[:, None] * inv_freq          # [s, dr/2]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (dr // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     axis=-1).reshape(x.shape)


def attention(p, u, arch):
    """u [s, d] (normed) -> [s, d]."""
    s = u.shape[0]
    H, r = arch["n_heads"], arch["kv_lora_rank"]
    dn, dr, dv = (arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
                  arch["v_head_dim"])
    pos = jnp.arange(s)
    q = (u @ p["q"]["kernel"]).reshape(s, H, dn + dr)
    q_nope, q_rope = q[..., :dn], rotate_pairs(q[..., dn:], pos,
                                               arch["rope_base"])
    kva = u @ p["kv_a"]["kernel"]
    c = rms_norm(p["kv_norm"]["scale"], kva[:, :r], arch["layernorm_eps"])
    k_rope = rotate_pairs(kva[:, r:], pos, arch["rope_base"])   # [s, dr]
    kv = (c @ p["kv_b"]["kernel"]).reshape(s, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]

    def q_block(start):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, start, Q_BLOCK, 0)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, start, Q_BLOCK, 0)
        scores = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
                  + jnp.einsum("qhd,kd->hqk", qr, k_rope)) \
            / jnp.sqrt(F32(dn + dr))
        rows = start + jnp.arange(Q_BLOCK)[:, None]
        scores = jnp.where(jnp.arange(s)[None, :] <= rows, scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(q_block, jnp.arange(0, s, Q_BLOCK))
    return out.reshape(s, H * dv) @ p["o"]["kernel"]


def swiglu(gate, up, down, u):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def expert_ffn(p, u, arch, forced):
    """u [s, d] -> (y [s, d], own choice [s, k], s + b [s, E], weights of
    the experts used [s, k]). With ``forced`` [s, k] the experts are those,
    else the layer's own."""
    E, k, f = arch["n_experts"], arch["moe_top_k"], arch["moe_d_ff"]
    scores = jax.nn.sigmoid(u @ p["router"]["kernel"])
    select = scores + p["router"]["bias"]
    own = jax.lax.top_k(select, k)[1]
    chosen = own if forced is None else forced
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    w = arch["moe_routed_scale"] * picked / (
        picked.sum(-1, keepdims=True) + 1e-20)
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
            dense_w, e, 1, True), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(u), jnp.arange(E))
    sh = p["shared"]
    y = y + swiglu(sh["gate"]["kernel"], sh["up"]["kernel"],
                   sh["down"]["kernel"], u)
    return y, own, select, w


def _layer(stacked, i, arch, keep_narrow=()):
    """Layer ``i`` of stacked parameters in float32; the expert stacks stay
    as stored and are upcast an expert at a time."""
    def pick(path, a):
        a = jax.lax.dynamic_index_in_dim(a, i, 0, False)
        name = path[-1].key if hasattr(path[-1], "key") else None
        return a if name in keep_narrow else lowered(a.astype(F32), arch)

    return jax.tree_util.tree_map_with_path(pick, stacked)


@functools.partial(jax.jit, static_argnames=("arch_items",))
def dense_block(dense_blocks, i, x, arch_items):
    arch = dict(arch_items)
    p = _layer(dense_blocks, i, arch)
    eps = arch["layernorm_eps"]
    h = x + attention(p["attn"], lowered(
        rms_norm(p["ln_1"]["scale"], x, eps), arch), arch)
    m = p["mlp"]
    return h + swiglu(m["gate"]["kernel"], m["up"]["kernel"],
                      m["down"]["kernel"],
                      lowered(rms_norm(p["ln_2"]["scale"], h, eps), arch))


@functools.partial(jax.jit, static_argnames=("arch_items",))
def expert_block(blocks, i, x, forced, arch_items):
    arch = dict(arch_items)
    p = _layer(blocks, i, arch, keep_narrow=("gate_up", "down"))
    eps = arch["layernorm_eps"]
    h = x + attention(p["attn"], lowered(
        rms_norm(p["ln_1"]["scale"], x, eps), arch), arch)
    y, own, select, w = expert_ffn(
        p["mlp"], lowered(rms_norm(p["ln_2"]["scale"], h, eps), arch), arch,
        forced)
    return h + y, own, select, w


@jax.jit
def embed(wte, ids):
    return wte.astype(F32)[ids]


@functools.partial(jax.jit, static_argnames=("eps",))
def head(ln_f, lm_head, x, *, eps):
    return rms_norm(ln_f["scale"].astype(F32), x, eps) \
        @ lm_head["kernel"].astype(F32)


def _frozen(arch):
    return tuple(sorted((k, v) for k, v in arch.items()
                        if isinstance(v, (int, float, str, bool))))


def hidden_states(params, ids, arch, forced=None):
    """ids [seq] -> (x [seq_padded, d] before the final norm, routing): per
    expert layer the reference's own choice [seq_padded, k], its ``s + b``
    [seq_padded, E] and the weights it gave the experts it used. ``forced`` [L_moe, seq, k] forces the
    experts of every position."""
    ids = np.asarray(ids).reshape(-1)
    seq = ids.shape[0]
    padded = -(-seq // SEQ_MULTIPLE) * SEQ_MULTIPLE \
        if seq > Q_BLOCK else -(-seq // Q_BLOCK) * Q_BLOCK
    ids = np.pad(ids, (0, padded - seq))
    items = _frozen(arch)
    kd = arch["first_k_dense"]
    routing = []
    with jax.default_matmul_precision("highest"):
        x = embed(params["wte"]["weight"], jnp.asarray(ids))
        for i in range(kd):
            x = dense_block(params["dense_blocks"], i, x, items)
        for i in range(arch["n_layers"] - kd):
            f = None
            if forced is not None:
                f = np.zeros((padded, arch["moe_top_k"]), np.int32)
                f[:seq] = forced[i][:seq]
                f = jnp.asarray(f)
            x, own, select, w = expert_block(params["blocks"], i, x, f,
                                             items)
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


def routing_margins(routing, served, seq):
    """Where the served choice differs from the reference's own: per expert
    layer and token, how far the reference prefers its own pick over the
    served one, in its own ``s + b``: the largest ``s + b`` among the
    experts it chose and the served path did not, minus the smallest among
    those the served path chose and it did not. 0 where the sets agree.
    ``served`` [L_moe, seq, k]. Returns [L_moe, seq] float32."""
    out = []
    for (own, select, _), srv in zip(routing, served):
        own, select = np.asarray(own)[:seq], np.asarray(select)[:seq]
        srv = np.asarray(srv)[:seq]
        E = select.shape[-1]
        in_own = np.zeros((seq, E), bool)
        in_srv = np.zeros((seq, E), bool)
        np.put_along_axis(in_own, own, True, axis=-1)
        np.put_along_axis(in_srv, srv, True, axis=-1)
        only_own = np.where(in_own & ~in_srv, select, -np.inf).max(-1)
        only_srv = np.where(in_srv & ~in_own, select, np.inf).min(-1)
        out.append(np.where(np.isfinite(only_own), only_own - only_srv, 0.0))
    return np.asarray(out, np.float32)


def weight_errors(routing, served_weights, seq):
    """Relative error of the weights the served path gave its experts
    against the weights the reference gives the same (forced) experts.
    ``served_weights`` [L_moe, seq, k]. Returns [L_moe, seq, k] float32."""
    return np.asarray([
        np.abs(np.asarray(srv)[:seq] - np.asarray(w)[:seq])
        / np.asarray(w)[:seq]
        for (_, _, w), srv in zip(routing, served_weights)], np.float32)
