"""Hybrid stacks of Mamba-2 mixers, latent expert layers and attention layers
(NVIDIA Nemotron-H, ``model_type`` nemotron_h; ``TransformerConfig.
hybrid_pattern``): the parameters, the uncached forward and the cached ones.

Every layer is one mixer, ``x += mixer(RMSNorm(x))``, of the kind its
character in the pattern names; after the last, a final RMS norm and an
untied head. The embedding is not scaled.

- ``M``, a Mamba-2 mixer (Dao and Gu 2024, arXiv:2405.21060): ``[z | xBC |
  dt] = W_in u``; ``xBC = silu(conv1d_causal(xBC))`` (depthwise, kernel
  ``ssm_conv``, with bias); x is ``[heads, head_dim]``, B and C ``[groups,
  state]``, head h reads group ``h // (heads / groups)``; ``dt = softplus(dt
  + dt_bias)``, ``A = -exp(A_log)``; per head ``S <- exp(dt A) S + dt x (x)
  B`` and ``y = S C + D x``; ``y = RMSNorm_by_group(y * silu(z)) * w``;
  ``out = W_out y``. A request's state is S, ``[heads, head_dim, state]`` in
  float32 a layer, and the conv's last ``ssm_conv - 1`` inputs.
- ``E``, a drop-free expert layer (``moe/dropfree.py`` with the LatentMoE
  options: ``relu2`` experts in a ``moe_latent_size`` latent, a full-width
  shared expert).
- ``*``, grouped-query attention without positions (no rotation, no bias,
  scale ``1 / sqrt(head_dim)``): ``window_moe``'s full layer.

The parameters are a LIST of layers (``params["layers"]``), each ``{"norm",
"mixer"}``: the stack is unrolled, so a layer's kind and its index among the
layers of its kind are static wherever a cache is touched, and no layer's
weights are sliced out of a stack (an expert stack sliced for the grouped
product is copied whole: ``moe/dropfree.py``).

Three forwards over one mixer each:

- ``backbone`` (``CausalLM.apply``): the chunked scan (SSD) from a zero
  state, attention in blocks (``window_moe.blockwise_attention``);
- ``forward_with_cache`` (prefill and every chunk of it, over a dense b=1
  cache ``{"k", "v"}`` of the attention layers beside ``{"ssm", "conv"}``
  of the Mamba layers): the scan starts from the cache's state and writes
  its final state back; positions from ``last_index + 1`` on are padding,
  whose ``dt`` is 0 (the state does not move) and which never enter the conv
  tail;
- ``forward_with_paged_cache`` (one decode token a slot): the one-token
  state update of every slot in place, attention over the paged pool by the
  decode kernel or through the view.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import layers as L
from .layers import Param

F32 = jnp.float32
MAMBA, EXPERT, ATTENTION = "M", "E", "*"
# the published initialisation of dt (config keys time_step_min / _max /
# _floor): log-uniform in [min, max], at least floor
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------
def kinds(cfg):
    return tuple(cfg.hybrid_pattern)


def layer_groups(cfg):
    """``{kind: [layer indices]}``; a layer's place in its list is its index
    among the layers of its kind (its row of a cache of that kind)."""
    out = {MAMBA: [], EXPERT: [], ATTENTION: []}
    for i, k in enumerate(kinds(cfg)):
        out[k].append(i)
    return out


def ssm_widths(cfg):
    """``(d_inner, conv_dim, proj)``: the heads' width, the conv's channels
    (x, B and C) and the input projection's outputs (z, xBC, dt)."""
    d_in = cfg.ssm_heads * cfg.ssm_head_dim
    conv = d_in + 2 * cfg.ssm_groups * cfg.ssm_state
    return d_in, conv, d_in + conv + cfg.ssm_heads


def state_geometry(cfg):
    """``{leaf: (shape of one slot in one Mamba layer, dtype)}``: the
    recurrent state S in float32 (it sums every token of a request's life)
    and the conv's last inputs in the compute dtype."""
    _, conv, _ = ssm_widths(cfg)
    return {"ssm": ((cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), F32),
            "conv": ((cfg.ssm_conv - 1, conv), None)}


def state_bytes_per_slot(cfg, dtype):
    """Bytes of one slot's state over every Mamba layer."""
    n = len(layer_groups(cfg)[MAMBA])
    return n * sum(int(np.prod(shape)) * jnp.dtype(dt or dtype).itemsize
                   for shape, dt in state_geometry(cfg).values())


def init_state(cfg, batch, dtype, n_layers=None):
    """Zeroed state leaves ``[Mamba layers, batch, ...]``."""
    n = n_layers if n_layers is not None else len(layer_groups(cfg)[MAMBA])
    return {name: jnp.zeros((n, batch) + shape, dt or dtype)
            for name, (shape, dt) in state_geometry(cfg).items()}


def init_cache(cfg, batch, max_len, dtype=None):
    """The dense cache of a request: K and V of the attention layers
    ``[L_attn, b, max_len, kv_heads, head_dim]`` and the Mamba layers'
    zeroed state."""
    dtype = dtype or cfg.compute_dtype
    n_attn = len(layer_groups(cfg)[ATTENTION])
    cache = {name: jnp.zeros((n_attn, batch, max_len) + row, dtype)
             for name, row in cfg.cache_geometry.items()}
    cache.update(init_state(cfg, batch, dtype))
    return cache


def param_count(cfg):
    """Every parameter the program holds (``init_params``)."""
    from ..moe.dropfree import gated

    d, v = cfg.d_model, cfg.vocab_size
    d_in, conv, proj = ssm_widths(cfg)
    H, G, dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    mamba = d * proj + cfg.ssm_conv * conv + conv + 3 * cfg.ssm_heads \
        + d_in + d_in * d
    attention = d * H * dh + 2 * d * G * dh + H * dh * d
    E, f = cfg.n_experts, cfg.expert_d_ff
    lat = cfg.moe_latent_size or d
    fs = cfg.moe_shared_d_ff or cfg.n_shared_experts * f
    per_expert = 3 if gated(cfg) else 2
    experts = cfg.held_experts[1] * per_expert * lat * f + d * E + E \
        + (2 * d * lat if cfg.moe_latent_size else 0) + per_expert * d * fs
    per = {MAMBA: mamba, EXPERT: experts, ATTENTION: attention}
    return int(sum(per[k] + d for k in kinds(cfg)) + d + 2 * v * d)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def mamba_init(rng, cfg, out_std):
    """The published initialisation: W_in and W_out normal, the conv as a
    depthwise ``Conv1d`` is (uniform in +-1/sqrt(kernel), bias too), ``A =
    1..heads``, ``D = 1``, ``dt_bias`` the inverse softplus of a dt drawn
    log-uniform in [DT_MIN, DT_MAX], the norm's weight 1."""
    d, H, K = cfg.d_model, cfg.ssm_heads, cfg.ssm_conv
    d_in, conv, proj = ssm_widths(cfg)
    k_in, k_w, k_b, k_dt, k_out = jax.random.split(rng, 5)
    std = cfg.initializer_range
    bound = 1.0 / math.sqrt(K)
    dt = jnp.exp(jax.random.uniform(k_dt, (H,), F32)
                 * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    dt = jnp.maximum(dt, DT_FLOOR)
    return {
        "in_proj": L.linear_init(k_in, d, proj, ("embed", "mlp"), False, std),
        "conv": {"kernel": Param(jax.random.uniform(
                     k_w, (K, conv), F32, -bound, bound), (None, "mlp")),
                 "bias": Param(jax.random.uniform(
                     k_b, (conv,), F32, -bound, bound), ("mlp",))},
        "dt_bias": Param(dt + jnp.log(-jnp.expm1(-dt)), (None,)),
        "A_log": Param(jnp.log(jnp.arange(1, H + 1, dtype=F32)), (None,)),
        "D": Param(jnp.ones((H,), F32), (None,)),
        "norm": {"scale": Param(L.ones_init((d_in,)), ("mlp",))},
        "out_proj": L.linear_init(k_out, d_in, d, ("mlp", "embed"), False,
                                  out_std),
    }


def init_params(cfg, rng):
    from ..moe.dropfree import dropfree_moe_init
    from .transformer import _norm_init

    k_emb, k_head, k_layers = jax.random.split(rng, 3)
    std = cfg.initializer_range
    out_std = std / (2.0 * cfg.n_layers) ** 0.5
    layers = []
    for i, kind in enumerate(kinds(cfg)):
        r = jax.random.fold_in(k_layers, i)
        if kind == MAMBA:
            mixer = mamba_init(r, cfg, out_std)
        elif kind == EXPERT:
            mixer = dropfree_moe_init(r, cfg)
        else:
            mixer = L.attention_init(r, cfg.d_model, cfg.n_heads,
                                     cfg.kv_heads, False, std,
                                     out_stddev=out_std,
                                     head_dim=cfg.head_dim)
        layers.append({"norm": _norm_init(cfg), "mixer": mixer})
    return {"wte": L.embedding_init(k_emb, cfg.vocab_size, cfg.d_model, std),
            "layers": layers, "ln_f": _norm_init(cfg),
            "lm_head": L.linear_init(k_head, cfg.d_model, cfg.vocab_size,
                                     ("embed", "vocab"), False, std)}


# ---------------------------------------------------------------------------
# the Mamba-2 mixer
# ---------------------------------------------------------------------------
def _in_proj(p, u):
    """``[z | xBC | dt] = u W_in`` with the products' float32 sums kept:
    dt sets the decay ``exp(dt A)`` of heads whose ``|A|`` reaches 128, where
    a bf16 dt (a relative 2^-9) moves the decay by several percent."""
    return jnp.einsum("...d,dn->...n", u, p["in_proj"]["kernel"].astype(
        u.dtype), preferred_element_type=F32)


def _split_proj(cfg, zxbcdt):
    d_in, conv, _ = ssm_widths(cfg)
    return (zxbcdt[..., :d_in], zxbcdt[..., d_in:d_in + conv],
            zxbcdt[..., d_in + conv:])


def _conv(p, window, dtype):
    """window [..., K + n - 1, C] -> silu(causal depthwise conv) [..., n,
    C] in ``dtype``, summed in float32."""
    w = p["conv"]["kernel"].astype(F32)
    K = w.shape[0]
    n = window.shape[-2] - K + 1
    acc = p["conv"]["bias"].astype(F32)
    for k in range(K):
        acc = acc + jax.lax.slice_in_dim(window, k, k + n, axis=-2) \
            .astype(F32) * w[k]
    return jax.nn.silu(acc).astype(dtype)


def _split_xbc(cfg, xbc):
    d_in = cfg.ssm_heads * cfg.ssm_head_dim
    gn = cfg.ssm_groups * cfg.ssm_state
    lead = xbc.shape[:-1]
    return (xbc[..., :d_in].reshape(lead + (cfg.ssm_heads, cfg.ssm_head_dim)),
            xbc[..., d_in:d_in + gn].reshape(
                lead + (cfg.ssm_groups, cfg.ssm_state)),
            xbc[..., d_in + gn:].reshape(
                lead + (cfg.ssm_groups, cfg.ssm_state)))


def _dt_and_a(p, dt):
    """softplus(dt + dt_bias) [..., H] and A = -exp(A_log) [H], float32."""
    return (jax.nn.softplus(dt.astype(F32) + p["dt_bias"].astype(F32)),
            -jnp.exp(p["A_log"].astype(F32)))


def _gated_out(cfg, p, y, z, dtype):
    """y [..., H, P] float32 (D x added) -> W_out RMSNorm_by_group(y *
    silu(z)) * w."""
    lead = y.shape[:-2]
    G = cfg.ssm_groups
    y = (y.reshape(lead + (-1,)) * jax.nn.silu(z.astype(F32))).reshape(
        lead + (G, -1))
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                          + cfg.layernorm_eps)
    y = y.reshape(lead + (-1,)) * p["norm"]["scale"].astype(F32)
    return L.linear_apply(p["out_proj"], y.astype(dtype))


def ssd_scan(cfg, x, dt, A, B, C, state):
    """The chunked scan (SSD, Mamba-2's ``ssd_minimal``) over blocks of
    ``cfg.ssm_chunk`` positions, float32 throughout. x [b, T, H, P], dt [b,
    T, H], A [H], B and C [b, T, G, N], state [b, H, P, N] (the state before
    position 0; T a multiple of the block). Returns (y [b, T, H, P] without
    the D term, the state after position T - 1)."""
    b, T, H, P = x.shape
    G, N = B.shape[-2:]
    R, Lc = H // G, cfg.ssm_chunk
    c = T // Lc
    hi = jax.lax.Precision.HIGHEST
    xs = (x * dt[..., None]).reshape(b, c, Lc, G, R, P)
    Bc, Cc = B.reshape(b, c, Lc, G, N), C.reshape(b, c, Lc, G, N)
    dA = (dt * A).reshape(b, c, Lc, G, R).transpose(0, 1, 3, 4, 2)
    cum = jnp.cumsum(dA, axis=-1)                          # [b, c, G, R, l]
    # within a block: y_i = sum_{j <= i} C_i.B_j exp(cum_i - cum_j) dt_j x_j
    causal = jnp.tril(jnp.ones((Lc, Lc), bool))
    decay = jnp.exp(jnp.where(causal, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                    # [b,c,G,R,l,s]
    cb = jnp.einsum("bclgn,bcsgn->bcgls", Cc, Bc, precision=hi)
    y = jnp.einsum("bcgls,bcgrls,bcsgrp->bclgrp", cb, decay, xs,
                   precision=hi)
    # each block's own end state, from zero, and its total decay
    to_end = jnp.exp(cum[..., -1:] - cum)                  # [b, c, G, R, l]
    own = jnp.einsum("bclgn,bcgrl,bclgrp->bcgrpn", Bc, to_end, xs,
                     precision=hi)
    total = jnp.exp(cum[..., -1])                          # [b, c, G, R]

    def across(s, inp):
        t, o = inp
        return t[..., None, None] * s + o, s

    final, entering = jax.lax.scan(
        across, state.reshape(b, G, R, P, N),
        (jnp.moveaxis(total, 1, 0), jnp.moveaxis(own, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                # [b,c,G,R,P,N]
    y = y + jnp.einsum("bclgn,bcgrpn,bcgrl->bclgrp", Cc, entering,
                       jnp.exp(cum), precision=hi)
    return y.reshape(b, T, H, P), final.reshape(b, H, P, N)


def mamba_chunk(cfg, p, u, ssm, tail, n_valid):
    """The mixer over a block of positions: u [b, q, d] (normed, compute
    dtype), the state before it (ssm [b, H, P, N] float32, tail [b, K - 1,
    C]), ``n_valid`` (traced) the positions that are real: those from it on
    are padding, whose dt is 0 and whose inputs stay out of the conv tail.
    Returns (out [b, q, d], ssm, tail)."""
    b, q, _ = u.shape
    dtype = u.dtype
    K = cfg.ssm_conv
    z, xbc, dt = _split_proj(cfg, _in_proj(p, u))
    with jax.named_scope("ssm_conv"):
        window = jnp.concatenate([tail.astype(F32), xbc], axis=1)
        xbc = _conv(p, window, F32)
        tail = jax.lax.dynamic_slice_in_dim(window, n_valid, K - 1, axis=1)
    x, B, C = _split_xbc(cfg, xbc)
    dt, A = _dt_and_a(p, dt)
    dt = jnp.where((jnp.arange(q) < n_valid)[None, :, None], dt, 0.0)
    T = -(-q // cfg.ssm_chunk) * cfg.ssm_chunk
    pad = lambda a: jnp.pad(a.astype(F32), [(0, 0), (0, T - q)]
                            + [(0, 0)] * (a.ndim - 2))
    with jax.named_scope("ssm_chunk_scan"):
        y, ssm = ssd_scan(cfg, pad(x), pad(dt), A, pad(B), pad(C), ssm)
    y = y[:, :q] + p["D"].astype(F32)[:, None] * x.astype(F32)
    return _gated_out(cfg, p, y, z, dtype), ssm, tail


def state_update(ssm, dA, dtx, B, C):
    """The one-token recurrence of every slot: ssm [S, H, P, N] float32, dA
    [S, H] (``exp(dt A)``), dtx [S, H, P] (``dt x``), B and C [S, G, N].
    Returns (y [S, H, P] = S_new C, S_new)."""
    S, H, P, N = ssm.shape
    G = B.shape[1]
    st = ssm.reshape(S, G, H // G, P, N)
    new = dA.reshape(S, G, H // G, 1, 1) * st \
        + dtx.reshape(S, G, H // G, P, 1) * B[:, :, None, None, :]
    y = jnp.sum(new * C[:, :, None, None, :], axis=-1)
    return y.reshape(S, H, P), new.reshape(S, H, P, N)


def mamba_decode(cfg, p, u, states, g, tail):
    """The mixer for one token a slot: u [S, 1, d]; ``states`` [L_mamba, S,
    H, P, N], every Mamba layer's (the decode program's donated leaf), of
    which this is layer ``g`` (static); tail [S, K - 1, C]. The recurrence
    runs in ``ops/pallas/ssm_state_update.py`` where ``update_path`` allows
    (it writes the layer's states in place), else in ``state_update``.
    Returns (out [S, 1, d], states, tail)."""
    from ..ops.pallas import ssm_state_update as kernel

    dtype = u.dtype
    with jax.named_scope("ssm_decode"):
        z, xbc, dt = _split_proj(cfg, _in_proj(p, u[:, 0]))
        with jax.named_scope("ssm_conv"):
            window = jnp.concatenate([tail.astype(F32), xbc[:, None]], axis=1)
            xbc = _conv(p, window, F32)[:, 0]
            tail = window[:, 1:]
        x, B, C = _split_xbc(cfg, xbc)
        dt, A = _dt_and_a(p, dt)
        x32 = x.astype(F32)
        args = (jnp.exp(dt * A), dt[..., None] * x32, B.astype(F32),
                C.astype(F32))
        with jax.named_scope("ssm_state_update"):
            if kernel.update_path(cfg.attention_interpret,
                                  cfg.mesh) == "kernel":
                y, states = kernel.ssm_state_update(
                    states, g, *args, interpret=cfg.attention_interpret)
            else:
                y, new = state_update(states[g], *args)
                states = states.at[g].set(new)
        y = y + p["D"].astype(F32)[:, None] * x32
        return _gated_out(cfg, p, y, z, dtype)[:, None], states, tail


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------
def _cast(cfg, p):
    """A layer's weights in the compute dtype; the Mamba scalars (A_log,
    D, dt_bias) and the router are read in float32 where they are used."""
    keep = ("router", "A_log", "D", "dt_bias")
    cast = lambda path, a: a if any(
        getattr(k, "key", None) in keep for k in path) \
        else a.astype(cfg.compute_dtype)
    return jax.tree_util.tree_map_with_path(cast, p)


def _run_layers(cfg, params, x, carry, mamba, attention):
    """Every layer, unrolled: ``mamba(p, h, carry, group) -> (out, carry)``
    and ``attention(p, h, carry, group) -> (out, carry)``, ``group`` the
    layer's static index among its kind. Returns (x, carry, routed [L_moe,
    b, q, 2k])."""
    from ..moe.dropfree import dropfree_moe_apply
    from .transformer import _norm_apply

    seen = {MAMBA: 0, EXPERT: 0, ATTENTION: 0}
    routed = []
    for kind, layer in zip(kinds(cfg), params["layers"]):
        g, seen[kind] = seen[kind], seen[kind] + 1
        h = _norm_apply(cfg, layer["norm"], x)
        p = layer["mixer"]
        if kind == EXPERT:
            out, r = dropfree_moe_apply(cfg, _cast(cfg, p), h)
            routed.append(r)
        elif kind == MAMBA:
            out, carry = mamba(_cast(cfg, p), h, carry, g)
        else:
            out, carry = attention(_cast(cfg, p), h, carry, g)
        x = x + out
    return x, carry, jnp.stack(routed) if routed else None


def _project(cfg, p, h):
    """h [b, q, d] -> q [b, q, H, dh], k and v [b, q, G, dh]."""
    b, q_len, _ = h.shape
    H, G, dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    return (L.linear_apply(p["q"], h).reshape(b, q_len, H, dh),
            L.linear_apply(p["k"], h).reshape(b, q_len, G, dh),
            L.linear_apply(p["v"], h).reshape(b, q_len, G, dh))


def _embed(cfg, params, input_ids):
    return L.embedding_apply(params["wte"], input_ids, cfg.compute_dtype)


def _head(cfg, params, x):
    from .transformer import _norm_apply

    return L.linear_apply(params["lm_head"], _norm_apply(cfg, params["ln_f"],
                                                         x))


def backbone(model, params, input_ids, positions=None):
    """The uncached forward: embedding, layers, final norm -> [b, s, d].
    (No layer reads positions.)"""
    from .transformer import _norm_apply
    from .window_moe import blockwise_attention

    cfg = model.config
    b, s = input_ids.shape
    zero = init_state(cfg, b, cfg.compute_dtype, n_layers=1)

    def mamba(p, h, carry, g):
        out, _, _ = mamba_chunk(cfg, p, h, zero["ssm"][0], zero["conv"][0], s)
        return out, carry

    def attention(p, h, carry, g):
        q, k, v = _project(cfg, p, h)
        read = lambda start, n: (
            jax.lax.dynamic_slice_in_dim(k, start, n, 1),
            jax.lax.dynamic_slice_in_dim(v, start, n, 1))
        out = blockwise_attention(cfg, q, read, s, 0, False)
        return L.linear_apply(p["o"], out), carry

    x, _, _ = _run_layers(cfg, params, _embed(cfg, params, input_ids), None,
                          mamba, attention)
    return _norm_apply(cfg, params["ln_f"], x)


def forward_with_cache(model, params, input_ids, cache, pos, kv_len,
                       last_index=None):
    """``decoding.forward_with_cache`` for this family: the block [b, q] at
    scalar cursor ``pos``; the attention layers write K and V into the dense
    cache (``k`` / ``v`` [L_attn, b, max_len, G, dh]) and attend to rows
    ``[0, pos + q)``; the Mamba layers scan from the cache's state (``ssm``
    / ``conv`` [L_mamba, b, ...]) and write it back. ``last_index``
    (traced): the last real position of the block; those after it are
    padding and leave the state alone, and only that row's logits are
    made. Returns (logits, cache, routed [L_moe, b, q, 2k])."""
    from .window_moe import blockwise_attention

    cfg = model.config
    if jnp.ndim(pos) != 0:
        raise ValueError(
            "hybrid stacks: per-row cursors over a dense cache are not "
            "implemented; decode through the paged pool")
    b, q_len = input_ids.shape
    n_valid = q_len if last_index is None else last_index + 1

    def mamba(p, h, cache, g):
        out, ssm, tail = mamba_chunk(cfg, p, h, cache["ssm"][g],
                                     cache["conv"][g], n_valid)
        return out, dict(cache, ssm=cache["ssm"].at[g].set(ssm),
                         conv=cache["conv"].at[g].set(
                             tail.astype(cache["conv"].dtype)))

    def attention(p, h, cache, g):
        q, k, v = _project(cfg, p, h)
        cache = dict(cache, **{
            name: jax.lax.dynamic_update_slice(
                cache[name], new[None].astype(cache[name].dtype),
                (g, 0, pos, 0, 0))
            for name, new in (("k", k), ("v", v))})

        def read(start, n):
            at = (g, 0, start, 0, 0)
            return tuple(jax.lax.dynamic_slice(
                cache[name], at, (1, b, n) + cache[name].shape[3:])[0]
                for name in ("k", "v"))

        out = blockwise_attention(cfg, q, read, kv_len, pos, False,
                                  kernel=True)
        return L.linear_apply(p["o"], out), cache

    x, cache, routed = _run_layers(cfg, params,
                                   _embed(cfg, params, input_ids),
                                   dict(cache), mamba, attention)
    if last_index is not None:
        x = jax.lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)
    return _head(cfg, params, x), cache, routed


def forward_with_paged_cache(model, params, input_ids, pool, table, pos,
                             block_size, kernel=False):
    """``decoding.forward_with_paged_cache`` for this family: one decode
    step ([S, 1] tokens). ``pool`` holds the attention layers' K/V blocks
    (``k`` / ``v`` [L_attn, n_blocks, bs, G * dh], through ``table`` [S,
    max_len / bs]) and every slot's state (``ssm`` [L_mamba, S, H, P, N]
    float32, ``conv`` [L_mamba, S, K - 1, C]), updated in place: the
    program that calls this donates them. Returns (logits [S, 1, vocab],
    pool, routed [L_moe, S, 1, 2k])."""
    from .decoding import _paged_view, _paged_write_rows
    from .window_moe import view_attention

    cfg = model.config
    S, q_len = input_ids.shape
    if q_len != 1:
        raise ValueError("hybrid stacks: speculative verify (several query "
                         "rows a slot) is not implemented")

    def mamba(p, h, pool, g):
        out, ssm, tail = mamba_decode(cfg, p, h, pool["ssm"], g,
                                      pool["conv"][g])
        return out, dict(pool, ssm=ssm, conv=pool["conv"].at[g].set(
            tail.astype(pool["conv"].dtype)))

    def attention(p, h, pool, g):
        q, k, v = _project(cfg, p, h)
        G = k.shape[2]
        group = {"k": pool["k"], "v": pool["v"]}
        rows = {"k": k[:, 0], "v": v[:, 0]}
        write = lambda group: _paged_write_rows(group, g, rows, table, pos,
                                                block_size)
        if kernel:
            from ..ops.pallas.paged_attention import paged_flash_decode

            with jax.named_scope("full_attn_decode"):
                out = paged_flash_decode(
                    q[:, 0], rows["k"], rows["v"], group["k"], group["v"],
                    table, pos, layer=g, scale=cfg.attn_scale,
                    interpret=cfg.attention_interpret,
                    mesh=cfg.mesh).reshape(S, -1)
            group = write(group)
        else:
            group = write(group)
            with jax.named_scope("full_attn_decode"):
                views = [_paged_view(group, n, g, table, G, q.dtype)
                         for n in ("k", "v")]
                k_pos = jnp.broadcast_to(
                    jnp.arange(views[0].shape[1])[None, :],
                    views[0].shape[:2])
                out = view_attention(cfg, q[:, 0], views[0], views[1],
                                     k_pos, pos, False)
        return L.linear_apply(p["o"], out[:, None]), dict(pool, **group)

    x, pool, routed = _run_layers(cfg, params,
                                  _embed(cfg, params, input_ids), dict(pool),
                                  mamba, attention)
    return _head(cfg, params, x), pool, routed
