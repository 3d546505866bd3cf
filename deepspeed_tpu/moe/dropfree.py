"""Drop-free expert layer: every chosen token-expert pair is computed.

The routing DeepSeek-V3-style checkpoints publish (``TransformerConfig.
moe_routing="dropfree"``), beside the capacity form of ``sharded_moe.py``:

- scores ``s`` in float32: ``sigmoid(W_g u)``;
- a per-expert bias ``b`` (``e_score_correction_bias``: trained, zero at
  init) picks but does not weigh: the ``top_k`` largest of ``s + b`` are
  chosen, their weights are ``s`` of the chosen, normalised over the chosen
  and scaled by ``moe_routed_scale``;
- no capacity and no drops: the token-expert pairs are ordered by expert and
  ONE grouped product over the ragged groups computes gate and up together,
  a second one down; a group may be empty;
- ``n_shared_experts`` always-on experts (one SwiGLU of their joint width)
  are added for every token.
- two static options of Nemotron-H's LatentMoE: an ``activation`` other
  than ``swiglu`` makes every expert (routed and shared) non-gated,
  ``down(act(up u))`` (``relu2``: the square of ReLU);
  ``moe_latent_size`` runs the routed experts in a latent of that width
  between two projections the layer's experts share (``latent_in`` before
  the grouped products, ``latent_out`` after the weighted sum: scope
  ``latent_moe_proj``), while the router and the shared expert
  (``moe_shared_d_ff`` wide) read the full-width input;
- a SHARE of a deployment's experts (``moe_local_experts`` at
  ``moe_expert_offset``: what one chip of an expert-parallel layer holds):
  the router keeps all ``n_experts`` outputs and ``top_k`` a token, the
  weights are normalised over all the chosen, absent ones included, and the
  layer computes the pairs on the experts it holds. The others get no row in
  any group and add nothing; their exchange is another chip's and not here.

The capacity form builds ``[b, s, E, C]`` one-hot tensors; drop-free with it
means ``C = s`` and E / top_k times the products the tokens need. Here the
work is the pairs', and in decode the bytes are those of the experts hit.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..models.layers import (ACTIVATIONS, Param, linear_apply, linear_init,
                             normal_init)

F32 = jnp.float32


def gated(cfg):
    """SwiGLU experts; any other ``activation`` makes them non-gated,
    ``down(act(up u))`` (Nemotron-H's ``relu2``)."""
    return cfg.activation == "swiglu"


def expert_names(cfg):
    """The expert stacks' leaves: the first product's, then ``down``."""
    return ("gate_up" if gated(cfg) else "up"), "down"


def dropfree_moe_init(rng, cfg):
    """``router`` (kernel, and the selection bias: zero, as the published
    init has it) over all E experts, ``gate_up`` [E_held, d, 2f] (gate
    columns first; ``up`` [E_held, d, f] for ``relu2`` experts) and ``down``
    [E_held, f, d] of the experts held here (``cfg.held_experts``: all,
    unless the layer is a share), and ``shared``, one SwiGLU (or ``relu2``
    MLP) of width ``n_shared_experts * f`` or ``moe_shared_d_ff``. With
    ``moe_latent_size`` the experts' ``d`` is the latent's and
    ``latent_in`` [d_model, latent] / ``latent_out`` [latent, d_model] are
    added."""
    E, d, f = cfg.n_experts, cfg.d_model, cfg.expert_d_ff
    held = cfg.held_experts[1]
    k_router, k_gu, k_down, k_shared = jax.random.split(rng, 4)
    std = cfg.initializer_range
    out_std = std / (2.0 * cfg.n_layers) ** 0.5
    lat = cfg.moe_latent_size or d
    first, _ = expert_names(cfg)
    params = {
        "router": {"kernel": Param(normal_init(k_router, (d, E), std),
                                   ("embed", "expert_logits")),
                   "bias": Param(jnp.zeros((E,), F32), ("expert_logits",))},
        first: Param(normal_init(k_gu, (held, lat, (1 + gated(cfg)) * f),
                                 std), ("expert", "embed", "mlp")),
        "down": Param(normal_init(k_down, (held, f, lat), out_std),
                      ("expert", "mlp", "embed")),
    }
    if cfg.moe_latent_size:
        k_in, k_out = jax.random.split(jax.random.fold_in(rng, 5))
        params["latent_in"] = linear_init(k_in, d, lat, ("embed", None),
                                          False, std)
        params["latent_out"] = linear_init(k_out, lat, d, (None, "embed"),
                                           False, std)
    if cfg.n_shared_experts:
        ks = jax.random.split(k_shared, 3)
        fs = cfg.moe_shared_d_ff or cfg.n_shared_experts * f
        params["shared"] = {
            "up": linear_init(ks[1], d, fs, ("embed", "mlp"), False, std),
            "down": linear_init(ks[2], fs, d, ("mlp", "embed"), False,
                                out_std),
        }
        if gated(cfg):
            params["shared"]["gate"] = linear_init(
                ks[0], d, fs, ("embed", "mlp"), False, std)
    return params


def scores_of(p_router, x):
    """x [T, d] -> float32 scores [T, E], whatever x is."""
    return jax.nn.sigmoid(jnp.dot(
        x.astype(F32), p_router["kernel"].astype(F32),
        precision=jax.lax.Precision.HIGHEST))


def choose(cfg, p_router, scores):
    """The ``top_k`` largest of ``scores + b`` (b picks, it does not weigh)."""
    return jax.lax.top_k(scores + p_router["bias"].astype(F32),
                         cfg.moe_top_k)[1].astype(jnp.int32)


def pair_weights(cfg, scores, ids):
    """Weights of the chosen pairs: from the scores, never from scores + b;
    normalised over the chosen, then scaled."""
    w = jnp.take_along_axis(scores, ids, axis=-1)
    return w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) \
        * cfg.moe_routed_scale


def product_path(m, interpret=False, mesh=None):
    """Which implementation ``grouped_product`` takes for ``m`` rows:
    ``"kernel"`` (``ops/pallas/grouped_matmul.py``) or ``"ragged_dot"``.
    The choice is static, from what a program can see when it is traced:
    the platform it is lowered for (a kernel needs a TPU, or ``interpret``:
    the models' ``attention_interpret``), the row count, which must cut
    into the kernel's row tiles, and the mesh (GSPMD cannot partition a
    Mosaic call, and the expert layer has no ``shard_map`` of its own: a
    TPU program over several devices keeps ``ragged_dot``). The serving engine
    books it by dispatch (``snapshot()["moe"]["product_dispatches"]``)."""
    from ..ops.pallas import grouped_matmul, unavailable_reason

    ok = grouped_matmul.row_tile(m) is not None \
        and unavailable_reason(interpret) is None \
        and (interpret or mesh is None or mesh.size == 1)
    return "kernel" if ok else "ragged_dot"


def _ragged_product(rows, w, group_sizes):
    return jax.lax.ragged_dot(
        rows, w, group_sizes, precision=_precision(rows.dtype),
        preferred_element_type=F32).astype(rows.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _kernel_product(rows, w, group_sizes, interpret):
    from ..ops.pallas.grouped_matmul import grouped_matmul

    return grouped_matmul(rows, w, group_sizes, interpret=interpret,
                          precision=_precision(rows.dtype))


def _kernel_product_fwd(rows, w, group_sizes, interpret):
    return _kernel_product(rows, w, group_sizes, interpret), \
        (rows, w, group_sizes)


def _kernel_product_bwd(interpret, saved, g):
    # no backward kernel: the same product's own derivative through
    # ``ragged_dot`` (the generic training path, models/transformer.py)
    rows, w, group_sizes = saved
    _, vjp = jax.vjp(lambda r, w_: _ragged_product(r, w_, group_sizes),
                     rows, w)
    return (*vjp(g), None)


_kernel_product.defvjp(_kernel_product_fwd, _kernel_product_bwd)


def grouped_product(rows, w, group_sizes, interpret=False, mesh=None):
    """``rows`` [M, K], sorted by group, times ``w[g]`` [K, N] for the rows
    of group g (``group_sizes`` [E] int32, summing to M; zeros allowed) ->
    [M, N] in ``rows.dtype``, accumulated in float32 over the whole of K.

    Two implementations of the one product, chosen by ``product_path``.
    Settled on the chip (v5e, PR 36; one layer's two products at the serve
    cells' widths, group sizes as skewed as the cells' routing makes them):
    ``jax.lax.ragged_dot`` takes nearly as long for 512 rows as for 8,192
    (trinity 5.2 and 6.1 ms, kanana 2.3 ms for 384 and 4.7 for 6,144): its
    time follows the groups with rows, not the rows, and is 31-32% of what
    the experts' bytes take at 819 GB/s. The kernel of ``ops/pallas/
    grouped_matmul.py``, row tiles of 128 and the whole of K a step, takes
    3.1 and 2.3 ms for a 1024-token chunk's 8,192 / 6,144 pairs (64-65% of
    that floor), is ahead at every row count the engines' programs have,
    decode's 256 / 192 rows included (3.2 -> 2.0 and 1.8 -> 1.4 ms), and
    still at 512 rows a group (65,536 rows: 4.6 against 9.2 ms), to the
    same bits. So a TPU program takes the kernel wherever ``product_path``
    allows it, and ``ragged_dot`` stays for the CPU, for a program over
    several devices and as the kernel's derivative. The scope names the
    region in the device trace whichever runs."""
    with jax.named_scope("moe_grouped_matmul"):
        w = w.astype(rows.dtype)
        if product_path(rows.shape[0], interpret, mesh) == "kernel":
            return _kernel_product(rows, w, group_sizes, interpret)
        return _ragged_product(rows, w, group_sizes)


def _precision(dtype):
    # a float32 model (the CPU tests, the reference's twin) multiplies in
    # full float32; bf16 inputs are exact in one pass
    return jax.lax.Precision.HIGHEST if dtype == F32 else None


def dropfree_moe_apply(cfg, p, x, ids=None, stacked=None):
    """x [b, s, d] (compute dtype) -> (y [b, s, d], routed [b, s, 2k] int32:
    the k chosen expert ids, then the bits of their k float32 weights; see
    ``routed_ids`` / ``routed_weights``).

    ``ids`` forces the chosen experts (the weights still come from this
    layer's own scores): tests; the served path never passes it.

    ``stacked = (experts, layer)``: the expert weights come not from ``p``
    but from ``experts["gate_up"]`` / ``["down"]`` stacked over the expert
    layers, [L, E, ...], and this is layer ``layer`` (traced) of them. The
    stack is seen as L * E groups of which only this layer's have rows, so
    the grouped product reads the experts hit in place: slicing a layer out
    of the stack first (what a scan over the weights does, the grouped
    product being no fusion's operand) copies every expert of the layer
    every step, 1.2 GB at kanana2's widths, 22 of a 60 ms decode step on
    the chip (PR 29).

    A layer that holds a SHARE of the experts (``cfg.held_experts``): the
    pairs on absent experts sort behind every held one and belong to no
    group, so the groups' sizes sum to the HELD pairs. The row count stays
    the static T * k (a token's k choices may all be held, and nothing may be
    dropped); the grouped kernel's grid is the visits of the groups that own
    rows, so rows past the last group cost it nothing and are left unwritten:
    they are masked here before they are weighed."""
    b, s, d = x.shape
    k, f = cfg.moe_top_k, cfg.expert_d_ff
    lo, E = cfg.held_experts
    flat = x.reshape(b * s, d)
    scores = scores_of(p["router"], flat)
    # the experts' input: the latent of a LatentMoE layer, else x itself
    lat = flat
    if cfg.moe_latent_size:
        with jax.named_scope("latent_moe_proj"):
            lat = linear_apply(p["latent_in"], flat)
    ids = choose(cfg, p["router"], scores) if ids is None \
        else ids.reshape(b * s, k)
    weights = pair_weights(cfg, scores, ids)

    # order the T*k pairs by expert; a stable sort keeps token order inside
    # a group, so the result does not depend on how ties are broken
    pair_expert = ids.reshape(-1)
    share = E != cfg.n_experts
    if share:
        held = (pair_expert >= lo) & (pair_expert < lo + E)
        pair_expert = jnp.where(held, pair_expert - lo, E)
    order = jnp.argsort(pair_expert, stable=True)
    pair_token = order // k
    name, _ = expert_names(cfg)
    if stacked is None:
        gate_up, down, first = p[name], p["down"], 0
    else:
        experts, layer = stacked
        gate_up = experts[name].reshape((-1,) + experts[name].shape[2:])
        down = experts["down"].reshape((-1,) + experts["down"].shape[2:])
        first = layer * E
    group_sizes = jnp.zeros((gate_up.shape[0],), jnp.int32)
    if share:
        group_sizes = group_sizes.at[first + pair_expert].add(
            held.astype(jnp.int32), mode="drop")
    else:
        group_sizes = group_sizes.at[first + pair_expert].add(1)
    rows = lat[pair_token]                                    # [T*k, d]
    how = (cfg.attention_interpret, cfg.mesh)
    h = grouped_product(rows, gate_up, group_sizes, *how)     # [T*k, 2f]
    h = jax.nn.silu(h[:, :f]) * h[:, f:] if gated(cfg) \
        else ACTIVATIONS[cfg.activation](h)
    out = grouped_product(h, down, group_sizes, *how)         # [T*k, d]
    w_sorted = weights.reshape(-1)[order]
    out = out.astype(F32) * w_sorted[:, None]
    if share:
        out = jnp.where(held[order][:, None], out, 0.0)
    # back to token order: pair i of token t sits at row inverse[t * k + i]
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    y = out[inverse].reshape(b * s, k, lat.shape[-1]).sum(axis=1)
    if cfg.moe_latent_size:
        with jax.named_scope("latent_moe_proj"):
            y = linear_apply(p["latent_out"], y.astype(x.dtype)).astype(F32)
    if "shared" in p:
        sp = jax.tree_util.tree_map(lambda a: a.astype(x.dtype), p["shared"])
        if gated(cfg):
            shared = linear_apply(sp["down"], jax.nn.silu(
                linear_apply(sp["gate"], flat)) * linear_apply(sp["up"], flat))
        else:
            shared = linear_apply(sp["down"], ACTIVATIONS[cfg.activation](
                linear_apply(sp["up"], flat)))
        y = y + shared.astype(F32)
    routed = jnp.concatenate(
        [ids, jax.lax.bitcast_convert_type(weights, jnp.int32)], axis=-1)
    return y.astype(x.dtype).reshape(b, s, d), routed.reshape(b, s, 2 * k)


def routed_ids(routed):
    """The expert ids of a ``routed`` array [..., 2k] (numpy or jax)."""
    return routed[..., :routed.shape[-1] // 2]


def routed_weights(routed):
    """The float32 pair weights of a host ``routed`` array [..., 2k]."""
    return np.ascontiguousarray(
        np.asarray(routed)[..., routed.shape[-1] // 2:]).view(np.float32)


def load_counts(ids, n_experts):
    """ids [..., T, k] -> pairs per expert [..., E] int32 (the load counters'
    raw material, computed where the ids are)."""
    flat = ids.reshape(ids.shape[:-2] + (-1,))
    return jnp.sum(jax.nn.one_hot(flat, n_experts, dtype=jnp.int32), axis=-2)
