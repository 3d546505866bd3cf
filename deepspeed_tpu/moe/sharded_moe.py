"""Mixture-of-Experts: top-k gating with capacity + expert-parallel dispatch.

Which routing forms exist, and who picks: ``TransformerConfig.moe_routing``
is a property of the model (a preset sets it; ``models/transformer.py``
``block_init`` / ``block_apply`` and ``models/latent.py`` read it).
``"capacity"`` is this module: softmax top-k with a per-group capacity and
drops, the reference's semantics (``gpt2_moe``, training with a capacity
factor). ``"dropfree"`` is ``moe/dropfree.py``: sigmoid top-k of the scores
plus a selection bias that picks but does not weigh, weights normalised and
scaled, shared experts, every chosen pair computed through a grouped product
over ragged groups (``kanana2``; beside latent attention only). The
deterministic path below is drop-free only by setting ``capacity = s``,
E / top_k times the products the tokens need.

TPU-native equivalent of the reference's ``deepspeed/moe/sharded_moe.py``:
``TopKGate`` (reference ``:420``), ``top1gating``/``top2gating`` (``:179``/``:277``)
and the ``_AllToAll`` autograd function (``:90``). The reference dispatches tokens
with an explicit ``dist.all_to_all_single`` between expert-parallel ranks; here the
dispatch/combine are einsums in the GShard formulation and XLA's SPMD partitioner
emits the all_to_all when token groups are sharded over ``data`` and the expert
dim over the ``expert`` mesh axis.

Formulation (GShard / Switch):
- tokens keep their [batch, seq] layout; each batch row is a dispatch *group* with
  its own capacity (capacity is per-group, so dispatch is local math — no global
  sort, no dynamic shapes);
- ``dispatch`` [b, s, E, C] (bool) routes token s of group b to slot c of expert e;
  ``combine`` [b, s, E, C] carries the gate weights for the weighted sum back;
- expert compute runs on [E, b, C, m] — sharded (expert, data) — so the
  data->expert resharding before/after is exactly the reference's all_to_all pair;
- the load-balancing aux loss is the Switch/GShard ``E * sum(f_e * p_e)`` term
  (reference ``sharded_moe.py:229``), returned to be added to the model loss.
"""

import dataclasses

import jax
import jax.numpy as jnp

from ..models.layers import Param, normal_init


def _dense_cfg(cfg):
    """Config for the PR-MoE residual branch: the same block geometry with the
    experts turned off (so the dense ``_mlp_init``/``_mlp_apply`` run)."""
    return dataclasses.replace(cfg, n_experts=0)


def expert_capacity(seq_len, n_experts, top_k, capacity_factor, min_capacity=4):
    """Per-group expert capacity (reference ``sharded_moe.py:179`` capacity calc)."""
    cap = int(capacity_factor * seq_len * top_k / n_experts)
    return max(cap, min_capacity)


def top_k_gating(logits, top_k, capacity, *, rng=None, noise_std=0.0,
                 rsample=False, use_rts=False):
    """Top-k gating with per-group capacity.

    Args:
      logits: [b, s, E] router logits (fp32).
      top_k: 1 or 2 (reference supports k in {1, 2}; we allow any k < E).
      capacity: C slots per expert per group.
      rng: optional rng for gating noise (reference's ``noisy_gate_policy``).
      noise_std: stddev of gaussian noise added to logits before top-k.
      rsample: reference 'RSample' policy (``sharded_moe.py:188``): gumbel
        noise on the SELECTION logits only; gate weights stay clean.
      use_rts: Random Token Selection (``sharded_moe.py:220``): the first
        choice's capacity overflow is dropped by random priority instead of
        sequence order, so late-sequence tokens aren't systematically dropped.

    Returns:
      dispatch: [b, s, E, C] bool — token -> (expert, slot) routing.
      combine: [b, s, E, C] float32 — gate weights for the return combine.
      aux_loss: scalar load-balancing loss (Switch: E * sum(f_e * p_e)).
    """
    b, s, E = logits.shape
    logits = logits.astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)  # [b, s, E]

    gauss_rng = gumbel_rng = rts_rng = None
    if rng is not None:
        gauss_rng, gumbel_rng, rts_rng = jax.random.split(rng, 3)
    select_logits = logits
    if noise_std > 0.0 and gauss_rng is not None:
        select_logits = logits + jax.random.normal(gauss_rng, logits.shape) * noise_std
    if rsample and gumbel_rng is not None:
        select_logits = select_logits + jax.random.gumbel(gumbel_rng, logits.shape)

    # iteratively pick k experts per token, masking previous picks
    masked = select_logits
    expert_masks = []   # k x [b, s, E] one-hot
    expert_gates = []   # k x [b, s]
    for _ in range(top_k):
        idx = jnp.argmax(masked, axis=-1)                      # [b, s]
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)     # [b, s, E]
        expert_masks.append(onehot)
        expert_gates.append(jnp.sum(gates * onehot, axis=-1))  # [b, s]
        masked = jnp.where(onehot > 0, -jnp.inf, masked)

    # aux loss from the top-1 assignment (reference top1gating:229 / top2gating:303)
    me = jnp.mean(gates, axis=(0, 1))           # [E] mean router prob
    ce = jnp.mean(expert_masks[0], axis=(0, 1)) # [E] fraction of tokens -> expert
    aux_loss = E * jnp.sum(me * ce)

    # position of each token within its expert's queue, counted across the k
    # choices in priority order (choice 0 gets slots first, as in top2gating where
    # locations2 += sum(mask1))
    dispatch = jnp.zeros((b, s, E, capacity), jnp.bool_)
    combine = jnp.zeros((b, s, E, capacity), jnp.float32)
    prior_counts = jnp.zeros((b, E), jnp.float32)  # slots consumed by higher choices
    denom = jnp.zeros((b, s), jnp.float32)
    kept_masks = []
    for choice, (mask, gate) in enumerate(zip(expert_masks, expert_gates)):
        if use_rts and rts_rng is not None and choice == 0:
            # random priority (reference mask1 * uniform -> _top_idx): rank
            # each token among its expert's tokens by a random draw. Done by
            # sorting into priority order, cumsumming, and scattering back —
            # O(s log s), no [s, s] pairwise matrix.
            r = jax.random.uniform(rts_rng, (b, s))
            perm = jnp.argsort(r, axis=1)                        # priority order
            mask_sorted = jnp.take_along_axis(mask, perm[:, :, None], axis=1)
            pos_sorted = jnp.cumsum(mask_sorted, axis=1) - mask_sorted
            inv = jnp.argsort(perm, axis=1)
            pos_in_expert = jnp.take_along_axis(pos_sorted, inv[:, :, None], axis=1)
        else:
            # cumulative position of this token in expert's queue in its group
            pos_in_expert = jnp.cumsum(mask, axis=1) - mask    # [b, s, E]
        pos = pos_in_expert + prior_counts[:, None, :]
        keep = mask * (pos < capacity)                         # drop overflow tokens
        kept_masks.append((keep, gate))
        prior_counts = prior_counts + jnp.sum(keep, axis=1)
        slot = jnp.sum(pos * keep, axis=-1)                    # [b, s]
        slot_oh = jax.nn.one_hot(slot, capacity, dtype=jnp.float32)  # [b, s, C]
        routed = keep[..., None] * slot_oh[:, :, None, :]      # [b, s, E, C]
        dispatch = dispatch | (routed > 0)
        combine = combine + routed * gate[..., None, None]
        denom = denom + gate * jnp.sum(keep, axis=-1)

    # normalize combine weights over the kept choices (top2gating:321 renormalize)
    combine = combine / jnp.maximum(denom, 1e-9)[..., None, None]
    return dispatch, combine, aux_loss


def moe_mlp_init(rng, cfg):
    """Expert-stacked MLP params: leading "expert" logical axis (sharded over the
    ``expert`` mesh axis) + router. Mirrors the reference's ``Experts`` module
    (``moe/experts.py``) holding E copies of the FFN. For ``swiglu`` models the
    experts are gated — silu(x @ wi_gate) ⊙ (x @ wi) — matching the dense FFN's
    silu(gate) * up convention (models/transformer.py)."""
    E = cfg.n_experts
    k_router, k1, k2, k3, k_res, k_coef = jax.random.split(rng, 6)
    std = cfg.initializer_range
    out_std = std / (2.0 * cfg.n_layers) ** 0.5
    params = {
        "router": {
            "kernel": Param(normal_init(k_router, (cfg.d_model, E), std),
                            ("embed", "expert_logits"))
        },
        "wi": Param(normal_init(k1, (E, cfg.d_model, cfg.d_ff), std),
                    ("expert", "embed", "mlp")),
        "wo": Param(normal_init(k2, (E, cfg.d_ff, cfg.d_model), out_std),
                    ("expert", "mlp", "embed")),
    }
    if cfg.activation == "swiglu":
        params["wi_gate"] = Param(normal_init(k3, (E, cfg.d_model, cfg.d_ff), std),
                                  ("expert", "embed", "mlp"))
    if cfg.moe_use_residual:
        # PR-MoE (reference moe/layer.py:16 use_residual): a dense MLP beside
        # the experts + a learned 2-way blend coefficient
        from ..models.transformer import _mlp_init

        params["res_mlp"] = _mlp_init(k_res, _dense_cfg(cfg))
        params["coef"] = {
            "kernel": Param(normal_init(k_coef, (cfg.d_model, 2), std),
                            ("embed", "coef")),
            "bias": Param(jnp.zeros((2,), jnp.float32), ("coef",)),
        }
    return params


def moe_mlp_apply(cfg, p, x, *, deterministic=True, rng=None):
    """MoE FFN. x: [b, s, m] -> (y [b, s, m], aux_loss scalar).

    The two big einsums below are the all_to_all pair: ``expert_in`` reshards from
    token-sharded (data) to expert-sharded layout and ``y`` back again.
    """
    from ..models import layers as L

    b, s, m = x.shape
    E = cfg.n_experts
    if deterministic:
        # Eval/decode: default to drop-free capacity (C = s covers the worst-case
        # all-tokens-to-one-expert) so KV-cache decode is exactly consistent with
        # the full forward; an explicit eval factor trades memory for drops.
        if cfg.moe_eval_capacity_factor and cfg.moe_eval_capacity_factor > 0:
            capacity = expert_capacity(s, E, cfg.moe_top_k,
                                       cfg.moe_eval_capacity_factor,
                                       cfg.moe_min_capacity)
        else:
            capacity = s
    else:
        capacity = expert_capacity(s, E, cfg.moe_top_k, cfg.moe_capacity_factor,
                                   cfg.moe_min_capacity)

    policy = (cfg.moe_noisy_gate_policy or "").lower()
    gate_in = x.astype(jnp.float32)
    gate_rng = rng
    if policy == "jitter" and not deterministic and rng is not None:
        # reference multiplicative_jitter (sharded_moe.py:49): scale the gate
        # INPUT by uniform(1±eps) — the router sees jittered activations
        jitter_rng, gate_rng = jax.random.split(rng)
        gate_in = gate_in * jax.random.uniform(
            jitter_rng, gate_in.shape, minval=1.0 - 1e-2, maxval=1.0 + 1e-2)
    router_logits = jnp.einsum(
        "bsm,me->bse", gate_in, p["router"]["kernel"].astype(jnp.float32)
    )
    noise = cfg.moe_noise_std if not deterministic else 0.0
    dispatch, combine, aux = top_k_gating(
        router_logits, cfg.moe_top_k, capacity, rng=gate_rng, noise_std=noise,
        rsample=(policy == "rsample" and not deterministic),
        use_rts=(cfg.moe_use_rts and not deterministic),
    )
    dispatch_f = dispatch.astype(x.dtype)
    combine = combine.astype(x.dtype)

    # data-sharded [b,s,..] -> expert-sharded [E,b,C,..]: the all_to_all.
    # Without an explicit constraint XLA is free to keep the [E,b,C,m]
    # intermediates replicated-E / sharded-b (turning the resharding pair into
    # all_reduces); pinning E over ``expert`` and b over ``data`` forces the
    # partitioner to emit the true all_to_all of the reference's ``_AllToAll``
    # autograd fn (``deepspeed/moe/sharded_moe.py:90``).
    expert_in = jnp.einsum("bsec,bsm->ebcm", dispatch_f, x)
    expert_in = _expert_a2a(expert_in, getattr(cfg, "mesh", None), to_expert=True)
    w_i = p["wi"].astype(x.dtype)
    w_o = p["wo"].astype(x.dtype)
    if cfg.activation == "swiglu":
        # same convention as the dense MLP (models/transformer.py): silu on the
        # projection named "gate", elementwise with the ungated up-projection wi
        w_g = p["wi_gate"].astype(x.dtype)
        h = (jax.nn.silu(jnp.einsum("ebcm,emf->ebcf", expert_in, w_g))
             * jnp.einsum("ebcm,emf->ebcf", expert_in, w_i))
    else:
        act = L.ACTIVATIONS[cfg.activation]
        h = act(jnp.einsum("ebcm,emf->ebcf", expert_in, w_i))
    expert_out = jnp.einsum("ebcf,efm->ebcm", h, w_o)
    expert_out = _expert_a2a(expert_out, getattr(cfg, "mesh", None), to_expert=False)
    # expert-sharded -> data-sharded: the return all_to_all
    y = jnp.einsum("bsec,ebcm->bsm", combine, expert_out)
    if cfg.moe_use_residual:
        # PR-MoE blend (reference moe/layer.py:118): out*c0 + dense(x)*c1
        from ..models.transformer import _mlp_apply

        res_p = jax.tree_util.tree_map(
            lambda a: a.astype(x.dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, p["res_mlp"])
        dense = _mlp_apply(_dense_cfg(cfg), res_p, x).astype(x.dtype)
        coef = jax.nn.softmax(
            x.astype(jnp.float32) @ p["coef"]["kernel"].astype(jnp.float32)
            + p["coef"]["bias"].astype(jnp.float32), axis=-1).astype(x.dtype)
        y = y * coef[..., 0:1] + dense * coef[..., 1:]
    return y, aux * cfg.moe_aux_loss_weight


def _expert_a2a(x, mesh, *, to_expert):
    """Force the data<->expert reshard of an [E, b, C, m] intermediate to compile
    to a true all_to_all.

    A single target constraint lets XLA's partitioner fold the reshard into its
    einsum strategy (which it resolves with all-gathers, replicating the E dim —
    O(tokens*E) traffic). Pinning BOTH endpoint layouts makes the reshard an
    explicit tensor-resharding step — the "expert" mesh axis moves between dim 0
    (E) and dim 1 (b) — which the partitioner lowers to the all_to_all of the
    reference's ``_AllToAll`` (``deepspeed/moe/sharded_moe.py:90``). Verified in
    tests/unit/test_moe.py::test_moe_dispatch_emits_all_to_all against HLO.

    No-op when there is no mesh / no expert axis / indivisible shapes — single
    -device tests and dense paths compile unchanged.
    """
    if mesh is None:
        return x
    from ..parallel.topology import DATA_AXIS, EXPERT_AXIS

    P = jax.sharding.PartitionSpec
    ep = mesh.shape.get(EXPERT_AXIS, 1)
    dp = mesh.shape.get(DATA_AXIS, 1)
    E, b = x.shape[0], x.shape[1]
    if ep <= 1 or E % ep or b % (dp * ep):
        return x
    rest = [None] * (x.ndim - 2)
    # tokens-local layout: E replicated, b sharded over the full dp*ep world
    token_spec = P(None, (DATA_AXIS, EXPERT_AXIS), *rest)
    # expert-local layout: E over expert, b over data
    expert_spec = P(EXPERT_AXIS, DATA_AXIS if dp > 1 else None, *rest)
    first, second = ((token_spec, expert_spec) if to_expert
                     else (expert_spec, token_spec))
    x = jax.lax.with_sharding_constraint(x, jax.sharding.NamedSharding(mesh, first))
    return jax.lax.with_sharding_constraint(x, jax.sharding.NamedSharding(mesh, second))
