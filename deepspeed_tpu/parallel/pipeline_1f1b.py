"""1F1B pipeline schedule as one compiled SPMD program.

TPU-native equivalent of the reference's ``TrainSchedule``
(``runtime/pipe/schedule.py:189``): the 1F1B interleave that bounds in-flight
activations to O(stages) instead of O(microbatches). The reference interprets an
instruction list per step, moving tensors with ``dist.send/recv``
(``pipe/engine.py:1273 _INSTRUCTION_MAP``); here the whole schedule — including
the backward passes — is a single ``lax.scan`` over global ticks inside one
``shard_map`` over the ``pipe`` mesh axis.

Why not AD through the GPipe scan (``parallel/pipeline.py``)? Reverse-mode AD
runs ALL forwards before ANY backward, so the saved microbatch activations grow
with M. 1F1B interleaves them, which AD cannot express — so this module computes
gradients *manually* with per-tick ``jax.vjp`` calls:

- schedule: stage ``s`` runs the forward of microbatch ``m`` at tick
  ``F(s,m) = s + 2m`` and its backward at tick ``B(s,m) = 2S-1-s + 2m``.
  Forward ticks have parity ``s mod 2``, backward ticks the opposite parity, so
  a stage never needs both in one tick; producers always run exactly one tick
  before consumers (``F(s,m)+1 = F(s+1,m)``, ``B(s+1,m)+1 = B(s,m)``), so a
  received activation/cotangent is consumed immediately — no queues.
- each tick is ONE ``lax.switch`` over idle / fwd / bwd: XLA conditionals
  execute only the taken arm at runtime, so a tick costs one fwd OR one
  recompute+bwd. The only collectives inside the arms are the manual-TP block's
  model-axis psums, which every device of a TP group meets in the same arm; the
  two ``ppermute`` rotations — activations forward, cotangents backward — run
  unconditionally after the switch, on its outputs (the reference's
  Send/Recv{Activation,Grad} instructions).
- the stage keeps a ring buffer of S saved *stage inputs* (its only residual);
  the backward tick recomputes the stage forward under ``jax.vjp`` — the same
  per-stage recompute the reference gets from activation checkpointing with
  ``checkpoint_interval = layers_per_stage``.
- the loss head (final norm + LM head + CE) runs inside the LAST stage's
  backward tick (``lax.cond(stage == S-1)``), seeding the cotangent chain; the
  first stage's input-cotangents are collected and returned so the embedding
  backward can run outside under plain SPMD AD.
- tied embeddings: the head's ``wte`` grad (last stage) is psum-masked out of
  the pipe region and ADDED to the embedding's ``wte`` grad — the reference's
  tied-weight allreduce (``pipe/module.py:406``) by construction.
"""

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .topology import PIPE_AXIS, DATA_AXIS


def _to_microbatches(a, M, mesh):
    a = a.reshape((M, a.shape[0] // M) + a.shape[1:])
    spec = P(*((None, DATA_AXIS) + (None,) * (a.ndim - 2)))
    return jax.lax.with_sharding_constraint(a, NamedSharding(mesh, spec))


def build_1f1b_train_step(model, mesh, n_microbatches, blocks_param_specs=None):
    """Returns ``train_step(params, batch, scale, rng) -> (loss, grads)`` — the
    1F1B replacement for the engine's ``fwd_bwd`` pass on pipe meshes.

    Tensor parallelism composes by widening the manual region to
    {pipe, model} and running the block in ``tp_manual`` mode (explicit
    row-parallel psums) — the auto partitioner cannot place model-axis
    collectives inside the schedule's stage-varying ``lax.cond`` branches
    (runtime deadlock), so the block writes them itself.
    ``blocks_param_specs``: the engine's PartitionSpec tree for
    ``params['blocks']`` (supplies the model-axis layout of each leaf).
    """
    cfg = model.config
    S = mesh.shape[PIPE_AXIS]
    TP = mesh.shape.get("model", 1)
    M = int(n_microbatches)
    if cfg.n_layers % S:
        raise ValueError(f"n_layers {cfg.n_layers} not divisible by stages {S}")
    L_local = cfg.n_layers // S
    # manual TP only when the caller supplies the model-axis layout; without
    # specs a TP-sized mesh keeps the block weights model-replicated (valid,
    # just unsharded — direct/test callers)
    tp_manual = TP > 1 and blocks_param_specs is not None
    if tp_manual:
        # Every matmul kernel must actually shard over 'model': a replicated
        # kernel (e.g. a TP-indivisible dim fell back in logical_to_physical)
        # would compute the FULL output per rank and the row-parallel psum
        # would then multiply it by TP — silent corruption. All-or-nothing.
        kernel_specs = [
            s for path, s in jax.tree_util.tree_flatten_with_path(
                blocks_param_specs, is_leaf=lambda x: isinstance(x, P))[0]
            if any(getattr(k, "key", None) == "kernel" for k in path)
        ]
        if not kernel_specs or not all("model" in tuple(s) for s in kernel_specs):
            from ..utils.logging import logger

            logger.warning(
                "1F1B x TP: not every block kernel shards over 'model' "
                "(indivisible dims?); keeping weights model-replicated")
            tp_manual = False

    from ..models import layers as Lyr
    from ..models.transformer import block_apply, _norm_apply, _remat_policy

    def pipe_block(p, h, side_mb, rng):
        m = side_mb.get("mask")
        r = ((side_mb["rope_cos"], side_mb["rope_sin"])
             if "rope_cos" in side_mb else side_mb.get("_rope_const"))
        return block_apply(cfg, p, h, mask=m, rope=r,
                           alibi=side_mb.get("_alibi_const"),
                           deterministic=side_mb.get("_det", True),
                           dropout_rng=rng, tp_manual=tp_manual)

    def head_loss(head_w, h, labels_mb):
        x = _norm_apply(cfg, head_w["ln_f"], h)
        return model.head_ce(head_w, x, labels_mb)

    def train_step(params, batch, scale, rng):
        input_ids = batch["input_ids"]
        labels = batch.get("labels")
        if labels is None:
            labels = jnp.concatenate(
                [input_ids[:, 1:], jnp.full_like(input_ids[:, :1], -100)], axis=1)
        B, s = input_ids.shape
        if B % M:
            raise ValueError(f"batch {B} not divisible by microbatches {M}")
        positions = batch.get("position_ids")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (B, s))
        attention_mask = batch.get("attention_mask")

        deterministic = rng is None
        compute_dtype = cfg.compute_dtype

        # ---- side inputs (masks / rope / alibi), same policy as the GPipe path:
        # batched ones ride per-microbatch, static ones are closed over.
        side = {}
        static_side = {"_det": deterministic}
        if attention_mask is not None:
            mask = Lyr.causal_mask(s, s) & attention_mask[:, None, None, :].astype(bool)
            side["mask"] = mask
        if cfg.position_embedding == "rope":
            cos, sin = Lyr.rotary_embedding(
                positions, cfg.rotary_dim or cfg.head_dim, cfg.rope_base)
            side["rope_cos"], side["rope_sin"] = cos, sin
        if cfg.position_embedding == "alibi":
            static_side["_alibi_const"] = Lyr.alibi_bias(cfg.n_heads, s, s)

        side_ms = jax.tree_util.tree_map(lambda a: _to_microbatches(a, M, mesh), side)

        # ---- embedding under vjp (plain SPMD; pipe sees only its output)
        embed_keys = ["wte"] + [k for k in ("wpe", "ln_emb") if k in params]
        embed_w = {k: params[k] for k in embed_keys}

        # NOTE: the microbatch reshape + sharding constraint live OUTSIDE the
        # vjp — constraining the gather output inside it makes XLA's SPMD
        # partitioner take the explicit-batch-dim gather path, which CHECK-fails
        # under tensor parallelism (spmd_partitioner_util.cc gather groups).
        def embed_all(ew):
            x = Lyr.embedding_apply(ew["wte"], input_ids, compute_dtype)
            if cfg.position_embedding == "learned":
                x = x + jnp.take(ew["wpe"]["weight"].astype(compute_dtype),
                                 positions, axis=0)
            if cfg.embed_layernorm:
                x = _norm_apply(cfg, ew["ln_emb"], x)
            # cross the shard_map boundary in f32 (see parallel/pipeline.py)
            return x.astype(jnp.float32)

        x_flat, embed_vjp = jax.vjp(embed_all, embed_w)
        xs = _to_microbatches(x_flat, M, mesh)

        head_keys = ["ln_f"] + (["wte"] if cfg.tie_embeddings else ["lm_head"])
        # Replicate the head weights across the non-pipe axes before entering the
        # manual region: TP-sharded head weights make the auto-axis partitioner
        # insert model-axis collectives inside the stage-varying lax.cond
        # branches, which the runtime cannot rendezvous (deadlock) — and the
        # vocab-sharded label gather CHECK-fails outright.
        head_w = {
            k: jax.tree_util.tree_map(
                lambda a: jax.lax.with_sharding_constraint(
                    a, NamedSharding(mesh, P())), params[k])
            for k in head_keys
        }
        labels_ms = _to_microbatches(labels, M, mesh)
        # Per-microbatch valid-token weights: head_ce returns a mean over each
        # microbatch's OWN valid tokens, so an unweighted sum/M would give
        # sparse microbatches (uneven -100 padding) outsized per-token gradient
        # weight vs the plain full-batch token-mean. Weight each microbatch's
        # loss (and cotangent seed) by its share of the global valid count.
        valid_ms = jnp.sum(labels_ms != -100, axis=(1, 2)).astype(jnp.float32)
        mb_weight = valid_ms / jnp.maximum(jnp.sum(valid_ms), 1.0)  # [M]

        # ---- the compiled 1F1B schedule over the pipe axis
        def pipe_fn(blocks_w, head_w, xs, labels_ms, mb_weight, side_ms):
            stage = jax.lax.axis_index(PIPE_AXIS)
            T = 2 * (M + S - 1)
            mb_shape = xs.shape[1:]  # [mb, s, d]

            def stage_fwd(wb, h, side_mb, mb_idx):
                def body(carry, w_i):
                    h, i, aux = carry
                    rng_i = None
                    if rng is not None:
                        rng_i = jax.random.fold_in(
                            jax.random.fold_in(rng, stage * L_local + i), mb_idx)
                    fn = pipe_block
                    if cfg.remat:
                        fn = jax.checkpoint(fn, policy=_remat_policy(cfg))
                    h, aux_i = fn(w_i, h, dict(side_mb, **static_side), rng_i)
                    return (h, i + 1, aux + aux_i), None

                (h, _, aux), _ = jax.lax.scan(
                    body,
                    (h, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32)),
                    wb)
                return h, aux

            zeros_mb = jnp.zeros(mb_shape, compute_dtype)
            carry0 = {
                "h_recv": zeros_mb,
                "g_recv": jnp.zeros(mb_shape, jnp.float32),
                "buf_h": jnp.zeros((S,) + mb_shape, compute_dtype),
                "buf_side": jax.tree_util.tree_map(
                    lambda a: jnp.zeros((S,) + a.shape[1:], a.dtype), side_ms),
                "gW": jax.tree_util.tree_map(
                    lambda a: jnp.zeros(a.shape, jnp.float32), blocks_w),
                "g_head": jax.tree_util.tree_map(
                    lambda a: jnp.zeros(a.shape, jnp.float32), head_w),
                "gx": jnp.zeros((M,) + mb_shape, jnp.float32),
                "loss": jnp.zeros((), jnp.float32),
                "aux": jnp.zeros((), jnp.float32),
            }

            fwd_perm = [(i, (i + 1) % S) for i in range(S)]
            bwd_perm = [(i, (i - 1) % S) for i in range(S)]
            aux_cot = (scale / M).astype(jnp.float32)

            def tick(carry, t):
                m_f = jnp.clip((t - stage) // 2, 0, M - 1)
                do_f = (t >= stage) & ((t - stage) % 2 == 0) & ((t - stage) // 2 < M)
                boff = 2 * S - 1 - stage
                m_b = jnp.clip((t - boff) // 2, 0, M - 1)
                do_b = (t >= boff) & ((t - boff) % 2 == 0) & ((t - boff) // 2 < M)
                no_g = jnp.zeros(mb_shape, jnp.float32)

                def idle(acc):
                    return zeros_mb, no_g, acc

                # ---- forward tick: run local layers, bank the stage input
                def fwd_case(acc):
                    side_f = jax.tree_util.tree_map(lambda a: a[m_f], side_ms)
                    h_in = jnp.where(stage == 0, xs[m_f].astype(compute_dtype),
                                     carry["h_recv"])
                    h_out, _ = stage_fwd(blocks_w, h_in, side_f, m_f)
                    buf_h = jax.lax.dynamic_update_index_in_dim(
                        acc["buf_h"], h_in, m_f % S, 0)
                    buf_side = jax.tree_util.tree_map(
                        lambda b, v: jax.lax.dynamic_update_index_in_dim(
                            b, v, m_f % S, 0), acc["buf_side"], side_f)
                    return h_out, no_g, dict(acc, buf_h=buf_h, buf_side=buf_side)

                # ---- backward tick: recompute stage fwd under vjp, chain cotangents
                def bwd_case(acc):
                    h_saved = acc["buf_h"][m_b % S]
                    side_b = jax.tree_util.tree_map(
                        lambda b: b[m_b % S], acc["buf_side"])
                    (h2, aux_v), f_vjp = jax.vjp(
                        lambda wb, h: stage_fwd(wb, h, side_b, m_b),
                        blocks_w, h_saved)

                    def head_case(_):
                        ls, h_vjp = jax.vjp(
                            lambda wh, hh: head_loss(wh, hh, labels_ms[m_b]),
                            head_w, h2)
                        w_m = mb_weight[m_b]
                        g_wh, g_h2 = h_vjp((scale * w_m).astype(ls.dtype))
                        return (jax.tree_util.tree_map(
                                    lambda a: a.astype(jnp.float32), g_wh),
                                g_h2.astype(compute_dtype),
                                (ls * w_m).astype(jnp.float32))

                    def mid_case(_):
                        return (jax.tree_util.tree_map(
                                    lambda a: jnp.zeros(a.shape, jnp.float32),
                                    head_w),
                                carry["g_recv"].astype(compute_dtype),
                                jnp.zeros((), jnp.float32))

                    g_wh, g_h2, ls = jax.lax.cond(stage == S - 1, head_case,
                                                  mid_case, None)
                    g_wb, g_h_in = f_vjp((g_h2, aux_cot))
                    g_h_in = g_h_in.astype(jnp.float32)
                    return zeros_mb, g_h_in, dict(
                        acc,
                        gW=jax.tree_util.tree_map(
                            lambda a, b: a + b.astype(jnp.float32),
                            acc["gW"], g_wb),
                        g_head=jax.tree_util.tree_map(
                            jnp.add, acc["g_head"], g_wh),
                        gx=jax.lax.dynamic_update_index_in_dim(
                            acc["gx"], g_h_in, m_b, 0),
                        loss=acc["loss"] + ls, aux=acc["aux"] + aux_v)

                # F and B ticks of a stage have opposite parity, so ONE switch
                # holds both: the arms' model-axis psums (tp_manual) then have
                # one order on every device. As two conds they were independent
                # of each other, and XLA:CPU, which runs whatever thunk is ready
                # with each collective a blocking rendezvous, entered them in
                # different orders on the two devices of a TP pair and hung.
                h_out, g_send, acc = jax.lax.switch(
                    do_f + 2 * do_b, (idle, fwd_case, bwd_case),
                    {k: v for k, v in carry.items()
                     if k not in ("h_recv", "g_recv")})

                # ---- rotate: activations forward, cotangents backward
                h_recv = jax.lax.ppermute(h_out, PIPE_AXIS, fwd_perm)
                g_recv = jax.lax.ppermute(g_send, PIPE_AXIS, bwd_perm)
                return dict(acc, h_recv=h_recv, g_recv=g_recv), None

            carry, _ = jax.lax.scan(tick, carry0, jnp.arange(2 * (M + S - 1)))

            is_last = (stage == S - 1).astype(jnp.float32)
            is_first = (stage == 0).astype(jnp.float32)
            # per-mb losses arrive pre-weighted by valid-token share -> plain sum
            loss = jax.lax.psum(carry["loss"] * is_last, PIPE_AXIS)
            aux = jax.lax.psum(carry["aux"], PIPE_AXIS) / M
            g_head = jax.tree_util.tree_map(
                lambda a: jax.lax.psum(a * is_last, PIPE_AXIS), carry["g_head"])
            gx = jax.lax.psum(carry["gx"] * is_first, PIPE_AXIS)
            return loss, aux, carry["gW"], g_head, gx

        if tp_manual:
            # layers dim over pipe + whatever model-axis layout the engine gave
            # each leaf; axes outside {pipe, model} (e.g. ZeRO's data) stay auto
            manual = (PIPE_AXIS, "model")

            def filt(spec):
                return P(*(a if a in manual else None for a in tuple(spec)))

            blocks_specs = jax.tree_util.tree_map(
                filt, blocks_param_specs, is_leaf=lambda x: isinstance(x, P))
            axis_names = {PIPE_AXIS, "model"}
        else:
            blocks_specs = jax.tree_util.tree_map(lambda _: P(PIPE_AXIS),
                                                  params["blocks"])
            axis_names = {PIPE_AXIS}
        head_specs = jax.tree_util.tree_map(lambda _: P(), head_w)
        side_specs = jax.tree_util.tree_map(lambda _: P(), side_ms)
        # Gather the block weights to exactly their manual-region layout BEFORE
        # entering the schedule: any leftover data-axis (ZeRO-3) sharding would
        # make the auto partitioner emit its all-gathers inside the
        # stage-varying lax.cond branches — a rendezvous deadlock at runtime.
        # (The reference has the same constraint: its pipeline engine composes
        # with ZeRO-1, not ZeRO-3, deepspeed/runtime/pipe/engine.py:61.)
        blocks_in = jax.tree_util.tree_map(
            lambda a, s: jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, s)),
            params["blocks"], blocks_specs)
        sm = jax.shard_map(
            pipe_fn,
            mesh=mesh,
            in_specs=(blocks_specs, head_specs, P(), P(), P(), side_specs),
            out_specs=(P(), P(), blocks_specs, head_specs, P()),
            axis_names=axis_names,
            check_vma=False,
        )
        loss, aux_mean, gW, g_head, gx = sm(
            blocks_in, head_w, xs, labels_ms, mb_weight, side_ms)

        (g_embed,) = embed_vjp(gx.reshape((B,) + gx.shape[2:]))

        grads = dict(g_embed)
        grads["blocks"] = gW
        for k, v in g_head.items():
            grads[k] = jax.tree_util.tree_map(jnp.add, grads[k], v) \
                if k in grads else v
        # grads carry the fp16 scale (cotangent seeds were scale/M); the loss
        # accumulator summed plain per-microbatch CE, so it reports unscaled —
        # the engine's fwd_bwd contract (grads scaled, loss plain).
        return loss + aux_mean, grads

    return train_step
