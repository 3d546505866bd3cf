"""FLOPs profiler.

Reference: ``deepspeed/profiling/flops_profiler/profiler.py`` monkey-patches
``torch.nn.functional`` (``wrapFunc:738``) and walks module hooks to count
flops/macs/latency per submodule. Under XLA the compiler itself is the source of
truth: ``Compiled.cost_analysis()`` reports exact flops/bytes for the optimized
HLO — no patching, and fusion effects are included. This module provides:

- ``FlopsProfiler``: profile any jittable fn (cost analysis + measured walltime
  -> achieved FLOP/s and utilization);
- ``transformer_train_flops``: the analytic 6*N + attention formula used for MFU
  accounting (matches the profiler's model-level numbers);
- ``get_model_profile``: reference ``get_model_profile`` shape — params/flops/
  latency summary for a model + batch.
"""

import time

import numpy as np
import jax

from ..utils.logging import logger


class FlopsProfiler:
    """Profile a jitted function: XLA-reported flops + measured latency.

    With ``collectives=True`` the compile also captures per-step collective
    wire bytes by kind and payload dtype (``profiling/collectives.py``) — a
    live run then reports wire bytes next to FLOPs. ``collective_trip_count``
    multiplies ops inside ``while`` bodies (pass ``n_layers`` for
    scan-over-layers programs; defaults to 1).
    """

    def __init__(self, fn, collectives=False, collective_trip_count=1):
        self.fn = fn
        self._compiled = None
        self._flops = None
        self._want_collectives = collectives
        self._trip_count = collective_trip_count
        self._collectives = None

    def compile(self, *args, **kwargs):
        lowered = jax.jit(self.fn).lower(*args, **kwargs)
        if self._want_collectives:
            from .collectives import (compile_with_partitioned_hlo,
                                      parse_collectives_by_dtype)

            self._compiled, hlo = compile_with_partitioned_hlo(lowered)
            stats = parse_collectives_by_dtype(
                hlo, jax.device_count(), self._trip_count)
            stats.pop("_loop_body_computations", None)
            self._collectives = stats
        else:
            self._compiled = lowered.compile()
        cost = self._compiled.cost_analysis()
        self._flops = float(cost.get("flops", 0.0)) if cost else 0.0
        self._bytes = float(cost.get("bytes accessed", 0.0)) if cost else 0.0
        return self

    @property
    def flops(self):
        return self._flops

    @property
    def bytes_accessed(self):
        return self._bytes

    @property
    def collective_stats(self):
        """Per-kind wire stats (None unless compiled with collectives=True)."""
        return self._collectives

    @property
    def collective_wire_bytes(self):
        """Total collective wire bytes per chip per step (0 when unknown)."""
        if not self._collectives:
            return 0.0
        return sum(s["wire_bytes"] for s in self._collectives.values())

    def measure(self, *args, n_iters=10, warmup=2, **kwargs):
        """Run the compiled fn; returns dict with flops, latency, achieved FLOP/s."""
        if self._compiled is None:
            self.compile(*args, **kwargs)
        for _ in range(warmup):
            out = self._compiled(*args, **kwargs)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(n_iters):
            out = self._compiled(*args, **kwargs)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / n_iters
        stats = {
            "flops": self._flops,
            "bytes_accessed": self._bytes,
            "latency_s": dt,
            "flops_per_s": self._flops / dt if dt > 0 else 0.0,
        }
        if self._collectives is not None:
            stats["collective_wire_bytes"] = self.collective_wire_bytes
            stats["collectives"] = self._collectives
        return stats


def transformer_train_flops(cfg, batch_size, seq_len, include_backward=True,
                            checkpoint_activations=False):
    """Analytic training flops for one step of a causal transformer.

    The standard accounting (also what the reference's profiler effectively sums):
    forward = 2 * N * tokens matmul flops + attention 2*b*h*s^2*dh*2;
    backward = 2x forward; activation recompute adds another forward.
    """
    tokens = batch_size * seq_len
    n_params = cfg.num_params()
    # embedding lookups are gathers; the LM head matmul is vocab*d per token
    matmul = 2 * n_params * tokens
    attn = 4 * batch_size * cfg.n_heads * (seq_len ** 2) * cfg.head_dim * cfg.n_layers
    fwd = matmul + attn
    mult = 1
    if include_backward:
        mult += 2
    if checkpoint_activations:
        mult += 1
    return fwd * mult


def _fmt(n):
    for unit in ["", "K", "M", "G", "T", "P"]:
        if abs(n) < 1000:
            return f"{n:.2f} {unit}"
        n /= 1000.0
    return f"{n:.2f} E"


def _profile_forward(model, batch, *, loss=False, n_iters=5):
    """Shared scaffold: init params, compile the forward (or loss), measure.
    Returns (params, stats)."""
    import jax.numpy as jnp

    from ..models import split_params_axes

    params, _ = split_params_axes(model.init(jax.random.PRNGKey(0)))
    if loss:
        fn = lambda p: model.loss(p, batch)
    else:
        ids = batch["input_ids"] if isinstance(batch, dict) else batch
        fn = lambda p: model.apply(p, jnp.asarray(ids))
    prof = FlopsProfiler(fn).compile(params)
    return params, prof.measure(params, n_iters=n_iters)


def get_model_profile(model, batch, *, loss=False, n_iters=5, print_profile=True):
    """Profile a model's forward (or loss) on a batch (reference
    ``flops_profiler.get_model_profile``). Returns (flops, macs, params)."""
    params, stats = _profile_forward(model, batch, loss=loss, n_iters=n_iters)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    flops = stats["flops"]
    macs = flops / 2
    if print_profile:
        logger.info(
            f"params: {_fmt(n_params)} | flops: {_fmt(flops)} | macs: {_fmt(macs)} "
            f"| latency: {stats['latency_s'] * 1e3:.2f} ms | "
            f"achieved: {_fmt(stats['flops_per_s'])}FLOP/s"
        )
    return flops, macs, n_params


# ---------------------------------------------------------------------------------
# Per-module breakdown (reference profiler.py:66 print_model_profile: a tree of
# params / MACs / latency per submodule with top-modules aggregation).
#
# The reference collects these with forward hooks on every nn.Module. Under XLA
# the whole model is ONE fused program, so per-module walltime is not separately
# observable from inside it; instead the profile MEASURES prefix programs
# (embedding -> backbone -> full forward) and attributes each stage its
# difference — real wall time, so memory-bound stages (embedding gather, the
# vocab-sized head matmul) no longer inherit GEMM-shaped estimates. Within the
# blocks stage, attn/mlp/ln split the MEASURED blocks time by flops share
# (marked basis="apportioned" — the reference's hook granularity without
# per-op tracing). Params are grouped exactly from the param tree; module rows
# sum to the whole-program totals by construction — pinned by
# tests/unit/test_aux.py.
# ---------------------------------------------------------------------------------


def _measure_stage_latencies(model, params, ids, n_iters, full_ms):
    """Measured wall time for the embedding and backbone prefix programs.

    Returns ``(embed_ms, backbone_ms, full_ms)`` — cumulative, monotone
    (clamped against timer noise). Each prefix is its own jitted program with
    the same shapes, so stage time = difference of adjacent prefixes. The
    full forward is NOT re-measured — ``full_ms`` comes from the
    whole-program measurement the caller already made (re-jitting
    ``model.apply`` here would add a redundant full-size compile).
    """
    import jax.numpy as jnp

    cfg = model.config
    ids = jnp.asarray(ids)

    # token-type injection must mirror what the PROFILED full program does:
    # MaskedLM.apply injects zero segments when type_vocab_size > 0,
    # CausalLM.apply has no token_type path at all — adding the wtt gather to
    # a prefix the full program lacks would overshoot backbone_ms and clamp
    # the head stage to zero
    import inspect

    inject_tt = (getattr(cfg, "type_vocab_size", 0)
                 and "token_type_ids" in inspect.signature(
                     model.apply).parameters)

    def embed_fn(p):
        from ..models import layers as L
        from ..models.transformer import _norm_apply

        x = L.embedding_apply(p["wte"], ids, cfg.compute_dtype)
        s = ids.shape[1]
        if getattr(cfg, "position_embedding", "") == "learned":
            x = x + jnp.take(p["wpe"]["weight"].astype(cfg.compute_dtype),
                             jnp.arange(s), axis=0)[None]
        if inject_tt and "wtt" in p:
            # segment-0 default, matching MaskedLM.apply's injected zeros
            x = x + jnp.take(p["wtt"]["weight"].astype(cfg.compute_dtype),
                             jnp.zeros((s,), jnp.int32), axis=0)[None]
        if getattr(cfg, "embed_layernorm", False) and "ln_emb" in p:
            x = _norm_apply(cfg, p["ln_emb"], x)
        return x

    def backbone_fn(p):
        kw = {"token_type_ids": jnp.zeros_like(ids)} if inject_tt else {}
        return model.backbone(p, ids, **kw)[0]

    out = []
    for fn in (embed_fn, backbone_fn):
        # AOT path (FlopsProfiler), matching how the full program was timed —
        # jit python-dispatch overhead on the prefixes would bias the stage
        # differences on small models
        stats = FlopsProfiler(fn).measure(params, n_iters=n_iters)
        out.append(stats["latency_s"] * 1e3)
    embed_ms, backbone_ms = out
    backbone_ms = max(backbone_ms, embed_ms)
    return embed_ms, backbone_ms, max(full_ms, backbone_ms)


def _module_param_counts(params):
    """Group exact param counts by module path: top-level entries, with the
    stacked ``blocks`` subtree split by submodule (attn/mlp/ln_*)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    counts = {}
    for path, leaf in flat:
        keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        if keys[0] == "blocks" and len(keys) > 1:
            name = f"blocks/{keys[1]}"
        else:
            name = keys[0]
        counts[name] = counts.get(name, 0) + int(np.prod(leaf.shape))
    return counts


def _module_flops(cfg, batch_size, seq_len, param_names=()):
    """Analytic forward flops per module (2*in*out per matmul output element).

    Embedding lookups are gathers (0 MACs, as the reference counts them); the
    LM-head matmul is attributed to ``lm_head`` even when tied to ``wte``.
    ``param_names`` (from the real tree) switches on rows for model variants
    the config alone can't see (MaskedLM's mlm head).
    """
    T = batch_size * seq_len
    d = cfg.d_model
    q_dim = cfg.n_heads * cfg.head_dim
    kv_dim = cfg.kv_heads * cfg.head_dim
    L = cfg.n_layers
    attn_proj = 2 * T * d * (q_dim + 2 * kv_dim) + 2 * T * q_dim * d
    attn_core = 4 * T * seq_len * cfg.n_heads * cfg.head_dim
    if cfg.n_experts > 0:
        # counts what the PROFILED forward executes: model.apply runs
        # deterministic gating, whose default eval capacity is drop-free
        # (C = s), so every expert processes E*b*C slots regardless of top_k
        # (moe/sharded_moe.py moe_mlp_apply)
        E = cfg.n_experts
        if cfg.moe_eval_capacity_factor and cfg.moe_eval_capacity_factor > 0:
            from ..moe.sharded_moe import expert_capacity

            C = expert_capacity(seq_len, E, cfg.moe_top_k,
                                cfg.moe_eval_capacity_factor,
                                cfg.moe_min_capacity)
        else:
            C = seq_len
        slots = batch_size * E * C
        n_expert_matmuls = 3 if cfg.activation == "swiglu" else 2
        mlp = 2 * T * d * E                                  # router
        mlp += n_expert_matmuls * 2 * slots * d * cfg.d_ff   # expert compute
        mlp += 2 * 2 * T * E * C * d                         # dispatch+combine einsums
        if cfg.moe_use_residual:
            n_res_matmuls = 3 if cfg.activation == "swiglu" else 2
            mlp += n_res_matmuls * 2 * T * d * cfg.d_ff + 2 * T * d * 2
    else:
        mlp = 2 * 2 * T * d * cfg.d_ff
        if cfg.activation == "swiglu":
            mlp += 2 * T * d * cfg.d_ff
    norm = 5 * T * d
    flops = {
        "wte": 0.0,
        "blocks/attn": float(L * (attn_proj + attn_core)),
        "blocks/mlp": float(L * mlp),
        "blocks/ln_1": float(L * norm),
        "blocks/ln_2": float(L * norm),
        "lm_head": float(2 * T * d * cfg.vocab_size),
    }
    if getattr(cfg, "position_embedding", "") == "learned":
        flops["wpe"] = 0.0
    if getattr(cfg, "final_layernorm", True):
        flops["ln_f"] = float(norm)
    if "mlm_transform" in param_names:
        # MaskedLM head: dense d->d transform + gelu + LN + output bias add —
        # without these rows the measured head stage would be attributed
        # entirely to lm_head (the only head peer with flops)
        flops["mlm_transform"] = float(2 * T * d * d)
        flops["mlm_ln"] = float(norm)
        flops["mlm_bias"] = float(T * cfg.vocab_size)
    return flops


def get_module_profile(model, batch, *, n_iters=5, print_profile=True):
    """Per-module params/flops/latency breakdown + whole-program totals
    (reference ``print_model_profile`` role).

    Returns ``{"modules": {name: {params, flops, macs, latency_ms, flops_pct}},
    "total": {params, flops, macs, latency_ms, xla_flops}}`` where the module
    flops/params sum EXACTLY to the totals row.
    """
    ids = batch["input_ids"] if isinstance(batch, dict) else batch
    b, s = np.asarray(ids).shape
    params, stats = _profile_forward(model, batch, n_iters=n_iters)
    latency_ms = stats["latency_s"] * 1e3

    param_counts = _module_param_counts(params)
    flops = _module_flops(model.config, b, s, param_names=set(param_counts))
    names = sorted(set(param_counts) | set(flops))
    total_flops = sum(flops.values())

    # measured stage times: embedding, blocks (backbone - embed; ln_f rides
    # here, its flops share is noise), head (full - backbone)
    try:
        embed_ms, backbone_ms, full_ms = _measure_stage_latencies(
            model, params, ids, n_iters, full_ms=latency_ms)
        # stages sum to full_ms; rescale to the canonical whole-program
        # latency so module rows keep summing EXACTLY to the totals row even
        # when timer noise made the clamped full_ms differ from latency_ms
        scale = latency_ms / full_ms if full_ms else 1.0
        stage_ms = {"embed": embed_ms * scale,
                    "blocks": (backbone_ms - embed_ms) * scale,
                    "head": (full_ms - backbone_ms) * scale}
        measured = True
    except Exception as e:  # non-transformer model: flops-share fallback
        logger.warning(f"stage measurement unavailable ({e}); "
                       "falling back to flops-share latency attribution")
        stage_ms = None
        measured = False

    def stage_of(name):
        if name in ("wte", "wpe", "wtt", "ln_emb"):
            return "embed"
        if name.startswith("blocks") or name == "ln_f":
            return "blocks"
        return "head"  # lm_head / mlm_* / pooler

    blocks_flops = sum(f for n, f in flops.items() if stage_of(n) == "blocks")
    modules = {}
    for name in names:
        f = flops.get(name, 0.0)
        share = f / total_flops if total_flops else 0.0
        if stage_ms is None:
            lat, basis = latency_ms * share, "apportioned"
        elif stage_of(name) == "blocks":
            # split the MEASURED blocks stage by flops share
            bshare = f / blocks_flops if blocks_flops else 0.0
            lat, basis = stage_ms["blocks"] * bshare, "apportioned"
        else:
            # embed/head stages: measured; split within the stage by flops
            # first (the tied lm_head owns the head matmul's flops but zero
            # params — param-first weighting would zero the dominant row
            # whenever any peer has params, e.g. MaskedLM's mlm_transform),
            # falling back to params for all-gather stages (wte/wpe: no
            # flops), then to an even split
            stage = stage_of(name)
            peers = [n for n in names if stage_of(n) == stage]
            weights = {n: flops.get(n, 0.0) for n in peers}
            if not any(weights.values()):
                weights = {n: float(param_counts.get(n, 0)) for n in peers}
            if not any(weights.values()):
                weights = {n: 1.0 for n in peers}
            lat = stage_ms[stage] * weights[name] / sum(weights.values())
            basis = "measured-stage"
        modules[name] = {
            "params": param_counts.get(name, 0),
            "flops": f,
            "macs": f / 2,
            "latency_ms": lat,
            "flops_pct": 100.0 * share,
            "basis": basis,
        }
    total = {
        "params": sum(param_counts.values()),
        "flops": total_flops,
        "macs": total_flops / 2,
        "latency_ms": latency_ms,
        "xla_flops": stats["flops"],  # the compiler's own count, for reference
    }
    if measured:
        total["stage_latency_ms"] = {k: round(v, 3)
                                     for k, v in stage_ms.items()}
    if print_profile:
        top = sorted(modules.items(), key=lambda kv: -kv[1]["latency_ms"])
        lines = [f"{'module':<14} {'params':>10} {'flops':>10} {'lat ms':>8} "
                 f"{'%':>6}  basis"]
        for name, m in top:
            lines.append(f"{name:<14} {_fmt(m['params']):>10} {_fmt(m['flops']):>10} "
                         f"{m['latency_ms']:>8.2f} {m['flops_pct']:>5.1f}%  "
                         f"{m['basis']}")
        how = ("stages measured via prefix programs"
               if measured else "latency attributed by flops share")
        lines.append(f"{'TOTAL':<14} {_fmt(total['params']):>10} "
                     f"{_fmt(total['flops']):>10} {latency_ms:>8.2f} {'100.0%':>6} "
                     f"({how}; xla counted {_fmt(total['xla_flops'])}flops)")
        logger.info("\n".join(lines))
    return {"modules": modules, "total": total}
