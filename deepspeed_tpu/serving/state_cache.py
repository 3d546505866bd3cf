"""The per-slot recurrent state of a model with Mamba-2 layers
(``models/hybrid.py``): a third kind of per-request cache beside the K/V
blocks of ``kv_pool.py``, as ONE object the serving engine asks what it
needs, instead of a flag tested wherever the engine touches a cache.

A slot's state is fixed in size and has no block boundary: every Mamba
layer's S (``[heads, head_dim, state]`` float32) and the conv's last inputs
(``[conv - 1, channels]``), for every slot at once, leaves ``[L_mamba,
n_slots, ...]`` of the engine's state beside the pool. The engine asks:

- ``leaves()``: those leaves, zeroed; the decode program hands them to the
  model with the pool and gets them back updated in place (it donates its
  state);
- ``insert(state, dense, slot)``, inside the insert program: the slot's
  state set whole from the request's dense cache, whose chunks scanned the
  prompt from a zeroed state (the admission's reset: ``book_reset``), so
  nothing of the slot's last occupant is read again;
- ``ahead``: which dispatch-ahead the family takes (a decode behind a
  decode-only step's own, a chunk behind a step's decode), and
  ``jobs_only``: every prefill runs as chunk jobs;
- ``refuse(...)``: what it cannot do with the state, by name: the prefix
  cache (a shared block carries no state), speculative verify (no rollback
  of the state), a migration snapshot, an int8 pool, tensor parallelism,
  and on-demand growth (a preemption's replay rebuilds the state from the
  tokens; growth would preempt for room the pool already holds); and
  ``refuse_feature(what)`` where the engine is asked, later, for a feature
  that would hand a slot's state elsewhere (the disaggregated hand-off, a
  live migration);
- ``read(state, slot)``: a slot's state as it stands, for a request that
  asked to keep it when it finishes (``Request.record_state``).

Every prefill of such a model runs as the engine's chunk jobs, whose dense
b=1 cache carries the state from chunk to chunk (``forward_with_cache``
scans from it and writes it back; padding enters neither S nor the conv
tail). ``snapshot()`` is ``ServingMetrics.snapshot()["ssm"]``.
"""

import numpy as np


class NoRecurrentState:
    """The engine's state object for a model without recurrent layers:
    nothing to hold, insert or refuse."""

    names = ()
    ahead = False
    kv_layers = None    # the pool holds every layer's K/V
    jobs_only = False   # a short prompt may prefill in one program
    snapshot = None     # no ``snapshot()["ssm"]``

    def leaves(self):
        return {}

    def insert(self, state, *dense):
        return {}

    def insert_args(self, cache, slot):
        return ()

    def refuse(self, serving_cfg, tp):
        pass

    def refuse_feature(self, what):
        pass

    def read(self, state, slot):
        return None

    def book_reset(self):
        pass

    def book_chunk(self, n, padded):
        pass

    def groups(self, pool_stats, rows_read):
        return {}


class RecurrentState(NoRecurrentState):
    """The state of ``n_slots`` slots of a ``hybrid_pattern`` model."""

    names = ("ssm", "conv")
    ahead = True
    jobs_only = True

    def __init__(self, mcfg, n_slots, dtype, bound_slots):
        from ..models import hybrid

        self.mcfg, self.n_slots, self.dtype = mcfg, n_slots, dtype
        groups = hybrid.layer_groups(mcfg)
        self.layers = len(groups[hybrid.MAMBA])
        # the layers whose K/V the pool holds
        self.kv_layers = len(groups[hybrid.ATTENTION])
        self.bytes_per_slot = hybrid.state_bytes_per_slot(mcfg, dtype)
        self._bound_slots = bound_slots
        # lifetime counters: admissions that started from a zeroed state,
        # real positions the chunked scan took in, padding it kept out
        self.resets = 0
        self.chunk_tokens = 0
        self.pad_tokens = 0
        self._read = None

    def leaves(self):
        from ..models.hybrid import init_state

        return init_state(self.mcfg, self.n_slots, self.dtype)

    def read(self, state, slot):
        """``{leaf: [L_mamba, ...]}``: slot ``slot``'s state as the engine's
        state holds it now (one program for every slot)."""
        import jax

        if self._read is None:
            self._read = jax.jit(lambda leaves, i: {
                name: leaf[:, i] for name, leaf in leaves.items()})
        return self._read({name: state[name] for name in self.names},
                          np.int32(slot))

    def insert(self, state, dense, slot):
        """The slot's state, set whole from a request's dense b=1 cache
        (traced; the slot index too, so one program serves every slot)."""
        return {name: state[name].at[:, slot].set(
            dense[name][:, 0].astype(state[name].dtype))
            for name in self.names}

    def insert_args(self, cache, slot):
        return {name: cache[name] for name in self.names}, np.int32(slot)

    def refuse(self, serving_cfg, tp):
        cfg = serving_cfg
        why = None
        if cfg.kv_pool.prefix_cache:
            why = ("the prefix cache (serving.kv_pool.prefix_cache): a "
                   "shared block carries no recurrent state")
        elif cfg.speculative.enabled:
            why = ("speculative verify (serving.speculative.enabled): the "
                   "recurrent state has no rollback")
        elif cfg.migration.snapshot_interval_tokens > 0:
            why = ("live migration (serving.migration."
                   "snapshot_interval_tokens > 0): a snapshot holds K/V "
                   "blocks, not the recurrent state")
        elif cfg.kv_pool.kv_dtype == "int8":
            why = "an int8 pool (serving.kv_pool.kv_dtype='int8')"
        elif tp > 1:
            why = f"tensor parallel {tp} (tp_size > 1)"
        elif cfg.kv_pool.on_demand_growth:
            why = "on-demand block growth (serving.kv_pool.on_demand_growth)"
        if why is not None:
            self.refuse_feature(why)

    def refuse_feature(self, what):
        raise ValueError(f"ServingEngine: recurrent state (Mamba layers, "
                         f"models/hybrid.py) does not implement {what}")

    def book_reset(self):
        self.resets += 1

    def book_chunk(self, n, padded):
        self.chunk_tokens += n
        self.pad_tokens += padded - n

    def groups(self, pool_stats, rows_read):
        """``snapshot()["kv_pool"]["groups"]``: the attention layers' blocks
        (``rows_read``: the live K/V rows the decode steps read in one
        layer) and the slots' state."""
        return {"groups": {
            "full": {"layers": self.kv_layers,
                     "allocated_blocks": pool_stats["allocated_blocks"],
                     "rows_read_per_layer": rows_read},
            "state": {"layers": self.layers, "slots": self.n_slots,
                      "bytes_per_slot": self.bytes_per_slot,
                      "bytes": self.bytes_per_slot * self.n_slots,
                      "slots_with_state": self._bound_slots()}}}

    def snapshot(self):
        """``ServingMetrics.snapshot()["ssm"]``."""
        return {"state_bytes_per_slot": self.bytes_per_slot,
                "slots_with_state": self._bound_slots(),
                "state_resets": self.resets,
                "chunk_tokens_scanned": self.chunk_tokens,
                "pad_tokens_masked": self.pad_tokens}


def recurrent_state(mcfg, n_slots, dtype, bound_slots):
    """The engine's state object for a model: ``RecurrentState`` where the
    model has Mamba layers, else one that holds nothing."""
    if getattr(mcfg, "hybrid_layers", False):
        return RecurrentState(mcfg, n_slots, dtype, bound_slots)
    return NoRecurrentState()
