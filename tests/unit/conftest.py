"""What the serving test files share: the tiny fp32 model, one engine over it
per worker, the sequential-``generate()`` reference, and a virtual-clock
replica. A file keeps only the keyword defaults that differ."""

import contextlib
import logging

import numpy as np
import jax.numpy as jnp
import pytest

import deepspeed_tpu
from deepspeed_tpu.config import ServingConfig
from deepspeed_tpu.models import CausalLM, TransformerConfig
from deepspeed_tpu.serving import Request, ServingEngine, VirtualClock
from deepspeed_tpu.telemetry import SpanTracer

def tiny_cfg(**kw):
    base = dict(vocab_size=64, max_seq_len=64, n_layers=2, n_heads=4,
                d_model=16, d_ff=32, compute_dtype=jnp.float32)
    base.update(kw)
    return TransformerConfig(**base)


def tiny_engine(**cfg_kw):
    return deepspeed_tpu.init_inference(
        CausalLM(tiny_cfg(**cfg_kw)), dtype="float32", max_tokens=64,
        prompt_bucket_size=16)


@pytest.fixture(scope="session")
def engine():
    """One tiny fp32 engine (its weights + generate cache) per worker: under
    ``--dist load`` a module's tests land on every worker, so a narrower scope
    only builds it more often. Each test builds its OWN ServingEngine slot
    pools over it."""
    return tiny_engine()


def make_replica(engine, trace_dir=None, job_name=None, **kw):
    """A virtual-clock, two-slot ServingEngine; with ``trace_dir``, traced on
    its own clock under ``<trace_dir>/<job_name>``."""
    kw.setdefault("virtual_clock", True)
    kw.setdefault("n_slots", 2)
    clock = VirtualClock()
    tracer = None
    if trace_dir is not None:
        tracer = SpanTracer(enabled=True, clock=clock.now,
                            output_path=str(trace_dir), job_name=job_name)
    return ServingEngine(engine, serving_config=ServingConfig(**kw),
                         clock=clock, tracer=tracer)


def make_full_replica(engine, trace_dir=None, job_name=None, **kw):
    """Paged + chunked + migrating: the full recovery / handoff surface."""
    kw.setdefault("chunked_prefill", {"enabled": True, "chunk_size": 8})
    kw.setdefault("kv_pool", {"block_size": 8,
                              "on_demand_growth": True})
    kw.setdefault("migration", {"enabled": True,
                                "snapshot_interval_tokens": 2})
    return make_replica(engine, trace_dir, job_name, **kw)


def make_paged(engine, kv_pool=None, **kw):
    return make_replica(
        engine, kv_pool={"block_size": 16, **(kv_pool or {})},
        **kw)


@contextlib.contextmanager
def unknown_key_warnings():
    """The ``ignoring unknown config key`` warnings of the package's logger
    (it does not propagate, so ``caplog`` sees none) while the block runs."""
    from deepspeed_tpu.utils.logging import logger

    seen = []
    handler = logging.Handler(level=logging.WARNING)
    handler.emit = lambda record: seen.append(record.getMessage()) \
        if "unknown config key" in record.getMessage() else None
    logger.addHandler(handler)
    try:
        yield seen
    finally:
        logger.removeHandler(handler)


# what a configuration written before PR 31 says of the two kv_pool keys the
# program dropped, one warning each
STALE_KV_KEYS = ["KVPoolConfig: ignoring unknown config key "
                 "'attention_backend'",
                 "KVPoolConfig: ignoring unknown config key 'enabled'"]


def ref_tokens(engine, req):
    out = np.asarray(engine.generate(req.prompt[None, :],
                                     max_new_tokens=req.max_new_tokens,
                                     greedy=True))
    return out[0, req.prompt_len:]


def staggered_requests(rng, n, arrival_gap=0.5, max_new=(3, 9), plen=(4, 14)):
    return [Request(
        prompt=rng.randint(0, 64, (int(rng.randint(*plen)),)).astype(np.int32),
        max_new_tokens=int(rng.randint(*max_new)),
        arrival_time=i * arrival_gap) for i in range(n)]
