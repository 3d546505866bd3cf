"""The paged decode kernel's latent form (``ops/pallas/paged_attention.py:
paged_latent_decode``) under the Pallas interpreter, against the absorbed
attention of ``models/latent.py`` over the view gathered through the block
table: cursors at and around a block's and a chunk's edges, slots at cursor
0 beside live ones, unbound table columns on a POISONED garbage block (any
read of it explodes the output), several layers read at a traced index, in
float32 and bf16. And the kernel's other forms trace the programs they
traced before the latent form existed (digests of their jaxprs).
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import get_model, latent, split_params_axes
from deepspeed_tpu.ops.pallas.paged_attention import (paged_flash_decode,
                                                       paged_latent_decode)

BS, COLS, N_BLOCKS, LAYERS = 16, 12, 40, 3
# a chunk of 2 blocks (32 rows) where ``chunk_tokens`` is 32
CURSORS = {
    "block_edges": [0, 1, 127, 128, 129, 15],
    "chunk_edges": [31, 32, 33, 0, 64, 0],
    "idle_beside_live": [0, 0, 100, 0, 0, 7],
}


def _fixture(cursors, seed=0):
    """A tiny kanana2 attention layer's weights and a latent pool of
    ``LAYERS`` layers, each slot bound to the blocks its cursor needs."""
    model = get_model("kanana2", "tiny")
    cfg = latent.dense_cfg(model.config)
    params, _ = split_params_axes(model.init(jax.random.PRNGKey(seed)))
    p = jax.tree_util.tree_map(lambda a: a[0], params["dense_blocks"]["attn"])
    H, r, dr = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    rng = np.random.RandomState(seed)
    pos = np.asarray(CURSORS[cursors], np.int32)
    S = len(pos)
    kc = rng.randn(LAYERS, N_BLOCKS, BS, 1, r).astype(np.float32)
    krc = rng.randn(LAYERS, N_BLOCKS, BS, 1, dr).astype(np.float32)
    kc[:, 0], krc[:, 0] = 1e4, 1e4           # the garbage block
    free = list(1 + rng.permutation(N_BLOCKS - 1))
    table = np.zeros((S, COLS), np.int32)
    for s in range(S):
        need = pos[s] // BS + 1 if pos[s] else 0
        table[s, :need] = [free.pop() for _ in range(need)]
    h = rng.randn(S, 1, cfg.d_model).astype(np.float32)
    q_nope, q_rope, c, k_rope = latent.project(
        cfg, p, jnp.asarray(h),
        latent.rope_tables(cfg, jnp.asarray(pos)[:, None]))
    return (cfg, p, q_nope[:, 0], q_rope[:, 0], c[:, 0], k_rope[:, 0], kc,
            krc, table, pos)


def _view(cfg, p, q_nope, q_rope, c, k_rope, kc, krc, table, pos, layer,
          dtype):
    """The view path: each slot's rows gathered through its table, the
    fresh row written at the cursor, ``absorbed_attention`` over them."""
    S = len(pos)

    def rows(leaf, new):
        view = jnp.asarray(leaf[layer])[jnp.asarray(table)][:, :, :, 0]
        view = view.reshape(S, COLS * BS, -1).astype(dtype)
        return jax.vmap(lambda v, n, at: jax.lax.dynamic_update_slice(
            v, n[None], (at, 0)))(view, new.astype(dtype), jnp.asarray(pos))

    cast = lambda t: jax.tree_util.tree_map(lambda a: a.astype(dtype), t)
    return latent.absorbed_attention(
        cfg, cast(p), q_nope.astype(dtype), q_rope.astype(dtype),
        rows(kc, c), rows(krc, k_rope), jnp.asarray(pos))


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("chunk_tokens", [16, 32, None])
@pytest.mark.parametrize("cursors", sorted(CURSORS))
def test_latent_form_matches_the_absorbed_view(cursors, chunk_tokens, dtype,
                                               rtol):
    """Every layer of a 3-layer pool, read at a traced index, through the
    kernel against the view; a chunk of one block, of two and as the bytes
    size it."""
    cfg, p, q_nope, q_rope, c, k_rope, kc, krc, table, pos = \
        _fixture(cursors)
    cast = lambda a: jnp.asarray(a, dtype)
    pc = jax.tree_util.tree_map(cast, p)

    @jax.jit
    def kernel(layer):
        attend = lambda q_lat: paged_latent_decode(
            q_lat, cast(q_rope), cast(c), cast(k_rope), cast(kc), cast(krc),
            jnp.asarray(table), jnp.asarray(pos), layer=layer,
            scale=latent.score_scale(cfg), chunk_tokens=chunk_tokens,
            interpret=True)
        return latent._absorbed(cfg, pc, cast(q_nope), attend)

    with jax.default_matmul_precision("highest"):
        for layer in range(LAYERS):
            want = _view(cfg, p, q_nope, q_rope, c, k_rope,
                         np.asarray(cast(kc).astype(jnp.float32)),
                         np.asarray(cast(krc).astype(jnp.float32)), table,
                         pos, layer, dtype)
            got = kernel(jnp.int32(layer))
            got, want = (np.asarray(a, np.float32) for a in (got, want))
            assert np.isfinite(got).all()
            assert np.abs(got - want).max() <= rtol * np.abs(want).max()


def test_a_slot_at_cursor_zero_attends_its_own_row_alone():
    """Nothing of the pool is read below cursor 0: ``o_lat`` is the fresh
    latent row itself, whatever its table row and the pool hold."""
    cfg, p, q_nope, q_rope, c, k_rope, kc, krc, table, pos = \
        _fixture("block_edges")
    q_lat = jnp.einsum("shd,rhd->shr", q_nope,
                       latent._kv_b(cfg, p)[..., :cfg.qk_nope_head_dim])
    zero = np.zeros_like(pos)
    out = paged_latent_decode(q_lat, q_rope, c, k_rope, jnp.asarray(kc),
                              jnp.asarray(krc), jnp.asarray(table),
                              jnp.asarray(zero), layer=jnp.int32(1),
                              scale=latent.score_scale(cfg), interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.broadcast_to(np.asarray(c)[:, None], out.shape),
        atol=1e-6)


def _other_forms():
    """The kernel's forms before the latent one, at small shapes: the OPT
    class (32 heads of 64), GQA (32 over 8 of 128), a band over a ring
    (32 over 4, window 40), and K rows wider than V rows with a sink (64
    over 8, K 192 / V 128, window 24)."""
    rng = np.random.RandomState(0)

    def operands(nh, kvh, dh, dv, n_blocks, cols, bs=16, slots=4):
        a = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.bfloat16)
        return (a(slots, nh, dh), a(slots, kvh, dh), a(slots, kvh, dv),
                a(2, n_blocks, bs, kvh * dh), a(2, n_blocks, bs, kvh * dv),
                jnp.zeros((slots, cols), jnp.int32),
                jnp.zeros((slots,), jnp.int32), jnp.int32(1))

    sink = jnp.zeros((64,), jnp.float32)
    return {
        "opt": ({}, operands(32, 32, 64, 64, 33, 8)),
        "gqa": ({}, operands(32, 8, 128, 128, 33, 8)),
        "band": (dict(window=40, ring=True),
                 operands(32, 4, 128, 128, 17, 4)),
        "sink": (dict(window=24, ring=True, sink=sink),
                 operands(64, 8, 192, 128, 13, 3)),
    }


# sha256 of each form's jaxpr, traced before the latent form existed
OTHER_FORMS = {
    "opt": "37ae3e77560f09330fb0a48b8126df046e52b4c91530cf7a9707be2ace50248d",
    "gqa": "e3b93976f6f3d1cfe39472c576eda841fb294f5832c10a327b24da9d3e45c10c",
    "band": "0166656a9727bd26510f93bfd5692c15ef91be7e2b9c4cc0949f036fcf1755db",
    "sink": "3f7b359fc9f03e05d304dee5f7ec73f88b36191b3fb5e1c41224af4647bafdeb",
}


@pytest.mark.parametrize("form", sorted(OTHER_FORMS))
def test_the_other_forms_trace_the_programs_they_had(form):
    """The latent form is static and absent from these calls: their jaxprs
    (the kernel's body included) are the ones the kernel traced before it
    existed, digest for digest."""
    kw, args = _other_forms()[form]
    text = str(jax.make_jaxpr(lambda q, kn, vn, kc, vc, t, pos, layer:
                              paged_flash_decode(q, kn, vn, kc, vc, t, pos,
                                                 layer=layer, **kw))(*args))
    assert hashlib.sha256(text.encode()).hexdigest() == OTHER_FORMS[form]
