"""``ops/pallas/grouped_matmul.py`` under the Pallas interpreter against
every group's product computed for every row and masked (the reference of
``tools/kernel_check.py``), and the drop-free expert layer with the kernel
taken against the same layer on ``jax.lax.ragged_dot``: forward and
``jax.grad``. Tiny sizes, CPU.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import get_model, split_params_axes
from deepspeed_tpu.moe import dropfree
from deepspeed_tpu.ops.pallas import grouped_matmul as gm

HIGHEST = jax.lax.Precision.HIGHEST


def masked_reference(rows, w, sizes):
    """Every group's matrix times every row, kept where the row is the
    group's: float64 on the host."""
    rows, w = np.asarray(rows, np.float64), np.asarray(w, np.float64)
    ends = np.cumsum(sizes)
    idx = np.arange(rows.shape[0])
    out = np.zeros((rows.shape[0], w.shape[2]))
    for g in range(w.shape[0]):
        mine = (idx >= ends[g] - sizes[g]) & (idx < ends[g])
        out += np.where(mine[:, None], rows @ w[g], 0.0)
    return out


def skewed_sizes(rng, n_groups, m, empty=3, sigma=0.6):
    """``m`` rows over ``n_groups`` as a cell's routing deals a chunk's
    pairs: a few experts several times the mean, a few with none."""
    p = np.exp(sigma * rng.standard_normal(n_groups))
    p[rng.choice(n_groups, empty, replace=False)] = 0
    return rng.multinomial(m, p / p.sum()).astype(np.int32)


# name -> (sizes, tm): 64 rows in tiles of 16 unless the case says otherwise
SIZES = {
    "uniform": (np.full(8, 8), 16),
    "skewed": (skewed_sizes(np.random.default_rng(0), 16, 64), 16),
    "empty_groups": (np.array([0, 20, 0, 0, 30, 14, 0, 0]), 16),
    "one_group_owns_every_row": (np.array([0, 0, 64, 0]), 16),
    "boundary_inside_a_tile": (np.array([5, 22, 37]), 16),
    "boundaries_on_tile_edges": (np.array([16, 0, 32, 16]), 16),
    "a_group_longer_than_three_tiles": (np.array([3, 55, 6]), 16),
    "one_row_tile": (np.array([9, 0, 40, 15]), 64),
    "more_groups_than_rows": (np.bincount(
        np.random.default_rng(1).integers(0, 96, 32), minlength=96), 8),
}


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 1e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(SIZES))
def test_kernel_against_every_group_masked(case, dtype, tol):
    sizes, tm = SIZES[case]
    sizes = np.asarray(sizes, np.int32)
    m, k, n = int(sizes.sum()), 32, 256
    rng = np.random.default_rng(3)
    rows = jnp.asarray(rng.standard_normal((m, k)), dtype)
    w = jnp.asarray(rng.standard_normal((len(sizes), k, n)) * 0.2, dtype)
    got = jax.jit(lambda r, w_, s: gm.grouped_matmul(
        r, w_, s, tiles=(tm, 128), interpret=True,
        precision=HIGHEST if dtype == jnp.float32 else None))(
            rows, w, jnp.asarray(sizes))
    assert got.dtype == dtype and got.shape == (m, n)
    want = masked_reference(rows, w, sizes)
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               atol=tol * np.abs(want).max())


@pytest.mark.parametrize("k,n", [(64, 128), (32, 256)],
                         ids=["gate_and_up", "down"])
@pytest.mark.parametrize("layer", [0, 2])
def test_stack_read_in_place_at_a_traced_layer(layer, k, n):
    """The ``stacked=`` form: L x E groups, only layer ``layer``'s (a traced
    offset) own rows; the tiles are ``choose_tiles``' own."""
    L, E, m = 3, 8, 48
    rng = np.random.default_rng(4)
    sizes = skewed_sizes(rng, E, m, empty=2)
    rows = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    stack = jnp.asarray(rng.standard_normal((L, E, k, n)) * 0.2, jnp.float32)

    def f(rows, stack, sizes, layer):
        all_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((L * E,), jnp.int32), sizes, (layer * E,))
        return gm.grouped_matmul(rows, stack.reshape(L * E, k, n), all_sizes,
                                 interpret=True, precision=HIGHEST)

    got = jax.jit(f)(rows, stack, jnp.asarray(sizes), jnp.int32(layer))
    want = masked_reference(rows, stack[layer], sizes)
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               atol=2e-6 * np.abs(want).max())


def test_visits_are_the_tiles_each_group_touches():
    sizes = np.array([0, 5, 0, 22, 37, 0, 0], np.int32)  # 64 rows, tiles of 16
    offsets, group, tile, next_live, n = jax.jit(
        lambda s: gm.group_visits(s, 64, 16))(jnp.asarray(sizes))
    n = int(n)
    assert list(np.asarray(offsets)) == [0, 0, 5, 5, 27, 64, 64, 64]
    assert list(zip(np.asarray(group)[:n], np.asarray(tile)[:n])) == [
        (1, 0), (3, 0), (3, 1), (4, 1), (4, 2), (4, 3)]
    assert len(group) == 64 // 16 + 7 - 1 and n == 6
    # the order the weights are wanted in: 1 -> 3 -> 4 -> (next column) 1
    live = np.asarray(next_live)
    assert (live[1], live[3], live[4]) == (3, 4, 1)


@pytest.mark.parametrize("m,k,n,itemsize,want", [
    (8192, 2048, 2048, 2, (128, 2048)),    # trinity chunk, gate and up
    (8192, 1024, 2048, 2, (128, 2048)),    # ... down
    (6144, 2048, 1536, 2, (128, 1536)),    # kanana chunk, gate and up
    (6144, 768, 2048, 2, (128, 2048)),
    (192, 2048, 1536, 2, (64, 1536)),      # kanana decode
    (256, 2048, 2048, 2, (128, 2048)),     # trinity decode
    (8192, 8192, 4096, 2, (128, 512)),     # a block held to 8 MB
    (48, 64, 64, 4, (16, 64)),             # a tiny model under the interpreter
    (100, 64, 128, 4, (None, 128)),        # no row tile divides 100
])
def test_tiles_follow_the_shapes(m, k, n, itemsize, want):
    assert gm.choose_tiles(m, k, n, itemsize) == want


def test_path_follows_platform_rows_and_mesh():
    from jax.sharding import Mesh

    from deepspeed_tpu.ops.pallas import lowering_target

    assert dropfree.product_path(8192) == "ragged_dot"     # a CPU
    assert dropfree.product_path(8192, interpret=True) == "kernel"
    assert dropfree.product_path(100, interpret=True) == "ragged_dot"
    one = Mesh(np.array(jax.devices()[:1]), ("model",))
    two = Mesh(np.array(jax.devices()[:2]), ("model",))
    with lowering_target("tpu"):
        assert dropfree.product_path(8192) == "kernel"
        assert dropfree.product_path(192, mesh=one) == "kernel"
        assert dropfree.product_path(8192, mesh=two) == "ragged_dot"
    assert dropfree.product_path(8192, interpret=True, mesh=two) == "kernel"


@pytest.fixture(scope="module")
def layer():
    """One expert layer of ``test_latent_moe.py``'s tiny model."""
    model = get_model("kanana2", "tiny", compute_dtype=jnp.float32)
    params, _ = split_params_axes(model.init(jax.random.PRNGKey(0)))
    p = jax.tree_util.tree_map(lambda a: a[0].astype(jnp.float32),
                               params["blocks"]["mlp"])
    x = jnp.asarray(np.random.default_rng(2).normal(
        size=(1, 24, model.config.d_model)), jnp.float32)
    return model.config, p, x


def test_expert_layer_on_the_kernel_equals_ragged_dot(layer):
    cfg, p, x = layer
    on = dataclasses.replace(cfg, attention_interpret=True)
    with jax.default_matmul_precision("highest"):
        want, routed = jax.jit(
            lambda p, x: dropfree.dropfree_moe_apply(cfg, p, x))(p, x)
        traced = jax.make_jaxpr(
            lambda p, x: dropfree.dropfree_moe_apply(on, p, x))(p, x)
        got, routed_k = jax.jit(
            lambda p, x: dropfree.dropfree_moe_apply(on, p, x))(p, x)
    assert "pallas_call" in str(traced) and "ragged_dot" not in str(traced)
    assert (np.asarray(routed) == np.asarray(routed_k)).all()
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_expert_layer_on_the_kernel_has_ragged_dots_gradient(layer):
    cfg, p, x = layer
    on = dataclasses.replace(cfg, attention_interpret=True)
    ids = dropfree.routed_ids(dropfree.dropfree_moe_apply(cfg, p, x)[1])

    def loss(cfg):
        # the chosen experts held fixed: the gradient is the products' own
        return lambda p, x: jnp.sum(jnp.sin(
            dropfree.dropfree_moe_apply(cfg, p, x, ids=ids)[0]))

    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(loss(cfg), argnums=(0, 1)))(p, x)
        got = jax.jit(jax.grad(loss(on), argnums=(0, 1)))(p, x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.abs(np.asarray(w)).max() > 0 or g.shape == (8,)
        np.testing.assert_allclose(g, w, atol=2e-6)


@pytest.mark.parametrize("path", ["ragged_dot", "kernel"])
def test_engine_books_every_dispatch_by_its_product_path(path):
    """``snapshot()["moe"]["product_dispatches"]``: every expert layer of
    every program dispatched (chunks and decode steps), under the product its
    program was traced with; the served tokens are the model's own either
    way."""
    import deepspeed_tpu
    from deepspeed_tpu.serving import Request, RequestState

    eng = deepspeed_tpu.init_inference(
        get_model("kanana2", "tiny", attention_interpret=path == "kernel"),
        max_tokens=256, seed=3, dtype="float32", prompt_bucket_size=16,
        prompt_bucket_policy="pow2",
        serving={"n_slots": 4, "max_len": 256,
                 "chunked_prefill": {"enabled": True, "chunk_size": 32},
                 "kv_pool": {"block_size": 16}})
    sv = eng.serving
    rng = np.random.default_rng(0)
    reqs = [sv.submit(Request(prompt=rng.integers(0, 512, n, dtype=np.int32),
                              max_new_tokens=4)) for n in (70, 20)]
    while any(r.state is not RequestState.FINISHED for r in reqs):
        sv.step()
    moe = sv.metrics.snapshot()["moe"]
    other = {"kernel": "ragged_dot", "ragged_dot": "kernel"}[path]
    assert moe["dispatches"] > moe["decode_dispatches"] > 0
    assert moe["product_dispatches"] == {path: moe["dispatches"], other: 0}
    apply = jax.jit(eng.module.apply)
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        want = np.asarray(apply(eng.params, jnp.asarray(seq[None])))[
            0, r.prompt_len - 1:].argmax(-1)
        assert (want == np.asarray(r.tokens)).all()
    eng.destroy()
