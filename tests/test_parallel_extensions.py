"""Tensor/pipeline/expert parallelism vs single-device oracles, on the
8-virtual-device CPU mesh (the distributed-in-one-process pattern of
SURVEY.md §4).

Uses ``utils.compat.shard_map`` (not ``jax.shard_map``), as product
code must: the one module that spells the jax name (SPMD101)."""

import numpy as np
import pytest

from bigdl_tpu.utils.compat import shard_map
from tests.oracle import assert_close


def _mesh(n=8, name="model"):
    import jax
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:n]).reshape(n), (name,))


# ---------------------------------------------------------------------------
# tensor parallel
# ---------------------------------------------------------------------------

def test_column_parallel_linear(rng):
    import jax
    from jax.sharding import PartitionSpec as P

    from bigdl_tpu.parallel import column_parallel_linear

    B, IN, OUT = 4, 12, 16
    x = rng.randn(B, IN).astype(np.float32)
    w = rng.randn(OUT, IN).astype(np.float32)
    b = rng.randn(OUT).astype(np.float32)
    mesh = _mesh()

    # unsplit weights; in_specs P("model", None) shards the output rows
    f = jax.jit(shard_map(
        lambda x, ws, bs: column_parallel_linear(x, ws, bs, "model"),
        mesh=mesh, in_specs=(P(), P("model", None), P("model")),
        out_specs=P(None, "model"),
    ))
    out = np.asarray(f(x, w, b))
    assert_close(out, x @ w.T + b, atol=1e-5)


def test_row_parallel_linear(rng):
    import jax
    from jax.sharding import PartitionSpec as P

    from bigdl_tpu.parallel import row_parallel_linear

    B, IN, OUT = 4, 16, 12
    x = rng.randn(B, IN).astype(np.float32)
    w = rng.randn(OUT, IN).astype(np.float32)
    b = rng.randn(OUT).astype(np.float32)
    mesh = _mesh()

    f = jax.jit(shard_map(
        lambda xs, ws, b: row_parallel_linear(xs, ws, b, "model"),
        mesh=mesh,
        # x sharded on features; w sharded on input columns (dim 1)
        in_specs=(P(None, "model"), P(None, "model"), P()),
        out_specs=P(),
    ))
    out = np.asarray(f(x, w, b))
    assert_close(out, x @ w.T + b, atol=1e-5)


def test_tp_mlp_matches_dense(rng):
    import jax
    from jax.sharding import PartitionSpec as P

    from bigdl_tpu.parallel import tp_mlp

    B, D, H = 4, 12, 32
    x = rng.randn(B, D).astype(np.float32)
    w1 = rng.randn(H, D).astype(np.float32)
    b1 = rng.randn(H).astype(np.float32)
    w2 = rng.randn(D, H).astype(np.float32)
    b2 = rng.randn(D).astype(np.float32)
    mesh = _mesh()

    f = jax.jit(shard_map(
        lambda x, w1, b1, w2, b2: tp_mlp(x, w1, b1, w2, b2, "model"),
        mesh=mesh,
        # w1 column-parallel (rows), w2 row-parallel (input columns)
        in_specs=(P(), P("model", None), P("model"), P(None, "model"), P()),
        out_specs=P(),
    ))
    out = np.asarray(f(x, w1, b1, w2, b2))
    want = np.asarray(jax.nn.gelu(x @ w1.T + b1)) @ w2.T + b2
    assert_close(out, want, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_tp_attention_matches_dense(rng, causal):
    import jax
    from jax.sharding import PartitionSpec as P

    from bigdl_tpu.parallel import tp_attention
    from bigdl_tpu.parallel.ring_attention import attention

    B, T, D, HEADS = 2, 6, 32, 8   # 8 heads over 8 chips -> 1 head/chip
    HD = D // HEADS
    x = rng.randn(B, T, D).astype(np.float32)
    wq, wk, wv = (rng.randn(D, D).astype(np.float32) for _ in range(3))
    wo = rng.randn(D, D).astype(np.float32)
    bo = rng.randn(D).astype(np.float32)
    mesh = _mesh()

    f = jax.jit(shard_map(
        lambda x, wq, wk, wv, wo, bo: tp_attention(
            x, wq, wk, wv, wo, "model", n_heads_local=1, causal=causal, bo=bo),
        mesh=mesh,
        in_specs=(P(), P("model", None), P("model", None), P("model", None),
                  P(None, "model"), P()),
        out_specs=P(),
    ))
    out = np.asarray(f(x, wq, wk, wv, wo, bo))

    # dense oracle
    q = (x @ wq.T).reshape(B, T, HEADS, HD)
    k = (x @ wk.T).reshape(B, T, HEADS, HD)
    v = (x @ wv.T).reshape(B, T, HEADS, HD)
    o = np.asarray(attention(q, k, v, causal=causal)).reshape(B, T, D)
    want = o @ wo.T + bo
    assert_close(out, want, atol=1e-4)


# ---------------------------------------------------------------------------
# pipeline parallel
# ---------------------------------------------------------------------------

def _stage_fn(p, x):
    import jax.numpy as jnp

    return jnp.tanh(jnp.matmul(x, p["w"]) + p["b"])


def test_gpipe_matches_sequential(rng):
    import jax
    from jax.sharding import PartitionSpec as P

    from bigdl_tpu.parallel import gpipe, microbatch, stack_stage_params

    n_stages, M, mb, D = 8, 4, 2, 6
    stages = [{"w": rng.randn(D, D).astype(np.float32) * 0.5,
               "b": rng.randn(D).astype(np.float32) * 0.1}
              for _ in range(n_stages)]
    x = rng.randn(M * mb, D).astype(np.float32)
    mesh = _mesh(name="pipe")

    stacked = stack_stage_params(stages)
    f = jax.jit(shard_map(
        lambda p, xm: gpipe(_stage_fn, p, xm, "pipe"),
        mesh=mesh, in_specs=(P("pipe"), P()), out_specs=P(),
    ))
    out = np.asarray(f(stacked, microbatch(x, M))).reshape(M * mb, D)

    want = x
    for p in stages:
        want = np.tanh(want @ p["w"] + p["b"])
    assert_close(out, want, atol=1e-5)


def test_gpipe_gradients_match(rng):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from bigdl_tpu.parallel import gpipe, microbatch, stack_stage_params

    n_stages, M, mb, D = 8, 2, 2, 4
    stages = [{"w": rng.randn(D, D).astype(np.float32) * 0.5,
               "b": rng.randn(D).astype(np.float32) * 0.1}
              for _ in range(n_stages)]
    x = rng.randn(M * mb, D).astype(np.float32)
    mesh = _mesh(name="pipe")
    stacked = stack_stage_params(stages)

    def piped_loss(p, xm):
        inner = shard_map(
            lambda p, xm: gpipe(_stage_fn, p, xm, "pipe"),
            mesh=mesh, in_specs=(P("pipe"), P()), out_specs=P(),
        )
        return jnp.sum(inner(p, xm) ** 2)

    g_pipe = jax.jit(jax.grad(piped_loss))(stacked, microbatch(x, M))

    def seq_loss(p, x):
        out = x
        for i in range(n_stages):
            out = _stage_fn(jax.tree_util.tree_map(lambda l: l[i], p), out)
        return jnp.sum(out ** 2)

    g_seq = jax.jit(jax.grad(seq_loss))(stacked, x)
    for a, b in zip(jax.tree_util.tree_leaves(g_pipe),
                    jax.tree_util.tree_leaves(g_seq)):
        assert_close(np.asarray(a), np.asarray(b), atol=1e-4)


# ---------------------------------------------------------------------------
# expert parallel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("top_k", [1, 2])
def test_moe_matches_dense_oracle(rng, top_k):
    import jax
    from jax.sharding import PartitionSpec as P

    from bigdl_tpu.parallel import mlp_expert, moe_layer

    n_dev, E, T_loc, D, H = 8, 8, 6, 4, 8
    T = n_dev * T_loc
    x = rng.randn(T, D).astype(np.float32)
    router = rng.randn(D, E).astype(np.float32)
    ep = {
        "w1": rng.randn(E, D, H).astype(np.float32) * 0.5,
        "b1": rng.randn(E, H).astype(np.float32) * 0.1,
        "w2": rng.randn(E, H, D).astype(np.float32) * 0.5,
        "b2": rng.randn(E, D).astype(np.float32) * 0.1,
    }
    mesh = _mesh(name="expert")

    f = jax.jit(shard_map(
        lambda x, r, ep: moe_layer(x, r, ep, mlp_expert, "expert",
                                   top_k=top_k, capacity=T_loc),
        mesh=mesh,
        in_specs=(P("expert"), P(), P("expert")),
        out_specs=P("expert"),
    ))
    out = np.asarray(f(x, router, ep))

    # dense oracle: per-token top-k routing, no capacity drops
    logits = x @ router
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.zeros_like(x)
    for t in range(T):
        top = np.argsort(-probs[t])[:top_k]
        gates = probs[t][top] / probs[t][top].sum()
        for g, e in zip(gates, top):
            hh = np.asarray(jax.nn.gelu(x[t] @ ep["w1"][e] + ep["b1"][e]))
            want[t] += g * (hh @ ep["w2"][e] + ep["b2"][e])
    assert_close(out, want, atol=1e-4)


def test_hybrid_dcn_ici_mesh_step():
    """Engine.hybrid_mesh: 2 slices x (2 data x 2 model) on 8 virtual
    devices; model-parallel psum stays intra-slice (ICI axes), gradient
    pmean crosses dcn+data — one full step must match the single-device
    computation."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from bigdl_tpu.utils.engine import Engine

    mesh = Engine.hybrid_mesh(
        ici_axis_names=("data", "model"), ici_axis_sizes=(2, 2),
        num_slices=2)
    assert mesh.shape == {"dcn": 2, "data": 2, "model": 2}

    rng = np.random.default_rng(0)
    w = rng.standard_normal((8, 8)).astype(np.float32)   # rows sharded: model
    x = rng.standard_normal((8, 8)).astype(np.float32)   # batch: dcn*data

    def spmd(w, x):
        # row-parallel matmul: the contraction dim is sharded over 'model',
        # so local products are PARTIAL sums completed by an intra-slice
        # (ICI) psum
        part = jnp.matmul(x, w)
        y = lax.psum(part, "model")
        loss = jnp.mean(y ** 2)
        # gradient-style reduction over the data axes (dcn is one of them)
        return lax.pmean(lax.pmean(loss, "data"), "dcn")

    step = jax.jit(shard_map(
        spmd, mesh=mesh,
        in_specs=(P("model", None), P(("dcn", "data"), "model")),
        out_specs=P()))
    got = float(step(w, x))
    want = float(np.mean((x @ w) ** 2))
    assert abs(got - want) < 1e-4
