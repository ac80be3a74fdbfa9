"""The pooled decode step, compiled for a v5e without one: the program
that holds the K/V pool every token must not re-lay it out.

The pool is stored ``(n_slots, max_len, heads*head_dim)``. Stored 4-D
(``(…, heads, 64)``), the device lays it out ``max_len``-minor, the row
scatter wants it the other way, and the compiled step carries two
pool-sized ``copy`` instructions per tensor per token (PERF.md, PR 27:
30 ms of a 44.5 ms step at GPT-2-medium). Nothing on the CPU shows
that: the local libtpu compiles the real step for a described
``v5e:2x2`` (XLA:TPU and Mosaic, no device), and the test reads the
compiled HLO. On the chip every pooled decode program reads the pool
through ``pooled_decode_attention``; on this host the dispatch probe
would pick the whole-window jnp sums, so the tests turn it."""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu.models import TransformerLM
from bigdl_tpu.models.transformer import (
    make_batch_decode_step, serving_params,
)
from bigdl_tpu.serving.sampling import make_knob_rows

N_SLOTS, MAX_LEN, HEADS, HD, VOCAB = 8, 256, 2, 64, 512
POOL_ELEMS = N_SLOTS * MAX_LEN * HEADS * HD


@pytest.fixture(scope="module")
def v5e_devices():
    from jax.experimental import topologies

    # what libtpu reads when it is loaded with no chip behind it
    with pytest.MonkeyPatch.context() as mp:
        for key, val in (("TPU_ACCELERATOR_TYPE", "v5litepod-4"),
                         ("TPU_SKIP_MDS_QUERY", "1"),
                         ("TPU_WORKER_HOSTNAMES", "localhost")):
            if key not in os.environ:
                mp.setenv(key, val)
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # no libtpu, or one that cannot describe
            pytest.skip(f"libtpu gives no v5e topology here: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def v5e_device(v5e_devices):
    return v5e_devices[0]


def _compile_decode_step(device, kv_quant):
    from jax.sharding import SingleDeviceSharding

    sh = SingleDeviceSharding(device)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)

    lm = TransformerLM(VOCAB, hidden_size=HEADS * HD, n_heads=HEADS,
                       n_layers=2, max_len=MAX_LEN, output="logits")
    step, init_carry = make_batch_decode_step(
        lm, jnp.bfloat16, sampling=True, kv_quant=kv_quant)
    params = jax.tree.map(sds, jax.eval_shape(
        lambda: serving_params(lm, jnp.bfloat16)))
    carry = jax.tree.map(sds, jax.eval_shape(lambda: init_carry(N_SLOTS)))
    knobs = jax.tree.map(sds, make_knob_rows(N_SLOTS, vocab=VOCAB))
    return step.lower(params, sds(jnp.zeros((N_SLOTS,), jnp.int32)),
                      sds(jnp.zeros((N_SLOTS,), bool)), carry,
                      knobs).compile()


@pytest.fixture
def on_the_chip(monkeypatch):
    monkeypatch.setattr("bigdl_tpu.utils.compat.auto_interpret",
                        lambda: False)


def _pool_sized_copies(text, sizes):
    return [m.group(0)
            for m in re.finditer(r"= \w+\[([\d,]+)\]\S* copy\(", text)
            if math.prod(map(int, m.group(1).split(","))) in sizes]


@pytest.mark.parametrize("kv_quant", [False, True], ids=["bf16", "int8"])
def test_decode_step_never_copies_the_pool(v5e_device, kv_quant,
                                           on_the_chip):
    compiled = _compile_decode_step(v5e_device, kv_quant)
    text = compiled.as_text()
    assert "pooled_decode_attention" in text
    assert not _pool_sized_copies(text, {POOL_ELEMS})
    # the donated carry comes back in the layout it arrived in
    carry_in = compiled.input_formats[0][3]
    carry_out = compiled.output_formats[2]
    for key in ("k0", "v0", "k1", "v1"):
        assert carry_in[key].layout == carry_out[key].layout, key
        assert carry_in[key].layout.major_to_minor == (0, 1, 2), key


def test_data_mesh_decode_step_runs_the_kernel_on_each_chips_rows(
        v5e_devices, on_the_chip):
    """A data-only plane (four chips, the slots sharded over them, the
    program a plain jit that XLA partitions): the decode kernel, which
    XLA cannot partition, runs per chip over the rows the chip holds
    (``_token_view``'s ``shard_map`` by rows); nothing of the pool or
    the rows is gathered or reduced across chips."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bigdl_tpu.models.transformer import serving_carry_specs
    from bigdl_tpu.serving.sampling import knob_partition_specs

    mesh = Mesh(np.array(v5e_devices).reshape(4, 1), ("data", "model"))

    def sds(x, spec):
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=NamedSharding(mesh, spec))

    lm = TransformerLM(VOCAB, hidden_size=HEADS * HD, n_heads=HEADS,
                       n_layers=2, max_len=MAX_LEN, output="logits")
    step, init_carry = make_batch_decode_step(
        lm, jnp.bfloat16, sampling=True, mesh=mesh)
    params = jax.tree.map(lambda x: sds(x, P()), jax.eval_shape(
        lambda: serving_params(lm, jnp.bfloat16)))
    carry = jax.tree.map(
        sds, jax.eval_shape(lambda: init_carry(N_SLOTS)),
        serving_carry_specs(lm, sampling=True, data_axis="data",
                            model_axis=None))
    knobs = jax.tree.map(sds, make_knob_rows(N_SLOTS, vocab=VOCAB),
                         knob_partition_specs("data"))
    text = step.lower(
        params, sds(jnp.zeros((N_SLOTS,), jnp.int32), P("data")),
        sds(jnp.zeros((N_SLOTS,), bool), P("data")), carry,
        knobs).compile().as_text()
    # each chip's kernel sees its own two of the eight rows
    assert re.search(r"pooled_decode_attention\S* = bf16\[2,", text)
    for collective in ("all-gather", "all-to-all", "collective-permute"):
        assert f" {collective}(" not in text, collective
    # ONE scalar crosses the chips: whether a decoding row anywhere
    # makes this step's sampler sort the vocabulary (sampling.py's
    # ``any`` over rows that XLA partitions)
    reduced = [line for line in text.splitlines() if " all-reduce(" in line]
    assert len(reduced) == 1 and "/reduce_or" in reduced[0], reduced
    assert re.search(r"= \w+\[\]\S* all-reduce\(", reduced[0]), reduced[0]


@pytest.mark.parametrize("shape", [(4, 1), (2, 2)], ids=["4x1", "2x2"])
def test_draft_decode_step_lowers_on_every_serving_mesh(
        v5e_devices, on_the_chip, shape):
    """The speculative DRAFT's decode step (``serving/speculative.py``:
    weights replicated, the carry's rows sharded over ``data`` and whole
    per chip over ``model``, a plain jit on the data-only and on the
    DP x TP plane alike) takes the plane's mesh with no model axis, so
    its kernel too runs per chip over the rows the chip holds. Built
    without the mesh it does not lower for a TPU at all."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bigdl_tpu.models.transformer import serving_carry_specs

    mesh = Mesh(np.array(v5e_devices).reshape(shape), ("data", "model"))

    def sds(x, spec):
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=NamedSharding(mesh, spec))

    lm = TransformerLM(VOCAB, hidden_size=HEADS * HD, n_heads=HEADS,
                       n_layers=2, max_len=MAX_LEN, output="logits")
    lm._ensure_params()
    params = jax.tree.map(lambda x: sds(x, P()), jax.eval_shape(
        lambda: serving_params(lm, jnp.bfloat16)))

    def lower(**plane):
        step, init_carry = make_batch_decode_step(lm, jnp.bfloat16, **plane)
        carry = jax.tree.map(
            sds, jax.eval_shape(lambda: init_carry(N_SLOTS)),
            serving_carry_specs(lm, sampling=False, data_axis="data",
                                model_axis=None))
        return step.lower(
            params, sds(jnp.zeros((N_SLOTS,), jnp.int32), P("data")),
            sds(jnp.zeros((N_SLOTS,), bool), P("data")), carry)

    text = lower(mesh=mesh, model_axis=None).compile().as_text()
    rows = N_SLOTS // shape[0]
    assert re.search(rf"pooled_decode_attention\S* = bf16\[{rows},", text)
    assert not _pool_sized_copies(text, {POOL_ELEMS // shape[0]})
    with pytest.raises(Exception, match="automatically partitioned"):
        lower().compile()


def test_recurrent_family_decode_step_copies_neither_cache_nor_state(
        v5e_device, on_the_chip):
    """The Falcon-H1 family's decode step at head and state widths of
    the published model (128-wide heads, a 128 x 256 state a head): the
    grouped-query read of the stored 3-D K/V and the in-place update of
    the float32 scan state leave no pool-sized copy either."""
    from jax.sharding import SingleDeviceSharding

    from bigdl_tpu.models.falcon_h1 import FalconH1LM

    sh = SingleDeviceSharding(v5e_device)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)

    config = dict(
        vocab_size=VOCAB, hidden_size=256, intermediate_size=512,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=128, mamba_d_ssm=512, mamba_n_heads=4, mamba_d_head=128,
        mamba_d_state=256, mamba_n_groups=2, mamba_d_conv=4,
        mamba_chunk_size=128, rms_norm_eps=1e-5, rope_theta=1e11,
        embedding_multiplier=5.66, lm_head_multiplier=0.0078125,
        attention_in_multiplier=1, attention_out_multiplier=0.0375,
        key_multiplier=0.011, ssm_in_multiplier=0.25,
        ssm_out_multiplier=0.088, ssm_multipliers=[0.35, 0.25, 0.18, 0.5,
                                                   0.35],
        mlp_multipliers=[0.18, 0.011])
    lm = FalconH1LM(config, max_len=MAX_LEN, param_dtype="bfloat16")
    family = lm.serving_family()
    step, init_carry = family.decode_step(jnp.bfloat16)
    params = jax.tree.map(sds, jax.eval_shape(
        lm.init_params, jax.random.PRNGKey(0)))
    carry = jax.tree.map(sds, jax.eval_shape(lambda: init_carry(N_SLOTS)))
    knobs = jax.tree.map(sds, make_knob_rows(N_SLOTS, vocab=VOCAB))
    compiled = step.lower(params, sds(jnp.zeros((N_SLOTS,), jnp.int32)),
                          sds(jnp.zeros((N_SLOTS,), bool)), carry,
                          knobs).compile()
    text = compiled.as_text()
    assert "pooled_decode_attention" in text
    assert not _pool_sized_copies(
        text, {math.prod(carry[key].shape) for key in ("k0", "ssm0")})
    # the state is updated by the kernel, over the decoding rows, in
    # place: no XLA fusion makes a whole leaf's float32 array
    assert text.count("ssm_decode_step") >= config["num_hidden_layers"]
    leaf = "f32[%d,%d,%d,%d]" % (N_SLOTS, config["mamba_n_heads"],
                                  config["mamba_d_head"],
                                  config["mamba_d_state"])
    made = [line.split(" fusion(")[0] for line in text.splitlines()
            if " fusion(" in line]
    assert not [out for out in made if leaf in out], leaf
    carry_in = compiled.input_formats[0][3]
    carry_out = compiled.output_formats[2]
    for key in ("k0", "v1", "ssm0", "conv1"):
        assert carry_in[key].layout == carry_out[key].layout, key


def test_routed_family_decode_step_reads_rings_and_windows_in_place(
        v5e_device, on_the_chip):
    """The ``afmoe`` family's decode step at the published head width
    (128, four query heads a K/V head): a sliding layer's ring and a
    full layer's window, leaves of two lengths, go through the kernel
    where they are stored."""
    from jax.sharding import SingleDeviceSharding

    from bigdl_tpu.models.afmoe import AfmoeLM

    sh = SingleDeviceSharding(v5e_device)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)

    config = dict(
        vocab_size=VOCAB, hidden_size=384, intermediate_size=512,
        moe_intermediate_size=128, num_hidden_layers=3, num_dense_layers=1,
        num_attention_heads=8, num_key_value_heads=2, head_dim=128,
        num_experts=4, num_experts_per_tok=2, num_shared_experts=1,
        route_norm=True, route_scale=2.448, score_func="sigmoid",
        sliding_window=128,
        layer_types=["sliding_attention", "full_attention",
                     "sliding_attention"],
        rms_norm_eps=1e-5, rope_theta=10000, mup_enabled=True,
        expert_share={"index": 1, "of": 4})
    lm = AfmoeLM(config, max_len=MAX_LEN, param_dtype="bfloat16")
    step, init_carry = lm.serving_family().decode_step(jnp.bfloat16)
    params = jax.tree.map(sds, jax.eval_shape(
        lm.init_params, jax.random.PRNGKey(0)))
    carry = jax.tree.map(sds, jax.eval_shape(lambda: init_carry(N_SLOTS)))
    assert carry["k0"].shape[1] == 128 and carry["k1"].shape[1] == MAX_LEN
    knobs = jax.tree.map(sds, make_knob_rows(N_SLOTS, vocab=VOCAB))
    compiled = step.lower(params, sds(jnp.zeros((N_SLOTS,), jnp.int32)),
                          sds(jnp.zeros((N_SLOTS,), bool)), carry,
                          knobs).compile()
    text = compiled.as_text()
    assert text.count("pooled_decode_attention") >= 3
    assert not _pool_sized_copies(
        text, {math.prod(carry[key].shape) for key in ("k0", "k1")})
    carry_in = compiled.input_formats[0][3]
    carry_out = compiled.output_formats[2]
    for key in ("k0", "v0", "k1", "v2"):
        assert carry_in[key].layout == carry_out[key].layout, key


def test_latent_family_decode_step_reads_its_one_leaf_in_place(
        v5e_device, on_the_chip):
    """The ``glm4_moe_lite`` family's bf16 decode step at the published
    head sizes (20 heads, a 512-wide latent beside a 64-wide rotary
    key, heads of 192 + 64 and 256), hidden size and depth cut: the one
    cache leaf a layer goes through ``latent_decode_attention`` where it
    is stored (640 columns, row-major), no pool-sized ``copy``, no
    tensor of per-head keys or values of cached positions, and the
    carry comes back in the layout it came in."""
    from jax.sharding import SingleDeviceSharding

    from bigdl_tpu.models.glm_moe_lite import GlmMoeLiteLM

    sh = SingleDeviceSharding(v5e_device)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)

    heads = 20
    config = dict(
        vocab_size=VOCAB, hidden_size=256, intermediate_size=512,
        moe_intermediate_size=128, num_hidden_layers=3,
        first_k_dense_replace=1, num_attention_heads=heads,
        num_key_value_heads=heads, q_lora_rank=128, kv_lora_rank=512,
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
        n_routed_experts=4, num_experts_per_tok=2, n_shared_experts=1,
        norm_topk_prob=True, routed_scaling_factor=1.8, rms_norm_eps=1e-5,
        rope_theta=1000000, expert_share={"index": 1, "of": 4})
    lm = GlmMoeLiteLM(config, max_len=MAX_LEN, param_dtype="bfloat16")
    step, init_carry = lm.serving_family().decode_step(jnp.bfloat16)
    params = jax.tree.map(sds, jax.eval_shape(
        lm.init_params, jax.random.PRNGKey(0)))
    carry = jax.tree.map(sds, jax.eval_shape(lambda: init_carry(N_SLOTS)))
    assert carry["k0"].shape == (N_SLOTS, MAX_LEN, 640) and "v0" not in carry
    knobs = jax.tree.map(sds, make_knob_rows(N_SLOTS, vocab=VOCAB))
    compiled = step.lower(params, sds(jnp.zeros((N_SLOTS,), jnp.int32)),
                          sds(jnp.zeros((N_SLOTS,), bool)), carry,
                          knobs).compile()
    text = compiled.as_text()
    assert text.count("latent_decode_attention") >= 3
    assert "%pooled_decode_attention" not in text     # no second fetch
    assert not _pool_sized_copies(text, {math.prod(carry["k0"].shape)})
    # nothing as large as (rows, L, heads, 256): expanded keys or values
    expanded = N_SLOTS * MAX_LEN * heads * 256
    sizes = [math.prod(map(int, m.group(1).split(",")))
             for m in re.finditer(r"= \w+\[([\d,]+)\]", text)]
    assert max(sizes) < expanded, max(sizes)
    carry_in = compiled.input_formats[0][3]
    carry_out = compiled.output_formats[2]
    for key in ("k0", "k1", "k2"):
        assert carry_in[key].layout == carry_out[key].layout, key
        assert carry_in[key].layout.major_to_minor == (0, 1, 2), key
