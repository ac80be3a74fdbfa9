"""The files of the cell ``glm47flash-serve-longctx``: the plain
``glm4_moe_lite`` reference against the program's own forward at the
rehearsal's toy size on the CPU, the configuration against the catalog's
published keys, the operation and byte counts against hand-worked
values, the readers on made-up observations, the cell's entries in
``BENCHMARK.json``, and its ``--rehearse-cpu`` run."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import (harness, reference, serve_costs_glm_moe_lite,
                       serve_flops_glm_moe_lite)
from benchmark.readers import serve_roofline_glm_moe_lite as readers

CELL = "glm47flash-serve-longctx"
CONFIG = harness.load_json(harness.HERE / "configs" / "glm-4.7-flash.json")
NEW_METRICS = ("decode_hbm_roofline.glm", "serve_mfu.glm",
               "experts_hit_share.glm", "kv_position_kb")
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "num_nextn_predict_layers"]


def test_reference_agrees_with_the_program_in_float32():
    import jax.numpy as jnp

    from bigdl_tpu.models.glm_moe_lite import GlmMoeLiteLM
    from bigdl_tpu.utils.random_gen import RNG

    cfg = harness.Cell(CELL, rehearsal=True).config
    RNG.set_seed(3)
    lm = GlmMoeLiteLM(cfg, max_len=cfg["serve"]["max_len"])     # float32
    lm._ensure_params()
    lm.evaluate()
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        1, cfg["vocab_size"] + 1, size=(2, 45)), jnp.int32)
    want = np.asarray(lm.apply(lm.params, tokens)[0][0])
    got = np.asarray(reference.load_reference(cfg).logits_and_ties(
        lm.params, tokens[0], jnp.arange(45), cfg)[0])
    # both float32 on the CPU: agreement to rounding
    assert np.abs(got - want).max() < 2e-5 * want.std()
    # the factory makes the cell's model: bfloat16 leaves, the cell's
    # cache window, the router over all experts of which two are held
    served = harness.resolve(cfg["model"]["factory"])(cfg)
    assert served.param_dtype == "bfloat16"
    assert served.max_len == cfg["serve"]["max_len"]
    assert (served.config.router_experts,
            served.config.n_routed_experts) == (8, 2)


def test_configuration_keeps_every_published_number():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    published = {
        "hidden_size": 2048, "intermediate_size": 10240,
        "moe_intermediate_size": 1536, "num_attention_heads": 20,
        "num_key_value_heads": 20, "q_lora_rank": 768, "kv_lora_rank": 512,
        "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
        "num_experts_per_tok": 4, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "first_k_dense_replace": 1,
        "rope_theta": 1000000, "rms_norm_eps": 1e-05}
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "GLM-4.7-Flash")
        published = row["config"]
        assert CONFIG["source"] == row["source_url"]
    assert CONFIG["reduced"] == REDUCED
    for key, value in published.items():
        if key not in REDUCED:
            assert key in CONFIG and CONFIG[key] == value, key
    assert CONFIG["published"] == {
        "num_hidden_layers": 47, "n_routed_experts": 64,
        "vocab_size": 154880, "num_nextn_predict_layers": 1}
    # the floors: the leading dense layer and at least four expert
    # layers, at least 8 experts held, an eighth of the vocabulary
    assert (CONFIG["num_hidden_layers"],
            CONFIG["first_k_dense_replace"]) == (13, 1)
    assert CONFIG["n_routed_experts"] == 8
    assert CONFIG["n_routed_experts"] * CONFIG["expert_share"]["of"] == 64
    assert CONFIG["vocab_size"] * 8 == 154880
    assert CONFIG["num_nextn_predict_layers"] == 0
    assert CONFIG["serve"]["engine"] == {"n_slots": 32,
                                         "compute_dtype": "bfloat16"}
    assert CONFIG["serve"]["max_len"] == 16384
    for block in ("deployment", "assumed", "departures", "rehearsal"):
        assert CONFIG[block], block


ATTENTION = 2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448 \
    + 20 * 256 * 2048
EXPERT = 3 * 2048 * 1536
DENSE = 13 * ATTENTION + 3 * 2048 * 10240 + 12 * (2048 * 64 + EXPERT) \
    + 2048 * 19360


def test_operation_counts():
    flops = serve_flops_glm_moe_lite
    assert flops.glm_attention_params(CONFIG) == ATTENTION == 21_757_952
    assert flops.glm_expert_params(CONFIG) == EXPERT == 9_437_184
    assert flops.glm_dense_matmul_params(CONFIG) == DENSE == 500_236_288
    # a cached position of a layer: scores over the 576, the sum over
    # the 512, 20 heads; a prompt's key pair: 256 + 256
    assert flops.glm_key_flops(CONFIG, absorbed=True) == 2 * 20 * (576 + 512)
    assert flops.glm_key_flops(CONFIG, absorbed=False) == 2 * 20 * 512
    assert flops.glm_flops_per_token(CONFIG, 13 * 9000, 6.0) \
        == 2.0 * (DENSE + 6 * EXPERT) + 2 * 20 * 1088 * 13 * 9000
    assert flops.glm_prompt_flops(CONFIG, 100, 6.0) \
        == 100 * 2.0 * (DENSE + 6 * EXPERT) \
        + 2 * 20 * 512 * 13 * 100 * 101 / 2
    # the model as held: these matrices, the 96 experts, the embedding,
    # the norms and the router's bias: 1,445.9M (my compile, PR 34)
    assert DENSE + 12 * 8 * EXPERT + 2048 * 19360 \
        + 13 * (2 * 2048 + 768 + 512) + 2048 + 12 * 64 == 1_445_927_936


def test_byte_counts():
    costs = serve_costs_glm_moe_lite
    assert costs.latent_row_bytes(CONFIG, CONFIG["serve"]) == 1152
    # six rows decoding at 8,500 positions each, 40 experts hit, 36 pairs
    load = {"rows": 6.0, "held_positions": 51000.0, "experts_hit": 40.0,
            "expert_pairs": 36.0}
    cost = costs.glm_decode_step(CONFIG, CONFIG["serve"], load)
    want = (DENSE + 40 * EXPERT) * 2 + 6 * 2048 * 2 \
        + 13 * 1152 * (51000 + 6) + 2 * 6 * 19360 * 4
    assert cost["bytes"] == want
    assert cost["flops"] == 6 * serve_flops_glm_moe_lite.glm_flops_per_token(
        CONFIG, 13 * 8500.0, 6.0)
    # the cache is 0.76 GB of the step's 2.5: the ISSUE's arithmetic
    assert 0.29 < 13 * 1152 * 51000 / want < 0.32


@pytest.fixture
def _obs():
    """Made-up observations of the cell, with ``over`` laid over them:
    six of 32 rows, each holding 8,500 positions of 16,640 stored bytes."""
    cell = harness.Cell(CELL)
    base = dict(config=cell.config, settings=cell.settings,
                traffic=cell.traffic, chips=1,
                peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
                series={"serving/slot_occupancy": [6 / 32] * 4,
                        "serving/kv_held_bytes": [51000 * 16640.0] * 4,
                        "serving/kv_position_bytes": [16640.0] * 4,
                        "serving/experts_hit": [40.0] * 4,
                        "serving/expert_pairs": [36.0] * 4,
                        "serving/batch_active": [6.0] * 4,
                        "serving/prefill_batch": [1.0, 1.0]},
                spans={"steps": [(0.0, 0.02, 0), (0.02, 2.0, 1)]},
                trace={"programs": {"jit_sample_step": {"mean_ms": 6.0,
                                                        "count": 300}}})
    return lambda **over: dict(base, **over)


LOAD = {"rows": 6.0, "held_positions": 51000.0, "experts_hit": 40.0,
        "expert_pairs": 36.0}


def test_decode_roofline_reader(_obs):
    args = {"costs": "benchmark.serve_costs_glm_moe_lite:glm_decode_step"}
    cost = serve_costs_glm_moe_lite.glm_decode_step(
        CONFIG, CONFIG["serve"], LOAD)
    got = readers.decode_roofline(_obs(), args)
    # the positions are held bytes over what ONE costs as stored, then
    # counted at the published 1,152 bytes a layer
    assert abs(got - 100 * (cost["bytes"] / 819e9) / 6e-3) < 1e-9
    assert 45 < got < 55
    # nothing to read: no trace, no such program, no series (the parent)
    assert readers.decode_roofline(_obs(trace=None), args) is None
    assert readers.decode_roofline(_obs(trace={"programs": {}}), args) is None
    assert readers.decode_roofline(_obs(series={}), args) is None
    partial = dict(_obs()["series"])
    del partial["serving/kv_position_bytes"]
    assert readers.decode_roofline(_obs(series=partial), args) is None


def test_serve_mfu_reader(_obs):
    from benchmark import traffic

    flops = serve_flops_glm_moe_lite
    args = {"flops": "benchmark.serve_flops_glm_moe_lite:glm_flops_per_token",
            "prompt_flops":
                "benchmark.serve_flops_glm_moe_lite:glm_prompt_flops"}
    got = readers.serve_mfu(_obs(), args)
    mix = harness.Cell(CELL).traffic
    prompts = [n - 1 for n in traffic.length_set(
        mix["prompt_len"], traffic.block_size(mix))]
    want = 24 * flops.glm_flops_per_token(CONFIG, 13 * 8500.0, 6.0) \
        + 2 * sum(flops.glm_prompt_flops(CONFIG, n, 6.0)
                  for n in prompts) / len(prompts)
    assert abs(got - 100 * want / (2.0 * 197e12)) < 1e-9
    # two long prompts in two seconds: the prefill's operations lead
    assert 5 < got < 20
    assert readers.serve_mfu(_obs(series={}), args) is None


def test_benchmark_json_lists_the_cell_as_additions():
    """The configuration, the cell and the four metrics stand at the END
    of their lists, the cell's name at the end of every ``workloads``
    that named the three serving cells, and the layers are ones the
    benchmark already names."""
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    assert bench["configs"][-1]["name"] == "glm-4.7-flash"
    assert bench["configs"][-1]["source"] == CONFIG["source"]
    assert bench["configs"][-1]["reduced"] == CONFIG["reduced"]
    assert bench["workloads"][-1] == dict(
        bench["workloads"][-1], name=CELL, config="glm-4.7-flash",
        traffic="long-context-steady", chips=1)
    new = bench["per_layer"][-4:]
    assert [m["name"] for m in new] == list(NEW_METRICS)
    assert all(m["workloads"] == [CELL] for m in new)
    assert {m["layer"] for m in new} \
        <= {m["layer"] for m in bench["per_layer"][:-4]}
    three = {"gpt2m-serve-chat", "falconh1-serve-reason",
             "trinity-serve-mixed"}
    joined = [m for m in bench["end_to_end"] + bench["per_layer"]
              if three <= set(m.get("workloads", []))]
    assert len(joined) == 24                  # 2 end to end + 22 per layer
    # all but the two that need a prefill wave inside the 2 s traced
    # after the window, which this mix's order never puts there
    apart = {"prefill_device_ms", "pool_write_device_ms"}
    assert {m["name"] for m in joined if m["workloads"][-1] != CELL} == apart
    # the metrics that read a series with no size of another cell in it
    named = {m["name"]: m for m in bench["per_layer"]}
    for name in ("expert_pairs_per_step", "expert_load_max", "kv_held_gb"):
        assert named[name]["workloads"] == ["trinity-serve-mixed", CELL]
    # and those whose files hold one stay Trinity's alone
    for name in ("experts_hit_share", "decode_hbm_roofline.trinity",
                 "serve_mfu.trinity"):
        assert named[name]["workloads"] == ["trinity-serve-mixed"]


def test_new_metrics_are_declared_for_the_new_cell_only():
    cell = harness.Cell(CELL)
    mine = {m["name"]: spec for m, spec in cell.per_layer}
    for name in NEW_METRICS:
        assert name in mine
        assert callable(harness.resolve(mine[name]["reader"]))
    for other in ("gpt2m-serve-chat", "falconh1-serve-reason",
                  "trinity-serve-mixed"):
        theirs = {m["name"] for m, _ in harness.Cell(other).per_layer}
        assert not theirs & set(NEW_METRICS)
    for series in ("serving/kv_held_bytes", "serving/kv_position_bytes",
                   "serving/kv_fetched_bytes", "serving/experts_hit",
                   "serving/expert_pairs", "serving/expert_load_max"):
        assert series in cell.series_names()
    # the metrics all serving cells report come along
    assert "prefill_device_ms" not in mine
    for name in ("decode_device_ms", "prefill_pad_share", "kv_used_share",
                 "hbm_peak.serve", "decode_chained_share", "kv_fetched_gb",
                 "kv_held_gb", "expert_pairs_per_step", "expert_load_max"):
        assert name in mine
    assert abs(mine["experts_hit_share.glm"]["args"]["scale"]
               - 100 / (12 * 8)) < 1e-12
    assert [m["name"] for m in cell.end_to_end()] == [
        "gap_p50_ms", "serve_tokens_per_s", "setup_s"]


def test_the_mix_is_the_issues_table():
    from benchmark import traffic

    mix = harness.Cell(CELL).traffic
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 8192,
                                 "sigma": 0.6, "min": 2048, "max": 15104}
    assert mix["output_len"] == {"dist": "lognormal", "median": 384,
                                 "sigma": 0.6, "min": 64, "max": 1024}
    assert mix["sampling"] == {"every": 2, "temperature": 0.8, "top_k": 50}
    assert (mix["block_s"], mix["ramp_s"], mix["drain_limit_s"],
            mix["reference_sample"]) == (10, 20, 60, 4)
    assert mix["warmup_prompt_lens"] == [2048, 3000, 6000, 12000]
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] == 16128 \
        < CONFIG["serve"]["max_len"]
    # every bucket from 2,048 to 16,384 is warmed, and hit by the mix
    from bigdl_tpu.serving.admission import bucket_len

    warmed = {bucket_len(n - 1, 16384) for n in mix["warmup_prompt_lens"]}
    assert warmed == {2048, 4096, 8192, 16384}
    drawn = traffic.length_set(mix["prompt_len"], 400)
    assert {bucket_len(n - 1, 16384) for n in drawn} == warmed
    assert 0.005 <= np.mean([n == 2048 for n in drawn]) < 0.02
    assert 0.13 < np.mean([n == 15104 for n in drawn]) < 0.17
    sweep = mix["sweep"]
    rate = mix["arrivals"]["rate_per_s"]
    assert rate == sweep["fixed_rate_per_s"]
    assert rate * mix["block_s"] == int(rate * mix["block_s"])
    assert 0 <= sweep["share_of_knee"] * sweep["knee_per_s"] - rate < 0.1


def test_rehearsal_exits_4_and_prints_no_result():
    p = subprocess.run(
        [sys.executable, str(harness.HERE / "run.py"), "--workload",
         CELL, "--rehearse-cpu", "--seed", "3000000019", "--seconds", "1",
         "--trace", "1"], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=str(harness.ROOT)), cwd=harness.ROOT,
        timeout=600)
    assert p.returncode == harness.REHEARSAL_EXIT, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    assert lines and all(ln.startswith(harness.REHEARSAL_TAG)
                         for ln in lines)
    assert not [ln for ln in lines if ln.startswith("{")]
    assert '"compiled_in_window": 0' in p.stdout
    assert '"counter_identities_broken": []' in p.stdout
    for name in ("experts_hit_share.glm", "kv_position_kb", "serve_mfu.glm",
                 "kv_held_gb", "kv_fetched_gb", "expert_pairs_per_step",
                 "prefill_pad_share"):
        assert name in lines[-2]
