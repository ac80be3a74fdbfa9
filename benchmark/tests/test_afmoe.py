"""The files of the cell ``trinity-serve-mixed``: the plain ``afmoe``
reference against the program's own forward at the rehearsal's toy size
on the CPU, the positions it leaves unjudged, the configuration against
the catalog's published keys, the operation and byte counts against
hand-worked values, the readers on made-up observations, the cell's
entries in ``BENCHMARK.json``, and its ``--rehearse-cpu`` run."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import harness, reference, serve_costs_afmoe, serve_flops_afmoe
from benchmark.readers import serve_roofline_afmoe

CELL = "trinity-serve-mixed"
CONFIG = harness.load_json(
    harness.HERE / "configs" / "trinity-large-preview.json")
NEW_METRICS = ("expert_pairs_per_step", "experts_hit_share",
               "expert_load_max", "kv_held_gb",
               "decode_hbm_roofline.trinity", "serve_mfu.trinity")


def test_reference_agrees_with_the_program_in_float32():
    import jax.numpy as jnp

    from bigdl_tpu.models.afmoe import AfmoeLM
    from bigdl_tpu.utils.random_gen import RNG

    cfg = harness.Cell(CELL, rehearsal=True).config
    RNG.set_seed(3)
    lm = AfmoeLM(cfg, max_len=cfg["serve"]["max_len"])        # float32
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
    # cache window, the published kinds of layer
    served = harness.resolve(cfg["model"]["factory"])(cfg)
    assert served.param_dtype == "bfloat16"
    assert served.max_len == cfg["serve"]["max_len"]
    assert served.config.router_experts == 16 and served.config.num_experts == 4


def test_configuration_keeps_every_published_number():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    published = {
        "hidden_size": 3072, "intermediate_size": 12288,
        "moe_intermediate_size": 3072, "num_attention_heads": 48,
        "num_key_value_heads": 8, "head_dim": 128, "num_experts_per_tok": 4,
        "num_shared_experts": 1, "sliding_window": 4096,
        "route_scale": 2.448, "score_func": "sigmoid", "rope_theta": 10000}
    full_types = None
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Trinity-Large-Preview")
        published = row["config"]
        full_types = published["layer_types"]
        assert CONFIG["source"] == row["source_url"]
    reduced = ["num_hidden_layers", "num_dense_layers", "num_experts",
               "vocab_size", "layer_types"]
    assert CONFIG["reduced"] == reduced
    for key, value in published.items():
        if key not in reduced:
            assert CONFIG[key] == value, key
    assert {k: CONFIG["published"][k] for k in reduced[:4]} == {
        "num_hidden_layers": 60, "num_dense_layers": 6, "num_experts": 256,
        "vocab_size": 200192}
    # the floors: one leading dense layer and a whole period of four
    # expert layers, at least 8 experts held, an eighth of the vocabulary
    assert (CONFIG["num_hidden_layers"], CONFIG["num_dense_layers"]) == (5, 1)
    if full_types:
        assert CONFIG["layer_types"] == full_types[:5]
    assert CONFIG["layer_types"].count("full_attention") == 1
    assert CONFIG["num_experts"] * CONFIG["expert_share"]["of"] == 256
    assert CONFIG["vocab_size"] * 8 == 200192
    assert CONFIG["serve"]["engine"] == {"n_slots": 16,
                                         "compute_dtype": "bfloat16"}
    assert CONFIG["serve"]["max_len"] == 8192
    for block in ("deployment", "assumed", "departures", "rehearsal"):
        assert CONFIG[block], block


def test_operation_and_byte_counts():
    attention = 3072 * (2 * 6144 + 2 * 1024) + 6144 * 3072
    assert attention == 62_914_560
    expert = 3 * 3072 * 3072
    assert serve_flops_afmoe.afmoe_expert_params(CONFIG) == expert \
        == 28_311_552
    n = serve_flops_afmoe.afmoe_dense_matmul_params(CONFIG)
    assert n == 5 * attention + 3 * 3072 * 12288 \
        + 4 * (3072 * 256 + expert) + 3072 * 25024
    # keys a token at position 5,000 attends over: four rings of 4,096
    # and the full layer
    assert serve_flops_afmoe.afmoe_held_keys(CONFIG, 8192, 5000) \
        == 4 * 4096 + 5000
    assert serve_flops_afmoe.afmoe_held_keys(CONFIG, 8192, 300) == 5 * 300
    assert serve_flops_afmoe.afmoe_flops_per_token(CONFIG, 1500, 2.0) \
        == 2.0 * (n + 2 * expert) + 4.0 * 48 * 128 * 1500
    # ten rows decoding, 20 experts hit, 21 pairs, 0.5 GB of K/V held
    load = {"rows": 10.0, "experts_hit": 20.0, "expert_pairs": 21.0,
            "kv_held_bytes": 0.5e9}
    cost = serve_costs_afmoe.afmoe_decode_step(CONFIG, CONFIG["serve"], load)
    want = (n + 20 * expert) * 2 + 10 * 3072 * 2 + 0.5e9 \
        + 10 * 5 * 4096 + 2 * 10 * 25024 * 4
    assert cost["bytes"] == want
    assert cost["flops"] == 10 * serve_flops_afmoe.afmoe_flops_per_token(
        CONFIG, 0.5e9 / 4096 / 10, 2.1)
    # the experts that were hit lead: 1.13 GB of the step's 2.9
    assert 0.38 < 20 * expert * 2 / want < 0.40


@pytest.fixture
def _obs():
    """Made-up observations of the cell, with ``over`` laid over them."""
    cell = harness.Cell(CELL)
    base = dict(config=cell.config, settings=cell.settings,
                traffic=cell.traffic, chips=1,
                peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
                series={"serving/slot_occupancy": [10 / 16] * 4,
                        "serving/kv_held_bytes": [0.5e9] * 4,
                        "serving/experts_hit": [20.0] * 4,
                        "serving/expert_pairs": [21.0] * 4,
                        "serving/batch_active": [10.0] * 4,
                        "serving/prefill_batch": [1.0, 1.0]},
                spans={"steps": [(0.0, 0.02, 0), (0.02, 0.6, 1)]},
                trace={"programs": {"jit_sample_step": {"mean_ms": 8.0}}})
    return lambda **over: dict(base, **over)


def test_decode_roofline_reader(_obs):
    args = {"costs": "benchmark.serve_costs_afmoe:afmoe_decode_step"}
    load = {"rows": 10.0, "experts_hit": 20.0, "expert_pairs": 21.0,
            "kv_held_bytes": 0.5e9}
    cost = serve_costs_afmoe.afmoe_decode_step(CONFIG, CONFIG["serve"], load)
    got = serve_roofline_afmoe.decode_roofline(_obs(), args)
    assert abs(got - 100 * (cost["bytes"] / 819e9) / 8e-3) < 1e-9
    assert 40 < got < 50
    # nothing to read: no trace, no such program, no series (the parent)
    assert serve_roofline_afmoe.decode_roofline(_obs(trace=None), args) is None
    assert serve_roofline_afmoe.decode_roofline(
        _obs(trace={"programs": {}}), args) is None
    assert serve_roofline_afmoe.decode_roofline(_obs(series={}), args) is None
    partial = dict(_obs()["series"])
    del partial["serving/experts_hit"]
    assert serve_roofline_afmoe.decode_roofline(_obs(series=partial),
                                                args) is None


def test_serve_mfu_reader(_obs):
    from benchmark import traffic

    args = {"flops": "benchmark.serve_flops_afmoe:afmoe_flops_per_token",
            "held_keys": "benchmark.serve_flops_afmoe:afmoe_held_keys"}
    got = serve_roofline_afmoe.serve_mfu(_obs(), args)
    mix = harness.Cell(CELL).traffic
    prompts = [n - 1 for n in traffic.length_set(
        mix["prompt_len"], traffic.block_size(mix))]
    mean_prompt = sum(prompts) / len(prompts)
    # 40 emitted tokens and two requests' prompts in 0.6 s: the matrices
    # every token passes through are the floor, the experts' 2.1 pairs a
    # token and the attention over up to 4,096 + 6,143 keys come on top
    tokens = 40 + 2 * mean_prompt
    floor = 100 * tokens * 2 \
        * serve_flops_afmoe.afmoe_dense_matmul_params(CONFIG) \
        / (0.6 * 197e12)
    assert floor < got < 1.6 * floor
    assert got < 100
    assert serve_roofline_afmoe.serve_mfu(_obs(series={}), args) is None


def test_benchmark_json_lists_the_cell_as_additions():
    """The configuration, the cell and the six metrics stand at the END
    of their lists, the cell's name at the end of every ``workloads``
    that named both serving cells, and the layers are ones the
    benchmark already names."""
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    assert bench["configs"][-1]["name"] == "trinity-large-preview"
    assert bench["configs"][-1]["source"] == CONFIG["source"]
    assert bench["configs"][-1]["reduced"] == CONFIG["reduced"]
    assert bench["workloads"][-1] == dict(
        bench["workloads"][-1], name=CELL, config="trinity-large-preview",
        traffic="mixed-context-steady", chips=1)
    new = bench["per_layer"][-6:]
    assert [m["name"] for m in new] == list(NEW_METRICS)
    assert all(m["workloads"] == [CELL] for m in new)
    assert {m["layer"] for m in new} \
        <= {m["layer"] for m in bench["per_layer"][:-6]}
    both = {"gpt2m-serve-chat", "falconh1-serve-reason"}
    joined = [m for m in bench["end_to_end"] + bench["per_layer"]
              if both <= set(m.get("workloads", []))]
    assert len(joined) == 23
    assert all(m["workloads"][-1] == CELL for m in joined)


def test_new_metrics_are_declared_for_the_new_cell_only():
    cell = harness.Cell(CELL)
    mine = {m["name"]: spec for m, spec in cell.per_layer}
    for name in NEW_METRICS:
        assert name in mine
        assert callable(harness.resolve(mine[name]["reader"]))
    for other in ("gpt2m-serve-chat", "falconh1-serve-reason"):
        theirs = {m["name"] for m, _ in harness.Cell(other).per_layer}
        assert not theirs & set(NEW_METRICS)
    for series in ("serving/kv_held_bytes", "serving/experts_hit",
                   "serving/expert_pairs", "serving/expert_load_max"):
        assert series in cell.series_names()
    # the metrics both serving cells report come along
    for name in ("decode_device_ms", "prefill_pad_share", "kv_used_share",
                 "hbm_peak.serve", "decode_chained_share"):
        assert name in mine
    assert [m["name"] for m in cell.end_to_end()] == [
        "gap_p50_ms", "serve_tokens_per_s", "setup_s"]


def test_the_mix_is_the_issues_table():
    from benchmark import traffic

    mix = harness.Cell(CELL).traffic
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 2048,
                                 "sigma": 0.9, "min": 256, "max": 6144}
    assert mix["output_len"] == {"dist": "lognormal", "median": 192,
                                 "sigma": 0.6, "min": 32, "max": 512}
    assert mix["sampling"] == {"every": 2, "temperature": 0.8, "top_k": 50}
    assert (mix["block_s"], mix["ramp_s"], mix["drain_limit_s"],
            mix["reference_sample"]) == (10, 20, 60, 4)
    assert mix["warmup_prompt_lens"] == [256, 400, 800, 1600, 3200, 6144]
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        < CONFIG["serve"]["max_len"]
    # every bucket from 256 to 8,192 is warmed, and hit by the mix
    from bigdl_tpu.serving.admission import bucket_len

    warmed = {bucket_len(n - 1, 8192) for n in mix["warmup_prompt_lens"]}
    assert warmed == {256, 512, 1024, 2048, 4096, 8192}
    drawn = traffic.length_set(mix["prompt_len"], 200)
    assert {bucket_len(n - 1, 8192) for n in drawn} == warmed
    assert 0.18 < np.mean([n > 4096 for n in drawn]) < 0.26
    sweep = mix["sweep"]
    assert abs(mix["arrivals"]["rate_per_s"]
               - sweep["share_of_knee"] * sweep["knee_per_s"]) < 0.051


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
    for name in NEW_METRICS[:4] + ("serve_mfu.trinity", "kv_used_share",
                                   "prefill_pad_share"):
        assert name in lines[-2]
