"""The new cell's files: the plain Falcon-H1 reference against the
program's own forward at the rehearsal's toy size on the CPU, the
configuration against the catalog's published keys, the operation and
byte counts against hand-worked values, the readers on made-up
observations, and the cell's ``--rehearse-cpu`` run."""

import os
import subprocess
import sys

import numpy as np

from benchmark import harness, reference, serve_costs, serve_flops
from benchmark.readers import serve_roofline

CELL = "falconh1-serve-reason"
CONFIG = harness.load_json(harness.HERE / "configs" / "falcon-h1-34b.json")


def test_reference_agrees_with_the_program_in_float32():
    import jax.numpy as jnp

    from bigdl_tpu.models.falcon_h1 import FalconH1LM
    from bigdl_tpu.utils.random_gen import RNG

    cfg = harness.Cell(CELL, rehearsal=True).config
    RNG.set_seed(3)
    lm = FalconH1LM(cfg, max_len=cfg["serve"]["max_len"])      # float32
    lm._ensure_params()
    lm.evaluate()
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        1, cfg["vocab_size"] + 1, size=(2, 45)), jnp.int32)
    want = np.asarray(lm.apply(lm.params, tokens)[0][0])
    got = np.asarray(reference.load_reference(cfg).logits_at(
        lm.params, tokens[0], jnp.arange(45), cfg))
    # both float32 on the CPU: agreement to rounding. The published
    # multipliers make the toy logits of order 1e-3, so the 1e-5 is of
    # their spread, not absolute
    assert np.abs(got - want).max() < 1e-5 * want.std()
    # the factory makes the cell's model: bfloat16 leaves as published
    served = harness.resolve(cfg["model"]["factory"])(cfg)
    assert served.param_dtype == "bfloat16"
    assert served.max_len == cfg["serve"]["max_len"]


def test_configuration_keeps_every_published_number():
    import json

    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    published = {
        "hidden_size": 5120, "intermediate_size": 21504,
        "num_attention_heads": 20, "num_key_value_heads": 4, "head_dim": 128,
        "mamba_n_heads": 32, "mamba_d_head": 128, "mamba_d_state": 256,
        "mamba_n_groups": 2, "mamba_d_conv": 4, "mamba_chunk_size": 128,
        "mamba_d_ssm": 4096, "rope_theta": 100000000000}
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Falcon-H1-34B-Instruct")
        published = row["config"]
        assert CONFIG["source"] == row["source_url"]
    assert CONFIG["reduced"] == ["num_hidden_layers", "vocab_size"]
    for key, value in published.items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert CONFIG["published"] == {"num_hidden_layers": 72,
                                   "vocab_size": 261120}
    # the floors: a whole period, at least an eighth of the vocabulary
    assert CONFIG["num_hidden_layers"] == 8
    assert CONFIG["vocab_size"] * 8 == CONFIG["published"]["vocab_size"]


def test_operation_and_byte_counts():
    layer = 5120 * 9248 + 4096 * 5120 + 5120 * (2560 + 512 + 512) \
        + 2560 * 5120 + 3 * 5120 * 21504
    assert layer == 430_080_000
    n = serve_flops.falcon_h1_matmul_params(CONFIG)
    assert n == 8 * layer + 5120 * 32640 == 3_607_756_800
    per_layer = 4 * 20 * 128 * 300 + 5 * 32 * 128 * 256 + 2 * 4 * 5120
    assert serve_flops.falcon_h1_flops_per_token(CONFIG, 300) \
        == 2.0 * n + 8 * per_layer
    # ten rows decoding with 3,000 positions between them
    state = 32 * 128 * 256 * 4 + 3 * 5120 * 2
    want = n * 2 + 10 * 5120 * 2 + 8 * 10 * 2 * state \
        + 8 * (2 * 4 * 128 * 2) * (3000 + 10) + 2 * 10 * 32640 * 4
    cost = serve_costs.falcon_h1_decode_step(CONFIG, CONFIG["serve"], 10, 3000)
    assert cost["bytes"] == want
    assert cost["flops"] == 10 * serve_flops.falcon_h1_flops_per_token(
        CONFIG, 300)
    # the weights lead: 7.2 GB of the step's 7.9
    assert 0.9 < n * 2 / want < 0.92


def _obs(**over):
    cell = harness.Cell(CELL)
    obs = dict(config=cell.config, settings=cell.settings,
               traffic=cell.traffic, chips=1,
               peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
               series={"serving/slot_occupancy": [10 / 32] * 4,
                       "serving/kv_used_share": [3000 / (32 * 1024)] * 4,
                       "serving/batch_active": [10.0] * 4,
                       "serving/prefill_batch": [1.0, 1.0]},
               spans={"steps": [(0.0, 0.02, 0), (0.02, 0.08, 1)]},
               trace={"programs": {"jit_sample_step": {"mean_ms": 20.0}}})
    obs.update(over)
    return obs


def test_decode_roofline_reader():
    cost = serve_costs.falcon_h1_decode_step(CONFIG, CONFIG["serve"], 10, 3000)
    got = serve_roofline.decode_roofline(
        _obs(), {"costs": "benchmark.serve_costs:falcon_h1_decode_step"})
    assert abs(got - 100 * (cost["bytes"] / 819e9) / 20e-3) < 1e-9
    assert 45 < got < 50
    # nothing to read: no trace, no such program, no series
    args = {"costs": "benchmark.serve_costs:falcon_h1_decode_step"}
    assert serve_roofline.decode_roofline(_obs(trace=None), args) is None
    assert serve_roofline.decode_roofline(
        _obs(trace={"programs": {}}), args) is None
    assert serve_roofline.decode_roofline(_obs(series={}), args) is None


def test_serve_mfu_reader():
    from benchmark import traffic

    args = {"flops": "benchmark.serve_flops:falcon_h1_flops_per_token"}
    got = serve_roofline.serve_mfu(_obs(), args)
    mix = harness.Cell(CELL).traffic
    prompts = [n - 1 for n in traffic.length_set(
        mix["prompt_len"], traffic.block_size(mix))]
    mean_prompt = sum(prompts) / len(prompts)
    # 40 emitted tokens and two requests' prompts in 0.08 s
    tokens = 40 + 2 * mean_prompt
    floor = 100 * tokens * 2 * serve_flops.falcon_h1_matmul_params(CONFIG) \
        / (0.08 * 197e12)
    assert floor < got < 1.02 * floor       # the matmuls lead
    assert serve_roofline.serve_mfu(_obs(series={}), args) is None


def test_new_metrics_are_declared_for_the_new_cell_only():
    cell = harness.Cell(CELL)
    mine = {m["name"]: spec for m, spec in cell.per_layer}
    for name in ("state_in_use_gb", "decode_hbm_roofline", "serve_mfu"):
        assert name in mine
        assert callable(harness.resolve(mine[name]["reader"]))
    other = {m["name"] for m, _ in harness.Cell("gpt2m-serve-chat").per_layer}
    assert not other & {"state_in_use_gb", "decode_hbm_roofline", "serve_mfu"}
    assert "serving/state_in_use_bytes" in cell.series_names()


def test_the_mix_is_the_issues_table():
    mix = harness.Cell(CELL).traffic
    assert mix["arrivals"]["process"] == "poisson"
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 64,
                                 "sigma": 0.7, "min": 16, "max": 256}
    assert mix["output_len"] == {"dist": "lognormal", "median": 384,
                                 "sigma": 0.5, "min": 128, "max": 512}
    assert mix["sampling"] == {"every": 2, "temperature": 0.8, "top_k": 50}
    assert (mix["block_s"], mix["ramp_s"], mix["drain_limit_s"],
            mix["reference_sample"]) == (10, 20, 30, 4)
    assert mix["warmup_prompt_lens"] == [16, 32, 64, 128, 256]
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        < CONFIG["serve"]["max_len"]
    sweep = mix["sweep"]
    assert abs(mix["arrivals"]["rate_per_s"]
               - sweep["share_of_knee"] * sweep["knee_per_s"]) < 0.051


def test_rehearsal_exits_4_and_prints_no_result():
    p = subprocess.run(
        [sys.executable, str(harness.HERE / "run.py"), "--workload", CELL,
         "--rehearse-cpu", "--seed", "3000000019", "--seconds", "1",
         "--trace", "1"], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=harness.ROOT,
        timeout=400)
    assert p.returncode == harness.REHEARSAL_EXIT, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    assert lines and all(ln.startswith(harness.REHEARSAL_TAG)
                         for ln in lines)
    assert not [ln for ln in lines if ln.startswith("{")]
    assert '"correct": true' in lines[-2], lines[-2]
    assert '"compiled_in_window": 0' in p.stdout
    for name in ("state_in_use_gb", "serve_mfu", "kv_used_share"):
        assert name in lines[-2]
