"""The span readers: on hand-made spans and gaps, on synthetic ``obs``,
and on a recorded chip trace of the program's own training loop
(``data/spans.xplane.pb``, written by ``data/record_spans.py`` on a TPU
v5 lite: three traced iterations of one causal attention layer trained
through ``Optimizer.optimize()`` with flash attention). The trace's
expected values are worked by hand from the events the recorder
printed."""

import json
import pathlib

import pytest

from benchmark import harness, kernel_costs, span_reduce
from benchmark.readers import spans as readers

TRACE = pathlib.Path(__file__).parent / "data" / "spans.xplane.pb"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


# -- idle attribution on hand-made events -------------------------------------

def test_idle_is_cut_at_span_edges():
    """Two gaps against an iteration that holds a fetch and a dispatch:
    each piece goes to the innermost span over it and to every span
    over it; what no span covers is unattributed."""
    gaps = [(0, 100), (200, 260)]
    spans = [("train.iteration", 10, 250), ("train.fetch", 20, 60),
             ("train.dispatch", 210, 240)]
    innermost, under = span_reduce.attribute_idle(gaps, spans)
    assert innermost == {
        span_reduce.UNATTRIBUTED: 10 + 10,       # 0-10 and 250-260
        "train.fetch": 40,                       # 20-60
        "train.iteration": 10 + 40 + 10 + 10,    # 10-20 60-100 200-210 240-250
        "train.dispatch": 30}                    # 210-240
    assert sum(innermost.values()) == 100 + 60   # every idle ns counted once
    assert under == {"train.iteration": 90 + 50, "train.fetch": 40,
                     "train.dispatch": 30}


def test_same_name_spans_do_not_count_a_piece_twice():
    _, under = span_reduce.attribute_idle(
        [(0, 10)],
        [("serving.pool.write", 0, 6), ("serving.pool.write", 2, 5)])
    assert under == {"serving.pool.write": 6}


def test_ops_are_found_by_a_part_of_their_name_and_by_program():
    reduced = {"op_s": {
        "jit_step": {"jvp_flash_fwd_ bf16[64,1024,64]{2,1,0}": 0.030,
                     "transpose_jvp_flash_bwd_dq__ bf16[64,1024,64]{2,1,0}":
                         0.040,
                     "transpose_jvp_flash_bwd_dkv__ bf16[64,1024,64]{2,1,0}":
                         0.045,
                     "copy bf16[32,1024,16,64]{3,2,1,0}": 0.5},
        "jit_prefill": {"copy bf16[32,1024,16,64]{3,2,1,0}": 0.1,
                        "flash_fwd bf16[8,128,64]{2,1,0}": 0.002}}}
    assert span_reduce.op_seconds(reduced, ["flash_fwd"]) == \
        pytest.approx(0.032)
    assert span_reduce.op_seconds(
        reduced, ["flash_bwd_dq", "flash_bwd_dkv"]) == pytest.approx(0.085)
    copies = ["copy bf16[32,1024,16,64]"]
    assert span_reduce.op_seconds(reduced, copies) == pytest.approx(0.6)
    assert span_reduce.op_seconds(reduced, copies, "jit_prefill") == \
        pytest.approx(0.1)


# -- the recorded chip trace ---------------------------------------------------
#
# XLA Modules of /device:TPU:0, (start_ns, duration_ns): per iteration a
# convert_element_type and a threefry_fold_in (the rng fold_in) and jit_step
MODULES = [(44810425, 592), (45036750, 4910), (45441995, 23186),
           (46666063, 593), (46814316, 5032), (47821275, 23393),
           (49163791, 592), (49332495, 4896), (50498148, 23626)]
# the loop's spans on the python3 line, (start_ns, duration_ns)
ITERATION = [(44962040, 2269969), (47258669, 2571620), (49843829, 2587300)]
DISPATCH = [(44970820, 1676749), (47265220, 1820580), (49849349, 2016371)]
FETCH = [(46657149, 156471), (49107849, 231200), (51884140, 148180)]
LOSS_SYNC = [(46816980, 385140), (49343969, 472000), (52036929, 386111)]


@pytest.fixture(scope="module")
def reduced():
    return span_reduce.reduce_file(str(TRACE))


def test_trace_window_idle_and_span_counts(reduced):
    window = MODULES[-1][0] + MODULES[-1][1] - MODULES[0][0]
    busy = sum(d for _, d in MODULES)
    assert (window, busy) == (5711349, 86820)
    assert reduced["window_s"] == pytest.approx(window / 1e9, rel=1e-9)
    assert reduced["idle_s"] == pytest.approx((window - busy) / 1e9, rel=1e-9)
    for name, events in (("train.iteration", ITERATION),
                         ("train.dispatch", DISPATCH),
                         ("train.fetch", FETCH),
                         ("train.loss_sync", LOSS_SYNC)):
        assert reduced["spans"][name]["count"] == 3
        assert reduced["spans"][name]["total_s"] == pytest.approx(
            sum(d for _, d in events) / 1e9, rel=1e-9)


def test_trace_idle_by_innermost_span(reduced):
    """The eight gaps between the nine programs, cut at the spans' edges
    by hand (ns). E.g. the third, 45,465,181-46,666,063: under the first
    dispatch to 46,647,569 (1,182,388), under the iteration alone to the
    fetch's start 46,657,149 (9,580), under the fetch for the rest
    (8,914)."""
    hand = {
        "train.dispatch": 65930 + 400335 + 1182388 + 556055 + 1241132
                          + 648799,
        "train.fetch": 8914 + 146964 + 55942 + 168112 + 1658,
        "train.loss_sync": 382772 + 472000,
        "train.iteration": 8780 + 9580 + 696 + 29889 + 6551 + 22049 + 4920
                           + 14320 + 5520,
        # before the first iteration's span, and between iterations (the
        # end trigger): 44,811,017-44,962,040 and the two turn-arounds
        span_reduce.UNATTRIBUTED: 151023 + 26660 + 13540}
    assert sum(hand.values()) == 5711349 - 86820
    got = reduced["idle_innermost_s"]
    assert set(got) == set(hand)
    for name, ns in hand.items():
        assert got[name] == pytest.approx(ns / 1e9, rel=1e-9), name
    # under a span, whatever is nested in it: an iteration holds its phases
    under = reduced["idle_under_s"]
    assert under["train.iteration"] == pytest.approx(
        (5624529 - hand[span_reduce.UNATTRIBUTED]) / 1e9, rel=1e-9)
    for name in ("train.dispatch", "train.fetch", "train.loss_sync"):
        assert under[name] == got[name]          # leaves: nothing nested


def test_trace_names_the_flash_kernels_in_the_step_program(reduced):
    """``jax.grad`` wraps the kernels' names; the reader finds them by
    the part the program gave them. Durations (ns) of the three
    iterations' events, from the recorder's printout."""
    step = reduced["op_s"]["jit_step"]
    assert step["jvp_flash_fwd_ bf16[4,256,64]{2,1,0}"] == \
        pytest.approx((4528 + 4528 + 4530) / 1e9)
    assert span_reduce.op_seconds(reduced, ["flash_fwd"]) == \
        pytest.approx(13586e-9)
    assert span_reduce.op_seconds(reduced, ["flash_bwd_dq"]) == \
        pytest.approx((2263 + 2265 + 2265) / 1e9)
    assert span_reduce.op_seconds(reduced, ["flash_bwd_dkv"], "jit_step") \
        == pytest.approx(3 * 4701 / 1e9)
    assert span_reduce.op_seconds(reduced, ["flash"],
                                  "jit__threefry_fold_in") == 0.0


def test_readers_on_the_recorded_trace(monkeypatch):
    from benchmark import trace_reduce

    monkeypatch.setattr(span_reduce, "newest_trace", lambda root: TRACE)
    obs = {"trace": trace_reduce.reduce_file(TRACE), "peaks": PEAKS,
           "config": {"n_head": 2, "n_embd": 128, "n_layer": 1},
           "settings": {"batch_size": 2, "seq_len": 256,
                        "step_program": "jit_step"}}
    window = 5711349
    assert readers.idle_pct(obs, {"under": ["train.fetch"]}) == \
        pytest.approx(100 * 381590 / window)
    assert readers.idle_pct(obs, {"under": [
        "train.dispatch", "train.loss_sync"]}) == \
        pytest.approx(100 * (4094639 + 854772) / window)
    assert readers.idle_pct(obs, {"unattributed": True}) == \
        pytest.approx(100 * 191223 / window)
    assert readers.program_ms(obs, {"program": "jit_step"}) == \
        pytest.approx((23186 + 23393 + 23626) / 3 / 1e6)
    # 4 sequences of 256 x 64: 32,896 kept pairs each. At this length the
    # kernels are bound by bytes: forward 528,384 B an iteration (0.645 us
    # at 819 GB/s) against 33.7 MFLOP (0.171 us); backward 925,696 B
    args = {"costs": "benchmark.kernel_costs:flash_attention"}
    assert kernel_costs.roofline_seconds(33685504, 528384, PEAKS)[1] == \
        "memory"
    assert readers.kernel_roofline(
        obs, dict(args, ops=["flash_fwd"], **{"pass": "fwd"})) == \
        pytest.approx(100 * 3 * (528384 / 819e9) / 13586e-9)
    assert readers.kernel_roofline(
        obs, dict(args, ops=["flash_bwd_dq", "flash_bwd_dkv"],
                  **{"pass": "bwd"})) == \
        pytest.approx(100 * 3 * (925696 / 819e9) / 20896e-9)


# -- kernel costs against shapes counted by hand ------------------------------

def test_flash_costs_of_a_shape_small_enough_to_count():
    """2 heads x 4, 3 tokens, batch 1, 1 layer: 2 sequences, 6 of the 9
    (query, key) pairs survive the causal mask."""
    cost = kernel_costs.flash_attention(
        {"n_head": 2, "n_embd": 8, "n_layer": 1},
        {"batch_size": 1, "seq_len": 3})
    # forward: QK^T and PV, 2 x d = 8 operations a kept pair each
    assert cost["fwd"]["flops"] == 2 * (6 * 8 + 6 * 8) == 192
    # q, k, v read and o written (3 x 4 bf16 = 24 B each), lse 3 x f32
    assert cost["fwd"]["bytes"] == 2 * (4 * 24 + 12) == 216
    # backward: dP, dV, dQ, dK; the recomputed QK^T counts for nothing
    assert cost["bwd"]["flops"] == 2 * 4 * 48 == 384
    # q, k, v, dO read, dq, dk, dv written; lse and delta read
    assert cost["bwd"]["bytes"] == 2 * (7 * 24 + 2 * 12) == 384


def test_flash_costs_of_the_gpt2_medium_cell():
    cell = harness.Cell("gpt2m-train")
    cost = kernel_costs.flash_attention(cell.config, cell.settings)
    seqs = 4 * 16 * 24                      # batch x heads x layers
    pairs = 1024 * 1025 // 2
    assert cost["fwd"]["flops"] == seqs * 4 * 64 * pairs == 206359756800
    assert cost["bwd"]["flops"] == 2 * cost["fwd"]["flops"]
    assert cost["fwd"]["bytes"] == seqs * (4 * 1024 * 64 * 2 + 4096)
    least, bound = kernel_costs.roofline_seconds(**_fb(cost["fwd"]),
                                                 peaks=PEAKS)
    assert bound == "compute" and least == pytest.approx(1.0475e-3, rel=1e-3)


def _fb(cost):
    return {"flops": cost["flops"], "nbytes": cost["bytes"]}


def test_roofline_takes_the_larger_bound_and_says_which():
    assert kernel_costs.roofline_seconds(197e12, 1.0, PEAKS) == \
        (1.0, "compute")
    assert kernel_costs.roofline_seconds(1.0, 819e9 * 2, PEAKS) == \
        (2.0, "memory")


# -- the readers on synthetic observations ------------------------------------

def _obs(**extra):
    cell = harness.Cell("gpt2m-train")
    return dict(config=cell.config, settings=cell.settings, peaks=PEAKS,
                **extra)


def test_program_ms_per_call_and_per_another_programs_call():
    obs = {"trace": {"programs": {
        "jit_prefill": {"count": 5, "total_s": 0.5, "mean_ms": 100.0},
        "jit__scatter_impl": {"count": 6, "total_s": 0.012,
                              "mean_ms": 2.0}}}}
    assert readers.program_ms(obs, {"program": "jit_prefill"}) == \
        pytest.approx(100.0)
    assert readers.program_ms(obs, {"program": "jit__scatter_impl",
                                    "per": "jit_prefill"}) == \
        pytest.approx(12.0 / 5)
    # a program the trace does not hold, or no trace: nothing to read
    assert readers.program_ms(obs, {"program": "jit_run"}) is None
    assert readers.program_ms({"trace": None},
                              {"program": "jit_prefill"}) is None
    assert readers.program_ms({}, {"program": "jit_prefill"}) is None


def test_idle_and_roofline_readers(monkeypatch):
    reduced = {"window_s": 2.0,
               "idle_innermost_s": {span_reduce.UNATTRIBUTED: 0.01},
               "idle_under_s": {"train.dispatch": 0.06,
                                "train.loss_sync": 0.04,
                                "train.fetch": 0.002},
               "op_s": {"jit_step": {
                   "jvp_flash_fwd_ bf16[64,1024,64]{2,1,0}": 0.034}}}
    monkeypatch.setattr(readers, "_spans", lambda obs: reduced)
    obs = _obs(trace={"programs": {"jit_step": {"count": 5}}})
    assert readers.idle_pct(obs, {"unattributed": True}) == \
        pytest.approx(0.5)
    assert readers.idle_pct(obs, {"under": [
        "train.dispatch", "train.loss_sync"]}) == pytest.approx(5.0)
    assert readers.idle_pct(obs, {"under": ["train.validate"]}) == 0.0
    # 5 iterations x 1.0475 ms of operations at peak over 34 ms measured
    args = {"ops": ["flash_fwd"], "pass": "fwd",
            "costs": "benchmark.kernel_costs:flash_attention"}
    assert readers.kernel_roofline(obs, args) == \
        pytest.approx(100 * 5 * 1.0475e-3 / 0.034, rel=1e-3)
    # no operation of that name in the trace: nothing to read
    assert readers.kernel_roofline(obs, dict(args, ops=["flash_bwd"])) is None


def test_readers_report_nothing_without_spans_or_a_trace(monkeypatch):
    """The parent of the PR that added the spans, and a rehearsal on
    the CPU: every reader returns None and raises nothing."""
    args = {"ops": ["flash_fwd"], "pass": "fwd",
            "costs": "benchmark.kernel_costs:flash_attention"}
    for obs in (_obs(trace=None), _obs()):
        assert readers.idle_pct(obs, {"unattributed": True}) is None
        assert readers.kernel_roofline(obs, args) is None
    monkeypatch.setattr(readers, "_spans", lambda obs: None)
    obs = _obs(trace={"programs": {"jit_step": {"count": 5}}})
    assert readers.idle_pct(obs, {"under": ["train.fetch"]}) is None
    assert readers.kernel_roofline(obs, args) is None


# -- discovery: every declared per-layer metric resolves ----------------------

BENCH = harness.load_json(harness.ROOT / "BENCHMARK.json")
NEW = ["queue_wait_ms", "admit_host_ms", "prefill_device_ms",
       "pool_write_device_ms", "fence_wait_ms", "kv_used_share",
       "idle_in_admit_pct", "idle_unattributed.serve", "data_fetch_ms",
       "dispatch_ms", "loss_sync_ms", "idle_in_fetch_pct",
       "idle_in_dispatch_pct", "idle_unattributed.train",
       "flash_fwd_roofline", "flash_bwd_roofline"]


def test_the_sixteen_new_metrics_are_declared_last():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-16:] == NEW and len(names) == len(set(names)) == 36


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_file_resolves(metric):
    """Its file is there, names a reader that imports, lists the kind
    of every cell the metric is declared for, and the series it slices
    are the ones its reader is given."""
    spec = harness.load_json(
        harness.HERE / "layer_metrics" / f"{metric['name']}.json")
    assert callable(harness.resolve(spec["reader"]))
    cells = metric.get("workloads") or [w["name"] for w in BENCH["workloads"]]
    for name in cells:
        cell = harness.Cell(name)
        assert cell.kind in spec["kinds"]
        assert metric["name"] in [m["name"] for m, _ in cell.per_layer]
        assert set(spec.get("series", [])) <= set(cell.series_names())
    if spec["reader"] == "benchmark.readers.series:stat" \
            and "series" in spec["args"]:
        assert spec["series"] == [spec["args"]["series"]]
    if "costs" in spec.get("args", {}):
        assert callable(harness.resolve(spec["args"]["costs"]))
    json.dumps(spec)                       # data, nothing else
