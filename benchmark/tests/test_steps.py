"""One record per decode dispatch: the join (``step_join.py``) on a
recorded chip trace of a small ``ServingEngine`` and on hand-made events,
and the readers over the window's four aligned series and over the join
(``readers/steps.py``) on synthetic ``obs``.

``data/steps.xplane.pb`` was written by ``data/record_steps.py`` on a TPU
v5 lite: 48 decode launches (``seq`` 14 to 61) of a 2-layer LM, a third
request's wave launched between ``seq`` 33 and 34, the trace stopped
with ``seq`` 61 in flight (no fence). The expected values are worked by
hand from the events the recorder printed, copied below."""

import json
import pathlib
import statistics

import pytest

from benchmark import harness, step_join
from benchmark.readers import steps as readers

DATA = pathlib.Path(__file__).parent / "data"
TRACE = DATA / "steps.xplane.pb"
PROGRAM = "jit_sample_step"

# (name, start_ns, duration_ns, run_id) on /device:TPU:0, XLA Modules
PROGRAM_33 = ("jit_sample_step", 69537706, 43977, 323)
BETWEEN_33_AND_34 = [                       # the wave and its slot's set-up
    ("jit_prefill", 72448415, 87121, 324),
    ("jit_convert_element_type", 72836786, 596, 325),
    ("jit_convert_element_type", 73172031, 593, 326),
    ("jit_convert_element_type", 73524986, 592, 327),
    ("jit__scatter_impl", 73849465, 5257, 328),
    ("jit_convert_element_type", 75378826, 641, 329),
    ("jit__threefry_seed", 75413534, 670, 330),
    ("jit_convert_element_type", 76072721, 712, 331),
    ("jit__threefry_fold_in", 76313964, 4840, 332),
    ("jit_convert_element_type", 77195774, 592, 333),
    ("jit_convert_element_type", 77328024, 592, 334),
    ("jit__squeeze", 77726897, 542, 335), ("jit_scatter", 78014284, 2986, 336),
    ("jit_convert_element_type", 79040559, 595, 337),
    ("jit_convert_element_type", 79148074, 593, 338),
    ("jit__squeeze", 79486871, 558, 339), ("jit_scatter", 79766366, 2482, 340),
    ("jit_convert_element_type", 81104026, 603, 341),
    ("jit_convert_element_type", 81119970, 594, 342),
    ("jit__squeeze", 81385326, 542, 343), ("jit_scatter", 81652462, 2492, 344)]
PROGRAM_34 = ("jit_sample_step", 85402927, 45232, 345)
PROGRAM_35 = ("jit_sample_step", 85785552, 44745, 346)
# (start_ns, duration_ns) on the python3 line, and DoEnqueueProgram starts
LAUNCH = {14: (48731017, 465220), 34: (83745805, 2822500),
          35: (86713795, 503980), 61: (117008043, 390490)}
FENCE = {14: (50620597, 471730), 33: (75319155, 343131),
         34: (87259825, 404050), 35: (88341275, 654100)}
ENQUEUE = {304: 49451133, 345: 86718501, 346: 87144861, 374: 117564330}


@pytest.fixture(scope="module")
def events():
    from jax.profiler import ProfileData

    return step_join.read_events(ProfileData.from_file(str(TRACE)), PROGRAM)


@pytest.fixture(scope="module")
def joined(events):
    steps, census = step_join.join(events)
    return {s["seq"]: s for s in steps}, census


# -- the join on the recorded chip trace --------------------------------------

def test_every_launch_is_joined_to_its_run_id_its_program_and_its_fence(
        events, joined):
    steps, census = joined
    assert census == {"launches": 48, "dropped_at_edges": 1, "matched": 47}
    assert sorted(steps) == list(range(14, 61))
    # the runtime numbers its executions as they are enqueued: the wave
    # between 33 and 34 took 21 run_ids, the finish before 47 two
    for seq, s in steps.items():
        assert s["run_id"] == seq + (290 if seq < 34 else
                                     311 if seq < 47 else 313), seq
    for seq, (name, start, duration, run) in (
            (33, PROGRAM_33), (34, PROGRAM_34), (35, PROGRAM_35)):
        assert steps[seq]["run_id"] == run
        assert steps[seq]["program"] == (start, start + duration)
    for seq, (start, duration) in FENCE.items():
        assert steps[seq]["fence"] == (start, start + duration)
    assert steps[14]["launch"] == (LAUNCH[14][0], sum(LAUNCH[14]))
    # an enqueue follows its launch's START and precedes its fence's END
    # on the host's clock; behind the wave it comes after the NEXT
    # launch has begun, which is why no rule of one launch's bracket
    # alone finds it
    enqueued = dict((run, t) for t, run in events["enqueues"])
    for s in steps.values():
        assert s["launch"][0] <= enqueued[s["run_id"]] <= s["fence"][1]
    assert enqueued[345] == ENQUEUE[345] > LAUNCH[35][0]
    assert enqueued[346] == ENQUEUE[346] < sum(LAUNCH[35])
    # the programs ran in launch order, every one of them joined once
    order = sorted(steps.values(), key=lambda s: s["program"][0])
    assert [s["seq"] for s in order] == sorted(steps)
    assert len({s["run_id"] for s in order}) == 47


def test_a_record_carries_the_launch_arguments_and_the_consume_notes(joined):
    steps, _ = joined
    assert {k: steps[34][k] for k in ("rows", "chained", "waves", "plain")} \
        == {"rows": 3, "chained": False, "waves": 1, "plain": False}
    assert {k: steps[35][k] for k in ("rows", "chained", "waves", "plain")} \
        == {"rows": 3, "chained": True, "waves": 0, "plain": True}
    assert (steps[14]["kv_held"], steps[14]["kv_fetched"]) == (78848, 524288)
    assert (steps[34]["kv_held"], steps[34]["kv_fetched"]) == (150528, 786432)
    assert steps[34]["experts_hit"] is None         # no expert family
    # 34 followed the wave and 47 a flush; the wave was launched while
    # dispatch 33 was in flight: its host time stands before fence 33
    # and in no program of that interval, so 33 is not plain either
    assert [s for s in steps if not steps[s]["plain"]] == [33, 34, 47]
    assert steps[33]["chained"] and steps[33]["waves"] == 0
    assert steps[33]["fence_to_fence_ns"] == 4413700 > \
        4 * steps[33]["interval_ns"] == 4 * 1060804
    assert steps[60]["plain"]           # 61 was dropped; its launch says 0


def test_the_split_sums_to_the_interval(joined):
    steps, _ = joined
    assert "interval_ns" not in steps[14]           # no predecessor joined
    for seq in range(15, 61):
        s = steps[seq]
        assert s["interval_ns"] == \
            s["program"][1] - steps[seq - 1]["program"][1]
        assert s["own_ns"] + sum(s["other_ns"].values()) + s["idle_ns"] \
            == pytest.approx(s["interval_ns"], abs=1e-6)
        assert s["own_ns"] == s["program"][1] - s["program"][0]
    # the wave's step, by hand: from the end of program 33 to the end
    # of program 34, the wave and what seated its row between them
    wave = steps[34]
    assert wave["interval_ns"] == \
        sum(PROGRAM_34[1:3]) - sum(PROGRAM_33[1:3]) == 15866476
    by_name = {}
    for name, _, duration, _ in BETWEEN_33_AND_34:
        by_name[name] = by_name.get(name, 0) + duration
    assert wave["other_ns"] == by_name
    assert by_name["jit_prefill"] == 87121 and by_name["jit_scatter"] == 7960
    assert wave["idle_ns"] == 15866476 - 45232 - sum(by_name.values())
    assert wave["fence_to_fence_ns"] == sum(FENCE[34]) - sum(FENCE[33])
    # the plain step behind it: its program started 337 us after the
    # wave step's ended, and nothing else ran
    after = steps[35]
    assert (after["own_ns"], after["other_ns"], after["idle_ns"]) == (
        44745, {}, PROGRAM_35[1] - sum(PROGRAM_34[1:3]))
    assert after["fence_to_fence_ns"] == sum(FENCE[35]) - sum(FENCE[34])


def test_the_ragged_edge_is_dropped(events, joined):
    """The trace stopped with dispatch 61 in flight: launched, enqueued
    and run, but never fenced inside the span."""
    steps, census = joined
    last = events["launches"][-1]
    assert last[2]["seq"] == 61 and last[0] == LAUNCH[61][0]
    assert 61 not in events["fences"] and 374 in events["programs"]
    assert 61 not in steps and census["dropped_at_edges"] == 1


def _without(events, **drop):
    """``events`` less the fences (``fences=[seq, ...]``) or the
    enqueues (``runs=[run_id, ...]``) named."""
    out = dict(events)
    out["fences"] = {k: v for k, v in events["fences"].items()
                     if k not in drop.get("fences", ())}
    out["enqueues"] = [e for e in events["enqueues"]
                       if e[1] not in drop.get("runs", ())]
    return out


@pytest.mark.parametrize("drop, census", [
    # one fence of 47 lost in the middle: 97.9% matched
    (dict(fences=[40]), {"launches": 48, "dropped_at_edges": 1,
                         "matched": 46}),
    # more lost edges than an end may have
    (dict(fences=[58, 59, 60]), {"launches": 48, "dropped_at_edges": 2,
                                 "matched": 44}),
    # an enqueue lost: its launch finds the next one only after its own
    # fence has returned, and takes none
    (dict(runs=[320]), {"launches": 48, "dropped_at_edges": 1,
                        "matched": 46}),
])
def test_under_99_percent_matched_there_is_no_join(events, drop, census):
    assert step_join.join(_without(events, **drop)) == (None, census)


def test_an_execution_no_launch_explains_breaks_the_join(events):
    """One to one: every execution of the program on the device between
    the first and the last joined one belongs to a joined launch."""
    _, end = events["programs"][320]
    stray = dict(events, programs={**events["programs"],
                                   9999: (end + 1000, end + 45000)})
    assert step_join.join(stray) == (
        None, {"launches": 48, "dropped_at_edges": 1, "matched": 47,
               "executions": 48})


def test_two_unmatched_launches_at_an_end_are_an_edge(events):
    steps, census = step_join.join(_without(events, fences=[14, 15, 60]))
    assert census == {"launches": 48, "dropped_at_edges": 4, "matched": 44}
    assert [s["seq"] for s in steps] == list(range(16, 60))
    assert "interval_ns" not in steps[0] and "interval_ns" in steps[1]


def test_a_trace_without_the_arguments_joins_to_none(events):
    """The parent's spans carry no ``seq``; a training trace has no
    launch at all; neither raises."""
    bare = dict(events, launches=[], fences={}, consumes={})
    assert step_join.join(bare) == (
        None, {"launches": 0, "dropped_at_edges": 0, "matched": 0})
    steps, census = step_join.join_file(
        str(DATA / "spans.xplane.pb"), "jit_step")
    assert steps is None and census["launches"] == 0


# -- the readers over the join ------------------------------------------------

@pytest.fixture
def traced_obs(monkeypatch):
    monkeypatch.setattr(readers.span_reduce, "newest_trace",
                        lambda root: TRACE)
    readers._joined.cache_clear()
    return {"trace": {"programs": {}}, "settings": {
        "decode_program": PROGRAM}, "series": {}}


def test_the_plain_step_parts_are_means_over_the_plain_joined_steps(
        traced_obs, joined, capsys):
    steps, _ = joined
    plain = [s for seq, s in steps.items() if seq not in (14, 33, 34, 47)]
    assert len(plain) == 43
    want = {
        "traced": statistics.mean(s["fence_to_fence_ns"] for s in plain),
        "program": statistics.mean(
            s["program"][1] - s["program"][0] for s in plain),
        "other": 0.0}
    want["idle"] = statistics.mean(
        s["interval_ns"] for s in plain) - want["program"]
    got = {part: readers.plain_step(traced_obs, {"part": part})
           for part in ("traced", "program", "other", "idle")}
    assert got == pytest.approx({k: v / 1e6 for k, v in want.items()})
    assert got["program"] == pytest.approx(0.04408, abs=1e-5)
    # the two clocks agree over the plain steps (1.2% here, on programs
    # of 44 us; with step 33 in, the host's side would read 1.192)
    assert got["traced"] == pytest.approx(1.1172, abs=1e-4)
    assert got["program"] + got["idle"] == pytest.approx(1.0976, abs=1e-4)
    lines = [json.loads(line.split("info ", 1)[1])
             for line in capsys.readouterr().out.splitlines()]
    assert {"step_join": {"launches": 48, "dropped_at_edges": 1,
                          "matched": 47, "joined": True,
                          "plain": 43}} in lines
    assert {"plain_step_other_ms_by_program": {}} in lines


def test_the_traced_load_is_set_against_the_windows(traced_obs, joined):
    steps, _ = joined
    rows = statistics.mean(s["rows"] for s in steps.values())
    assert rows == pytest.approx((13 * 3 + 34 * 2) / 47)
    n = 10
    traced_obs["series"] = {
        "serving/decode_gap_s": [0.001] * n, "serving/step_rows": [4.0] * n,
        "serving/step_waves": [0.0] * n, "serving/step_chained": [1.0] * n}
    assert readers.traced_load_gap_pct(traced_obs, {}) == \
        pytest.approx(100.0 * (4.0 - rows) / 4.0)
    for note in ("kv_held", "kv_fetched"):
        mean = statistics.mean(s[note] for s in steps.values())
        assert readers.traced_note(
            traced_obs, {"note": note, "scale": 1e-9}) == \
            pytest.approx(mean * 1e-9)
    # no expert family: no dispatch of the span carries the note
    assert readers.traced_note(
        traced_obs, {"note": "experts_hit", "scale": 1.0}) is None
    del traced_obs["series"]["serving/step_rows"]   # the parent's window
    assert readers.traced_load_gap_pct(traced_obs, {}) is None


def test_without_a_join_the_trace_readers_report_nothing(
        traced_obs, monkeypatch, capsys):
    assert readers.plain_step({"trace": None}, {"part": "idle"}) is None
    monkeypatch.setattr(readers.span_reduce, "newest_trace",
                        lambda root: DATA / "spans.xplane.pb")
    for part in ("traced", "program", "other", "idle"):
        assert readers.plain_step(traced_obs, {"part": part}) is None
    assert readers.traced_note(
        traced_obs, {"note": "kv_held", "scale": 1e-9}) is None
    assert readers.traced_load_gap_pct(traced_obs, {}) is None
    assert '"joined": false' in capsys.readouterr().out


# -- the readers over the window's aligned series -----------------------------

def _window(gap, rows, waves, chained):
    return {"series": {
        "serving/decode_gap_s": gap, "serving/step_rows": rows,
        "serving/step_waves": waves, "serving/step_chained": chained}}


def test_the_engine_gap_is_a_median_weighted_by_rows():
    """Three steps of one row at 2 ms and one step of five rows at 3 ms
    emitted eight tokens: five of them waited 3 ms."""
    obs = _window([0.002, 0.002, 0.002, 0.003], [1.0, 1.0, 1.0, 5.0],
                  [0.0] * 4, [1.0] * 4)
    assert readers.engine_gap_p50_ms(obs, {}) == pytest.approx(3.0)
    assert readers.plain_step_ms(obs, {}) == pytest.approx(2.0)
    obs = _window([0.002, 0.004], [3.0, 3.0], [0.0] * 2, [1.0] * 2)
    assert readers.engine_gap_p50_ms(obs, {}) == pytest.approx(3.0)


def test_a_waves_stall_and_the_stalled_share():
    """Plain steps of 2 ms (median), a step after a flush with no wave
    at 2.5, two steps behind a wave at 102 and 62 ms; the chained step
    in flight while the second wave was launched waited 10 ms at its
    fence, so it is no plain step (with it and the last, whose successor
    is unknown, the median would read 2.05) and its 8 ms are the
    admission's too."""
    gap = [0.002, 0.002, 0.0025, 0.102, 0.010, 0.062, 0.0021]
    waves = [0.0, 0.0, 0.0, 1.0, 0.0, 2.0, 0.0]
    chained = [1.0, 1.0, 0.0, 0.0, 1.0, 0.0, 1.0]
    obs = _window(gap, [2.0] * 7, waves, chained)
    assert readers.plain_step_ms(obs, {}) == pytest.approx(2.0)
    # device side (100 + 60) + host side (0.5 before the first wave,
    # 8 before the second), a wave step each
    assert readers.wave_stall_ms(obs, {}) == \
        pytest.approx((100.0 + 60.0 + 0.5 + 8.0) / 2)
    excess = 0.0005 + 0.100 + 0.008 + 0.060 + 0.0001
    assert readers.stalled_share(obs, {}) == \
        pytest.approx(100.0 * excess / sum(gap))
    # two waves running: the first's step is behind a wave itself, so
    # its excess counts once
    twice = _window([0.002, 0.002, 0.052, 0.042, 0.002], [2.0] * 5,
                    [0.0, 0.0, 1.0, 1.0, 0.0], [1.0, 1.0, 0.0, 0.0, 1.0])
    assert readers.wave_stall_ms(twice, {}) == pytest.approx((50 + 40) / 2)
    # a window without a wave has a plain step and no stall
    calm = _window(gap[:3], [2.0] * 3, waves[:3], chained[:3])
    assert readers.wave_stall_ms(calm, {}) is None
    assert readers.stalled_share(calm, {}) == \
        pytest.approx(100.0 * 0.0005 / sum(gap[:3]))


@pytest.mark.parametrize("series", [
    {},                                               # no engine series
    {"serving/decode_gap_s": [0.002, 0.003]},         # the parent's
    {"serving/decode_gap_s": [0.002, 0.003], "serving/step_rows": [1.0],
     "serving/step_waves": [0.0], "serving/step_chained": [1.0]},
    {"serving/decode_gap_s": [0.002], "serving/step_rows": [1.0],
     "serving/step_waves": [1.0], "serving/step_chained": [0.0]},
    {"serving/decode_gap_s": [0.002], "serving/step_rows": [1.0],
     "serving/step_waves": [0.0], "serving/step_chained": [1.0]},
])
def test_a_tree_without_the_series_reports_nothing(series):
    """Missing, of unequal length, or with no plain step in them (the
    last: its one sample has no successor to say that no wave was
    launched while it was in flight): every window reader returns None
    and none raises (the last two have a weighted median still)."""
    obs = {"series": series}
    for reader in (readers.plain_step_ms, readers.wave_stall_ms,
                   readers.stalled_share):
        assert reader(obs, {}) is None
    if len(series) == 4 and len(series["serving/decode_gap_s"]) == 1:
        assert readers.engine_gap_p50_ms(obs, {}) == pytest.approx(2.0)
    else:
        assert readers.engine_gap_p50_ms(obs, {}) is None


# -- BENCHMARK.json -----------------------------------------------------------

NEW = ["engine_gap_p50_ms", "plain_step_ms", "wave_stall_ms", "stalled_share",
       "plain_step_traced_ms", "plain_step_program_ms", "plain_step_other_ms",
       "plain_step_idle_ms", "traced_load_gap_pct", "traced_kv_held_gb",
       "traced_kv_fetched_gb", "traced_experts_hit"]
ROUTED = ["trinity-serve-mixed", "glm47flash-serve-longctx"]


def test_the_twelve_metrics_are_declared_with_their_files_and_series():
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    serving = [w["name"] for w in bench["workloads"] if "serve" in w["name"]]
    declared = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    assert [n for n in names if n in NEW] == NEW
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    for name in NEW:
        m = declared[name]
        assert m["layer"] in layers and m["better"] == "lower"
        assert m["workloads"] == (ROUTED if name in (
            "traced_kv_held_gb", "traced_experts_hit") else serving)
        spec = harness.load_json(
            harness.HERE / "layer_metrics" / f"{name}.json")
        assert spec["kinds"] == ["serve"]
        assert spec["reader"].startswith("benchmark.readers.steps:")
        from_window = m["source"] == "program_span" or \
            name == "traced_load_gap_pct"
        assert (spec.get("series") == [
            "serving/decode_gap_s", "serving/step_rows",
            "serving/step_waves", "serving/step_chained"]) == from_window
        assert m["source"] in ("program_span", "device_trace")
