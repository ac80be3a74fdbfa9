"""The trace reduction on a recorded chip trace: 4 ``jit_f`` programs of
~17.8 us on a TPU v5 lite, a host loop of ``tiny.step`` (dispatch and
read back) and ``tiny.sleep`` (3 ms) annotations. Expected values are
worked by hand from the events' start and duration."""

import pathlib

import pytest

from benchmark import trace_reduce

TRACE = pathlib.Path(__file__).parent / "data" / "tiny.xplane.pb"

# XLA Modules on /device:TPU:0: (start_ns, duration_ns)
MODULES = [(42511820, 17738), (47557157, 17853), (52132080, 17775),
           (56306526, 17743)]


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_file(TRACE)


def test_busy_and_window(reduced):
    busy_ns = sum(d for _, d in MODULES)                     # 71,109
    window_ns = MODULES[-1][0] + MODULES[-1][1] - MODULES[0][0]
    assert busy_ns == 71109 and window_ns == 13812449
    assert reduced["busy_s"] == pytest.approx(busy_ns / 1e9, rel=1e-9)
    assert reduced["window_s"] == pytest.approx(window_ns / 1e9, rel=1e-9)
    idle = 1 - reduced["busy_s"] / reduced["window_s"]
    assert idle == pytest.approx(0.994852, abs=1e-6)


def test_program_time(reduced):
    assert set(reduced["programs"]) == {"jit_f"}
    f = reduced["programs"]["jit_f"]
    assert f["count"] == 4
    assert f["mean_ms"] == pytest.approx(71109 / 4 / 1e6, rel=1e-9)


def test_gaps_are_attributed_to_the_sleep(reduced):
    # the three gaps between the four programs: 5,027,599 + 4,557,070 +
    # 4,156,671 ns. No host event covers more than half of any, and the
    # 3 ms tiny.sleep overlaps each gap more than the tiny.step before
    # it does (2,150,927 ns against 2,123,620 ns in the first).
    gaps_ns = [MODULES[i + 1][0] - (MODULES[i][0] + MODULES[i][1])
               for i in range(3)]
    assert gaps_ns == [5027599, 4557070, 4156671]
    assert reduced["gaps_total_s"] == pytest.approx(sum(gaps_ns) / 1e9)
    assert reduced["idle_gaps"] == [
        ["tiny.sleep", pytest.approx(sum(gaps_ns) / 1e9)]]


def test_top_operations_group_by_name_and_shape(reduced):
    names = [name for name, _ in reduced["device_ops"]]
    assert names[0] == "fusion bf16[1024,1024]{1,0}"
    # 4 x (15,759 + 15,871 + 15,794 + 15,761) / 4: one row, not four
    assert reduced["device_ops"][0][1] == pytest.approx(63185e-9)
    assert len(names) == len(set(names)) <= 10


def test_names():
    assert trace_reduce.program_name("jit_step(123)") == "jit_step"
    assert trace_reduce.op_name(
        "%copy.12 = bf16[32,1024,16,64]{3,2,1,0:T(8,128)(2,1)} copy(%x)") \
        == "copy bf16[32,1024,16,64]{3,2,1,0}"
    assert trace_reduce.op_name(
        "%fusion.3.1 = (f32[4]{0}, f32[4]{0}) fusion(%a)") == "fusion f32[4]{0}"


def test_innermost_covering_event_wins():
    events = [("outer", 0, 100), ("inner", 10, 80), ("other", 95, 50)]
    assert trace_reduce._attribute((20, 60), events) == "inner"
    assert trace_reduce._attribute((90, 140), events) == "other"
    assert trace_reduce._attribute((200, 300), events).startswith("(no event")


def test_no_device_plane_reads_nothing():
    class Empty:
        planes = []

    assert trace_reduce.reduce_profile(Empty()) is None
