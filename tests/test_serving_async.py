"""Dispatch-ahead decode window (ServingEngine ``dispatch_ahead=W``):
the async-readiness ledger CASHED IN.  Byte-identity is the acceptance
bar everywhere — W in {0, 1, 2} must produce identical streams across
greedy + fixed-seed sampled traces, slot recycling, priority
preemption, chunked admission, the speculative plane (structurally
W=0), the disaggregated plane, and fault/stall replay mid-window —
with ZERO new compiles (the window re-dispatches the same program on
device handles) and the host_step/fence_wait accounting split intact.

The machine-checked half: the ASY306-310 census strips each window
invariant out of the REAL serving tree in turn (inline stale consume,
literal depth bound, in-window fence, clock-blind consumer) and each
mutation must yield exactly ONE finding of the right code, while the
unmutated tree scans clean — so the analyzer tier actually guards the
engine shape this suite exercises, not a fixture-only idiom.

Determinism discipline matches test_serving_faults: seeded fault
schedules, VirtualClock stalls (no sleeps), ``max_retries=None`` so
truncated error-finishes can't masquerade as passing streams.
"""

from pathlib import Path

import numpy as np
import pytest

pytestmark = pytest.mark.asyncwin

REPO = Path(__file__).resolve().parent.parent
SERVING_DIR = REPO / "bigdl_tpu" / "serving"

WINDOW_CODES = ["ASY306", "ASY307", "ASY308", "ASY309", "ASY310"]


def _make_lm(V=29, hidden=32, heads=4, layers=2, max_len=48, seed=9):
    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.utils.random_gen import RNG

    RNG.set_seed(seed)
    lm = TransformerLM(V, hidden_size=hidden, n_heads=heads,
                       n_layers=layers, max_len=max_len)
    lm._ensure_params()
    lm.evaluate()
    return lm


@pytest.fixture(scope="module")
def lm():
    return _make_lm()


def _trace():
    """Mixed acceptance trace: greedy rows, fixed-seed sampled rows
    (penalties included), and a 1-token prompt — 4 requests through 2
    slots, so rows recycle mid-flight (the readmission path)."""
    from bigdl_tpu.serving import SamplingParams

    return [
        ([3, 7, 2], 10, None),
        ([5, 1], 8, SamplingParams(temperature=0.9, top_k=8, seed=123)),
        ([9], 6, None),
        ([4, 4, 4, 4], 9, SamplingParams(temperature=1.1, seed=7,
                                         repetition_penalty=1.2,
                                         frequency_penalty=0.2)),
    ]


def _run(lm, n_slots=2, **kw):
    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, n_slots=n_slots, **kw)
    rids = [eng.submit(p, max_new_tokens=n, sampling=sp)
            for p, n, sp in _trace()]
    outs = eng.drain()
    return eng, [list(outs[r]) for r in rids]


@pytest.fixture(scope="module")
def baseline(lm):
    """The W=0 streams — dispatch-then-fence within one step, the
    pre-window engine byte for byte."""
    _, outs = _run(lm)
    return outs


# -- byte-identity across window depths (THE acceptance contract) ----------

@pytest.mark.parametrize("W", [1, 2])
def test_window_byte_identity(W, lm, baseline):
    """W in-flight dispatches chained on device token handles: every
    finished stream — greedy AND fixed-seed sampled, slots recycling
    across 4 requests / 2 slots — equals the W=0 run byte for byte,
    and the window drains to empty with the pool healed."""
    eng, outs = _run(lm, dispatch_ahead=W)
    assert outs == baseline
    assert not eng._window
    assert eng.pool.free_slots == eng.pool.n_slots


def test_window_zero_is_the_default_and_validated(lm, baseline):
    from bigdl_tpu.serving import ServingEngine

    eng, outs = _run(lm, dispatch_ahead=0)
    assert outs == baseline
    assert eng.dispatch_ahead == 0
    with pytest.raises(ValueError, match="dispatch_ahead"):
        ServingEngine(lm, n_slots=2, dispatch_ahead=-1)


def test_window_zero_new_compiles(lm):
    """The window replays the SAME compiled decode program on device
    handles — a W=2 drain after a W=0 drain adds zero programs."""
    from tests.compile_guards import compile_count

    eng0, _ = _run(lm, dispatch_ahead=0)
    n0 = compile_count(eng0._step_fn)
    eng2, _ = _run(lm, dispatch_ahead=2)
    assert compile_count(eng2._step_fn) == n0


def test_window_preemption_byte_identity(lm, baseline):
    """Priority preemption mid-window: eviction breaks the window's
    row snapshot, the open-check drains it, and the preempted +
    readmitted streams still match the fault-free W=0 run."""
    from bigdl_tpu.serving import ServingEngine

    trace = _trace()
    eng = ServingEngine(lm, n_slots=2, policy="priority",
                        dispatch_ahead=2)
    low = [eng.submit(p, max_new_tokens=n, sampling=sp)
           for p, n, sp in trace[:2]]
    for _ in range(3):
        eng.step()
    hi = [eng.submit(p, max_new_tokens=n, sampling=sp, priority=5)
          for p, n, sp in trace[2:]]
    drained = eng.drain()
    assert [list(drained[r]) for r in low + hi] == baseline
    assert eng.metrics.summary()["serving/preempted"] >= 1


def test_window_chunked_admission_byte_identity(lm):
    """Chunked-prefill admission under the window: staggered submits
    land mid-flight (window drains on each admission), and W=2 equals
    the W=0 chunked run token for token."""
    from bigdl_tpu.serving import ServingEngine

    def run(W):
        eng = ServingEngine(lm, n_slots=2, admission="chunked",
                            chunk_budget=5, dispatch_ahead=W)
        ids = [eng.submit(p, max_new_tokens=n, sampling=sp)
               for p, n, sp in _trace()[:2]]
        eng.step(); eng.step()
        ids += [eng.submit(p, max_new_tokens=n, sampling=sp)
                for p, n, sp in _trace()[2:]]
        outs = eng.drain()
        assert eng.pool.free_slots == eng.pool.n_slots
        return [list(outs[r]) for r in ids]

    assert run(2) == run(0)


def test_window_speculative_plane_byte_identity(lm, baseline):
    """The speculative plane is structurally W=0 (draft budgets are
    host decisions from the previous verify readback) — the knob must
    be inert there, not harmful."""
    from bigdl_tpu.serving import ServingEngine, SpeculativeConfig

    draft = _make_lm(seed=31)
    eng = ServingEngine(lm, n_slots=2,
                        speculative=SpeculativeConfig(draft, k=3),
                        dispatch_ahead=2)
    rids = [eng.submit(p, max_new_tokens=n, sampling=sp)
            for p, n, sp in _trace()]
    outs = eng.drain()
    assert [list(outs[r]) for r in rids] == baseline
    assert not eng._window


@pytest.mark.disagg
def test_window_disagg_byte_identity(lm, baseline):
    """The disaggregated plane threads dispatch_ahead to every decode
    worker; handoffs and cross-pool routing under the window stay
    byte-identical to the monolithic W=0 run."""
    from bigdl_tpu.serving import DisaggregatedEngine

    d = DisaggregatedEngine(lm, prefill_slots=4, decode_slots=2,
                            decode_pools=2, dispatch_ahead=2)
    rids = [d.submit(p, max_new_tokens=n, sampling=sp)
            for p, n, sp in _trace()]
    outs = d.drain()
    assert [list(outs[r]) for r in rids] == baseline
    for w in d.decoders:
        assert w.engine.dispatch_ahead == 2
        assert not w.engine._window


# -- faults mid-window ------------------------------------------------------

@pytest.mark.faults
@pytest.mark.parametrize("seed", [1, 3])
def test_faults_mid_window_byte_identity(seed, lm, baseline):
    """Dispatch failures and garbage readbacks with W=2 in flight: a
    failed dispatch flushes the (healthy) window first, an unhealthy
    consumed entry discards every newer entry chained through the
    poisoned carry — and replay restores the exact streams."""
    from bigdl_tpu.serving import FaultInjector, WatchdogConfig

    eng, outs = _run(lm, dispatch_ahead=2,
                     watchdog=WatchdogConfig(max_retries=None),
                     faults=FaultInjector(seed=seed, p_fail=0.25,
                                          p_garbage=0.15))
    assert eng._faults.total > 0
    assert outs == baseline
    assert eng.metrics.summary()["serving/recovered_rows"] > 0
    assert eng.pool.free_slots == eng.pool.n_slots


@pytest.mark.faults
def test_stall_watchdog_fires_through_deferred_fence(lm, baseline):
    """A stalled in-flight dispatch (VirtualClock advance, no sleeps)
    surfaces at the DELAYED consumer: elapsed spans dispatch →
    readback landed, so step_timeout_s still trips with the fence a
    full window behind the dispatch, and replay restores the exact
    streams."""
    from bigdl_tpu.serving import (
        FaultInjector, VirtualClock, WatchdogConfig,
    )

    clk = VirtualClock()
    eng, outs = _run(
        lm, dispatch_ahead=2, clock=clk,
        watchdog=WatchdogConfig(step_timeout_s=5.0, max_retries=None),
        faults=FaultInjector(seed=6, p_stall=0.35, stall_s=30.0,
                             clock=clk))
    assert eng._faults.counts["stall"] > 0
    assert outs == baseline


# -- the accounting split under the window ----------------------------------

def test_host_split_pairing_survives_window(lm):
    """The host_step/decode_step/fence_wait series stay paired one for
    one at W=2 (flush steps pad host_step with zero-residue samples),
    and the device phases are the BLOCKED phases: fence_wait counts
    once per consumed entry while decode_step — which OVERLAPS host
    work under a window — no longer feeds device_seconds."""
    from bigdl_tpu.serving.metrics import ServingMetrics

    assert "fence_wait" in ServingMetrics.DEVICE_PHASES
    assert "decode_step" not in ServingMetrics.DEVICE_PHASES

    eng, _ = _run(lm, dispatch_ahead=2)
    m = eng.metrics.metrics
    _, n_host = m.get("serving/host_step_s")
    _, n_dec = m.get("serving/decode_step_s")
    _, n_fence = m.get("serving/fence_wait_s")
    assert n_host == n_dec == n_fence >= 4
    assert eng.metrics.device_seconds >= 0.0
    s = eng.metrics.summary()
    assert s["serving/host_step_p50_s"] <= s["serving/host_step_p99_s"]


# -- the ASY306-310 census over the REAL engine ------------------------------

def _serving_tree(tmp_path):
    dst = tmp_path / "bigdl_tpu" / "serving"
    dst.mkdir(parents=True)
    for f in SERVING_DIR.glob("*.py"):
        (dst / f.name).write_text(f.read_text())
    return dst


def _scan(tmp_path):
    from bigdl_tpu.analysis import analyze_paths

    return analyze_paths([str(tmp_path)], select=WINDOW_CODES)


def _mutate(tree, needle, repl):
    eng = tree / "engine.py"
    src = eng.read_text()
    assert src.count(needle) == 1, f"census anchor drifted: {needle!r}"
    eng.write_text(src.replace(needle, repl))
    return src


def test_window_census_unmutated_engine_is_clean(tmp_path):
    tree = _serving_tree(tmp_path)
    assert tree.is_dir()
    clean = _scan(tmp_path)
    assert clean == [], [f.format() for f in clean]


def test_window_census_exactly_one_delayed_site(capsys, monkeypatch):
    """The sync-point inventory proves exactly ONE declared
    delayed-consumer site in the whole serving plane: the decode fence
    in ServingEngine._consume_window, depth-bound by dispatch_ahead;
    every other declared fence is an inline consumer."""
    import json

    from bigdl_tpu.analysis import main

    monkeypatch.chdir(REPO)
    rc = main(["bigdl_tpu/serving", "--report", "sync-points",
               "--format", "json"])
    rep = json.loads(capsys.readouterr().out)
    assert rc == 0
    delayed = [e for e in rep["entries"]
               if e.get("window", "").startswith("delayed")]
    assert len(delayed) == 1
    e = delayed[0]
    assert e["kind"] == "fence:decode"
    assert e["function"].endswith("ServingEngine._consume_window")
    assert "dispatch_ahead" in e["window"]
    inline = [e for e in rep["entries"] if e.get("window") == "inline"]
    assert len(inline) == len(rep["entries"]) - 1


def test_window_census_stale_consumer_detected(tmp_path):
    """Inline-consume-and-redispatch (the re-serializing shape the
    window exists to forbid) -> exactly one ASY306."""
    tree = _serving_tree(tmp_path)
    _mutate(
        tree,
        "                    self._advance_constraint(slot, req)\n"
        "            return True\n",
        "                    self._advance_constraint(slot, req)\n"
        "            self._dispatch(\"decode\", self._step_fn, self.params,\n"
        "                           jnp.asarray(nxt), entry.active_dev,\n"
        "                           self.pool.carry, self._knobs_device)\n"
        "            return True\n")
    found = _scan(tmp_path)
    assert [f.code for f in found] == ["ASY306"], (
        [f.format() for f in found])
    assert found[0].path.endswith("engine.py")


def test_window_census_literal_depth_detected(tmp_path):
    """The consume loop bound by a literal instead of the declared
    dispatch_ahead knob -> exactly one ASY308."""
    tree = _serving_tree(tmp_path)
    _mutate(
        tree,
        "            while len(self._window) > self.dispatch_ahead:\n"
        "                if not self._consume_window(emitted):\n"
        "                    break\n",
        "            while len(self._window) > 2:\n"
        "                if not self._consume_window(emitted):\n"
        "                    break\n")
    found = _scan(tmp_path)
    assert [f.code for f in found] == ["ASY308"], (
        [f.format() for f in found])
    assert found[0].path.endswith("engine.py")


def test_window_census_inwindow_fence_detected(tmp_path):
    """An eager readback inserted between dispatch and append (inside
    the owning unit) re-serializes the window -> exactly one ASY309."""
    tree = _serving_tree(tmp_path)
    _mutate(
        tree,
        "            self.pool.carry = carry\n",
        "            self.pool.carry = carry\n"
        "            nxt0, lps0 = fence(\"verify\", tok, chosen)\n")
    found = _scan(tmp_path)
    assert [f.code for f in found] == ["ASY309"], (
        [f.format() for f in found])
    assert found[0].path.endswith("engine.py")


def test_window_census_clock_blind_consumer_detected(tmp_path):
    """Stripping the consumer's clock read (a constant instead of the
    engine-clock read after the fence; the fence_wait bracket is the
    ``fence`` span's) blinds the watchdog's elapsed -> exactly one
    ASY310 at the deferred fence."""
    tree = _serving_tree(tmp_path)
    _mutate(
        tree,
        "                nxt, lps = fence(\"decode\", entry.tok, "
        "entry.chosen)\n"
        "            now = self._clock()\n",
        "                nxt, lps = fence(\"decode\", entry.tok, "
        "entry.chosen)\n"
        "            now = 0.0\n")
    found = _scan(tmp_path)
    assert [f.code for f in found] == ["ASY310"], (
        [f.format() for f in found])
    assert found[0].path.endswith("engine.py")
