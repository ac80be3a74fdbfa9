"""Dispatch-ahead decode window (ServingEngine ``dispatch_ahead=W``;
the engine's own default is ``DISPATCH_AHEAD`` = 1, W=0 is the oracle):
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


def test_window_zero_is_the_oracle_and_validated(lm, baseline):
    from bigdl_tpu.serving import ServingEngine

    eng, outs = _run(lm, dispatch_ahead=0)
    assert outs == baseline
    assert eng.dispatch_ahead == 0
    with pytest.raises(ValueError, match="dispatch_ahead"):
        ServingEngine(lm, n_slots=2, dispatch_ahead=-1)


# -- the default engine runs the window --------------------------------------

def _plain_decode_run(lm, **kw):
    """Two rows admitted together and decoded side by side for 24 and
    30 tokens: one admission, one finish before the end, long runs of
    plain decode steps between them."""
    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, n_slots=2, **kw)
    rids = [eng.submit([3, 7, 2], max_new_tokens=24),
            eng.submit([5, 1], max_new_tokens=30)]
    outs = eng.drain()
    return eng, [list(outs[r]) for r in rids]


def test_default_engine_keeps_one_decode_in_flight(lm, baseline):
    """Nothing passed: the engine and the disaggregated plane's decode
    workers run at DISPATCH_AHEAD = 1, the streams are the W=0
    streams, and nearly every dispatch of a plain-decode run chained on
    the in-flight one (the flushes: the first dispatch and the one
    after the 24-token row's finish)."""
    from bigdl_tpu.serving import DisaggregatedEngine
    from bigdl_tpu.serving.engine import DISPATCH_AHEAD

    assert DISPATCH_AHEAD == 1
    eng, outs = _run(lm)
    assert eng.dispatch_ahead == DISPATCH_AHEAD and outs == baseline
    d = DisaggregatedEngine(lm, prefill_slots=2, decode_slots=2)
    assert [w.engine.dispatch_ahead for w in d.decoders] == [DISPATCH_AHEAD]

    eng, plain = _plain_decode_run(lm)
    chained = eng.metrics.metrics.values("serving/decode_chained")
    assert len(chained) >= 30 and set(chained) == {0.0, 1.0}
    assert sum(chained) / len(chained) > 0.9
    assert chained.count(0.0) == 2
    eng0, plain0 = _plain_decode_run(lm, dispatch_ahead=0)
    assert plain == plain0
    chained0 = eng0.metrics.metrics.values("serving/decode_chained")
    assert len(chained0) == 30 and set(chained0) == {0.0}


def test_speculative_engine_records_no_chained_sample(lm):
    """The verify fence stays inline: a speculative engine makes no
    plain decode dispatch, so the series stays empty there."""
    from bigdl_tpu.serving import ServingEngine, SpeculativeConfig

    eng = ServingEngine(lm, n_slots=2,
                        speculative=SpeculativeConfig(_make_lm(seed=31),
                                                      k=3))
    eng.submit([3, 7, 2], max_new_tokens=6)
    eng.drain()
    assert eng.metrics.metrics.values("serving/decode_chained") == []
    assert not eng._window


@pytest.mark.parametrize("W", [0, 1, 2, 4])
def test_overshoot_is_not_counted(W, lm):
    """A row that finished one consume earlier is stepped once more by
    the dispatch already in flight and its token thrown away: it is no
    emitted token (``serving/batch_active`` sums to the tokens the
    requests got, whatever the depth), no sampled or greedy row, and an
    entry none of whose rows still runs leaves no step sample (no 0.0
    in the occupancy series)."""
    eng, outs = _run(lm, dispatch_ahead=W)
    m = eng.metrics.metrics
    n_tokens = sum(len(o) for o in outs)
    assert m.get("serving/batch_active")[0] == n_tokens
    sampled = sum(len(o) for o, (_, _, sp) in zip(outs, _trace())
                  if sp is not None)
    assert m.get("serving/rows_sampled")[0] == sampled
    assert m.get("serving/rows_greedy")[0] == n_tokens - sampled
    assert min(m.values("serving/slot_occupancy")) > 0.0
    assert min(m.values("serving/batch_active")) >= 1.0
    # every dispatch still leaves its paired split samples, kept or not
    assert m.get("serving/decode_step_s")[1] \
        == m.get("serving/decode_chained")[1]


def test_default_window_zero_new_compiles(lm):
    """The chained call has the shapes, dtypes and placement of the
    classical one: the default engine after a W=0 engine adds no
    program."""
    from tests.compile_guards import compile_count

    eng0, _ = _run(lm, dispatch_ahead=0)
    n0 = compile_count(eng0._step_fn)
    eng1, _ = _run(lm)
    assert eng1.dispatch_ahead >= 1
    assert compile_count(eng1._step_fn) == n0


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


# -- every reader of a RUNNING row flushes the window first -------------------
# (the device row is W tokens ahead of the emitted prefix while dispatches
# are in flight: ``ServingEngine.row_state`` is the one reader and
# refuses a window that is not empty)

def test_row_state_refuses_a_window_in_flight(lm):
    from bigdl_tpu.serving import ServingEngine

    eng = ServingEngine(lm, n_slots=2)
    eng.submit([3, 7, 2], max_new_tokens=12)
    for _ in range(3):
        eng.step()
    assert eng._window
    slot = next(iter(eng.scheduler.running))
    with pytest.raises(AssertionError, match="flush the window"):
        eng.row_state(slot)
    eng.flush_window()
    req = eng.scheduler.running[slot]
    payload = eng.row_state(slot)
    # settled: the row's device position is its emitted prefix's
    assert int(np.asarray(payload["carry"]["pos"]).ravel()[0]) \
        == len(req.prompt) + len(req.output) - 1


@pytest.mark.parametrize("W", [1, 2])
def test_window_tier_spill_byte_identity(W, lm, baseline):
    """Priority preemption through the HOST TIER (the spill packs
    ``row_state`` bytes): flushed before the victim is chosen, so the
    spilled row resumes on its emitted prefix and no token is lost."""
    from bigdl_tpu.serving import ServingEngine

    trace = _trace()
    eng = ServingEngine(lm, n_slots=2, policy="priority", tier=True,
                        dispatch_ahead=W)
    low = [eng.submit(p, max_new_tokens=n, sampling=sp)
           for p, n, sp in trace[:2]]
    for _ in range(4):
        eng.step()
    assert eng._window
    hi = [eng.submit(p, max_new_tokens=n, sampling=sp, priority=5)
          for p, n, sp in trace[2:]]
    drained = eng.drain()
    assert [list(drained[r]) for r in low + hi] == baseline
    s = eng.metrics.summary()
    assert s["serving/preempted"] >= 1 and s["serving/spills"] >= 1
    assert s["serving/resumed_without_prefill"] >= 1


@pytest.mark.disagg
@pytest.mark.parametrize("W", [1, 2])
def test_window_drain_pool_byte_identity(W, lm, baseline):
    """A graceful pool drain with dispatches in flight migrates every
    row on its emitted prefix: the tokens in flight are read back
    first, and the migrated streams are the W=0 streams."""
    from bigdl_tpu.serving import DisaggregatedEngine

    d = DisaggregatedEngine(lm, prefill_slots=4, decode_slots=2,
                            decode_pools=2, dispatch_ahead=W)
    rids = [d.submit(p, max_new_tokens=n, sampling=sp)
            for p, n, sp in _trace()]
    for _ in range(5):
        d.step()
    busy = max(range(2), key=lambda i: len(d.decoders[i].engine._window))
    assert d.decoders[busy].engine._window
    assert d.drain_pool(busy) >= 1
    assert not d.decoders[busy].engine._window
    outs = d.drain()
    assert [list(outs[r]) for r in rids] == baseline
    assert d.metrics.summary()["serving/migrated_rows"] >= 1


@pytest.mark.disagg
def test_window_failover_discards_what_was_in_flight(lm, baseline):
    """A pool killed with a dispatch in flight: nothing of it is read
    (the window is dropped unfenced), its rows restore or replay from
    their EMITTED prefixes on the survivor, byte-identically."""
    from bigdl_tpu.serving import DisaggregatedEngine

    d = DisaggregatedEngine(lm, prefill_slots=4, decode_slots=2,
                            decode_pools=2)
    rids = [d.submit(p, max_new_tokens=n, sampling=sp)
            for p, n, sp in _trace()]
    for _ in range(5):
        d.step()
    busy = max(range(2), key=lambda i: len(d.decoders[i].engine._window))
    assert d.decoders[busy].engine._window
    d.kill_pool(busy)
    outs = d.drain()
    assert not d.decoders[busy].engine._window
    assert [list(outs[r]) for r in rids] == baseline
    assert d.metrics.summary()["serving/pool_deaths"] == 1


def test_cancel_mid_window_freezes_the_stream(lm, baseline):
    """cancel() of a RUNNING row with its next token in flight: the
    slot frees at once, the token in flight is thrown away, and the
    next occupant of the slot serves its own stream."""
    from bigdl_tpu.serving import ServingEngine

    (p0, n0, s0), (p1, n1, s1) = _trace()[:2]
    eng = ServingEngine(lm, n_slots=1)
    a = eng.submit(p0, max_new_tokens=n0, sampling=s0)
    b = eng.submit(p1, max_new_tokens=n1, sampling=s1)
    for _ in range(4):
        eng.step()
    assert eng._window
    kept = list(eng.scheduler.running[0].output)
    assert eng.cancel(a)
    outs = eng.drain()
    assert list(eng.request(a).output) == kept == baseline[0][:len(kept)]
    assert list(outs[b]) == baseline[1]
    assert not eng._window


def test_service_time_estimate_prices_a_token_at_one_step(lm):
    """The dispatch-to-fence bracket of a CHAINED dispatch includes its
    wait behind the previous program (``decode_step_s``: up to W + 1
    steps long); what feasibility admission and deadline preemption
    multiply by the tokens left is the part in which the dispatch had
    the device. On a clock that ticks at every read: the estimate times
    the tokens served never exceeds the time the run took, which the
    bracket's median does under the window."""
    from bigdl_tpu.serving import ServingEngine, SteppingClock

    def run(W):
        clk = SteppingClock(0.001)
        eng = ServingEngine(lm, n_slots=1, clock=clk, dispatch_ahead=W)
        eng.submit([3, 7, 2], max_new_tokens=30)
        t0 = clk.t
        eng.drain()
        bracket = float(np.median(
            eng.metrics.metrics.values("serving/decode_step_s")))
        return eng.metrics.service_time_estimate(), bracket, clk.t - t0

    est0, bracket0, _ = run(0)
    assert est0 == pytest.approx(bracket0)       # W=0: the whole bracket
    for W in (1, 2):
        est, bracket, took = run(W)
        assert est * 30 <= took < bracket * 30
        assert est0 <= est < bracket


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
        "                                         *entry.extra)\n"
        "            now = self._clock()\n",
        "                                         *entry.extra)\n"
        "            now = 0.0\n")
    found = _scan(tmp_path)
    assert [f.code for f in found] == ["ASY310"], (
        [f.format() for f in found])
    assert found[0].path.endswith("engine.py")
