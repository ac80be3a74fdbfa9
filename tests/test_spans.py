"""The one span facility (``optim.Metrics.span`` / ``ServingMetrics.span``):
a bracket is a series sample and a profile event at once, the serving
plane's span names are a closed vocabulary, the training loop splits an
iteration into three phases of equal count, and the Pallas kernels and
jitted programs carry the names the benchmark's readers look for."""

import json
import pathlib

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent


class _Recorder:
    """Stands in for ``TraceAnnotation``: keeps (name, args, depth) of
    every span entered, in order, and what ``note`` added later."""

    def __init__(self):
        self.events, self.depth = [], 0

    def __call__(self, name, **ids):
        rec = self

        class _Ann:
            def __enter__(self):
                self.ev = [name, dict(ids), rec.depth]
                rec.events.append(self.ev)
                rec.depth += 1

            def set_metadata(self, **more):
                self.ev[1].update(more)

            def __exit__(self, *exc):
                rec.depth -= 1

        return _Ann()

    def names(self):
        return [e[0] for e in self.events]


@pytest.fixture
def recorder(monkeypatch):
    import bigdl_tpu.optim.metrics as m

    rec = _Recorder()
    monkeypatch.setattr(m, "TraceAnnotation", rec)
    monkeypatch.setattr(m, "StepTraceAnnotation", rec)
    return rec


# -- Metrics.span -------------------------------------------------------------

def test_span_records_series_on_the_metrics_clock_and_nests(recorder):
    from bigdl_tpu.optim.metrics import Metrics

    t = [0.0]
    m = Metrics(clock=lambda: t[0])
    with m.span("outer", "outer time", step_num=7):
        t[0] += 1.0
        with m.span("inner", "inner time", rid=3):
            t[0] += 0.25
        with m.span("bare"):                 # no series: profile only
            t[0] += 0.5
    assert m.values("outer time") == [1.75]
    assert m.values("inner time") == [0.25]
    assert m.get("bare") == (0.0, 0)
    assert recorder.events == [["outer", {"step_num": 7}, 0],
                               ["inner", {"rid": 3}, 1],
                               ["bare", {}, 1]]


def test_span_leaves_no_sample_on_an_exception_or_a_drop(recorder):
    from bigdl_tpu.optim.metrics import Metrics

    m = Metrics(clock=lambda: 0.0)
    with pytest.raises(StopIteration):
        with m.span("fetch", "fetch time"):
            raise StopIteration
    with m.span("admit", "admit time") as sp:
        sp.note(rids="4 5")
        sp.drop()
    assert m.get("fetch time") == (0.0, 0) == m.get("admit time")
    assert recorder.events[1][1] == {"rids": "4 5"}
    assert recorder.depth == 0               # both annotations were left


def test_span_is_a_real_trace_annotation_by_default():
    """Without a profile running the annotation is a disabled TraceMe:
    the bracket still works and still records."""
    from bigdl_tpu.optim.metrics import Metrics

    m = Metrics()
    with m.span("train.iteration", "t", step_num=1):
        with m.span("train.fetch"):
            pass
    assert m.get("t")[1] == 1


def test_serving_span_vocabulary_is_closed():
    from bigdl_tpu.serving.metrics import ServingMetrics, span

    with pytest.raises(ValueError, match="SPAN_NAMES"):
        ServingMetrics().span("prefill")
    with pytest.raises(ValueError, match="SPAN_NAMES"):
        span("pool.read")


def test_serving_span_keeps_add_phase_bookkeeping(recorder):
    """A phase span IS an add_phase sample: the DEVICE_PHASES sum and
    the decode-step window move exactly as a bare add_phase moves them."""
    from bigdl_tpu.optim.metrics import Metrics
    from bigdl_tpu.serving.metrics import ServingMetrics

    t = [10.0]
    sm = ServingMetrics(Metrics(clock=lambda: t[0]))
    with sm.span("fence", phase="fence_wait"):
        t[0] += 0.04
    with sm.span("admit", phase="admit_host"):
        t[0] += 0.01
    assert sm.metrics.values("serving/fence_wait_s") == [pytest.approx(0.04)]
    assert sm.metrics.values("serving/admit_host_s") == [pytest.approx(0.01)]
    assert sm.device_seconds == pytest.approx(0.04)   # admit is host time
    assert recorder.names() == ["serving.fence", "serving.admit"]


# -- the serving engine -------------------------------------------------------

def _make_lm(V=29, hidden=32, heads=4, layers=2, max_len=48, seed=9):
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.utils.random_gen import RNG

    RNG.set_seed(seed)
    lm = TransformerLM(V, hidden_size=hidden, n_heads=heads,
                       n_layers=layers, max_len=max_len)
    lm._ensure_params()
    lm.evaluate()
    return lm


#: what one step of the plain engine nests, by depth
SERVING_PARENT = {
    "serving.step": None, "serving.admit": "serving.step",
    "serving.prefill.launch": "serving.admit",
    "serving.decode.build": "serving.step",
    "serving.decode.launch": "serving.step",
    "serving.consume": "serving.step", "serving.fence": "serving.consume"}


def test_engine_emits_exactly_the_span_vocabulary(recorder):
    from bigdl_tpu.serving import SamplingParams, ServingEngine, VirtualClock
    from bigdl_tpu.serving.fences import SPAN_NAMES

    clk = VirtualClock()
    eng = ServingEngine(_make_lm(), n_slots=3, clock=clk)
    rng = np.random.RandomState(0)
    rids = [eng.submit(list(rng.randint(1, 29, size=n)), max_new_tokens=4,
                       sampling=SamplingParams(temperature=0.8, seed=i)
                       if i % 2 else None)
            for i, n in enumerate((5, 9, 3, 7, 6))]
    clk.advance(2.0)
    eng.drain()

    seen = set(recorder.names())
    assert seen == {f"serving.{n}" for n in SPAN_NAMES}
    # nesting: every span but pool.write has ONE parent; pool.write sits
    # under the admission (prefill scatter), the build (sampling lanes)
    # or the consumer (a finished row's slot reset)
    stack = []
    for name, _, depth in recorder.events:
        del stack[depth:]
        parent = stack[-1] if stack else None
        if name == "serving.pool.write":
            assert parent in ("serving.admit", "serving.decode.build",
                              "serving.consume")
        else:
            assert parent == SERVING_PARENT[name], (name, parent)
        stack.append(name)
    # arguments: the step number counts up; an admission names the
    # request ids it bound; a prefill its rows, padded rows and bucket
    steps = [ids["step"] for n, ids, _ in recorder.events
             if n == "serving.step"]
    assert steps == list(range(1, len(steps) + 1))
    bound = [int(r) for n, ids, _ in recorder.events
             if n == "serving.admit" and "rids" in ids
             for r in ids["rids"].split()]
    assert sorted(bound) == sorted(rids)
    waves = [ids for n, ids, _ in recorder.events
             if n == "serving.prefill.launch"]
    assert waves and all(
        set(w) == {"rows", "padded", "bucket"}
        and 1 <= w["rows"] <= w["padded"] for w in waves)


def test_queue_wait_one_sample_per_admission_on_the_engine_clock():
    from bigdl_tpu.serving import ServingEngine, VirtualClock

    clk = VirtualClock()
    eng = ServingEngine(_make_lm(), n_slots=2, clock=clk)
    for n in (4, 6, 5):                   # the third waits for a slot
        eng.submit(list(range(1, n + 1)), max_new_tokens=3)
    clk.advance(1.5)
    eng.step()
    m = eng.metrics.metrics
    assert m.values("serving/queue_wait_s") == [1.5, 1.5]
    clk.advance(0.5)
    eng.drain()
    waits = m.values("serving/queue_wait_s")
    assert len(waits) == 3 and waits[2] >= 2.0
    # admit_host_s: only the steps that bound a request leave a sample
    assert m.get("serving/admit_host_s")[1] == 2
    assert m.get("serving/fence_wait_s")[1] == \
        m.get("serving/decode_step_s")[1] == m.get("serving/host_step_s")[1]


def test_kv_used_share_is_sum_pos_over_reserved():
    """Computed from host state alone at each dispatch, for the
    positions the program reads and writes, it equals what the device
    holds once that dispatch has run: sum of ``pos`` over the in-use
    slots / (slots x max_len)."""
    from bigdl_tpu.serving import ServingEngine, VirtualClock

    eng = ServingEngine(_make_lm(), n_slots=4, clock=VirtualClock())
    for n in (5, 11, 2):
        eng.submit(list(range(1, n + 1)), max_new_tokens=8)
    for _ in range(3):
        eng.step()
        eng.flush_window()
        pos = np.asarray(eng.pool.carry["pos"])
        held = sum(int(pos[s]) for s in eng.scheduler.running)
        share = eng.metrics.metrics.values("serving/kv_used_share")[-1]
        assert share == pytest.approx(
            held / (eng.pool.n_slots * eng.pool.max_len))
    # the prompts less each one's fed token, plus three steps of three rows
    assert held == 5 + 11 + 2 - 3 + 3 * 3


# -- one record per decode dispatch -------------------------------------------

ALIGNED = ("serving/decode_gap_s", "serving/step_rows", "serving/step_waves",
           "serving/step_chained")


def _aligned(eng) -> int:
    """The four series gain their samples in one hook: one length."""
    lengths = {len(eng.metrics.metrics.values(n)) for n in ALIGNED}
    assert len(lengths) == 1, lengths
    return lengths.pop()


def _seqs(recorder, name):
    return [ids["seq"] for n, ids, _ in recorder.events
            if n == f"serving.{name}"]


def _check_record(recorder):
    """Launches are numbered 1, 2, ... in order; every consume holds the
    fence of the SAME dispatch; what was read back is a subset of what
    was launched, in launch order."""
    launches = _seqs(recorder, "decode.launch")
    assert launches == list(range(1, len(launches) + 1))
    consumes, fences = _seqs(recorder, "consume"), _seqs(recorder, "fence")
    assert consumes == fences == sorted(set(consumes))
    assert set(consumes) <= set(launches)
    for name, ids, _ in recorder.events:
        if name == "serving.decode.launch":
            assert set(ids) == {"seq", "rows", "chained", "waves"}
            assert ids["rows"] >= 1 and ids["chained"] in (0, 1)
    return launches, consumes


@pytest.mark.parametrize("ahead", [0, 1, 2])
def test_a_dispatch_carries_one_seq_from_launch_to_consume(recorder, ahead):
    """Across an admission (a flush; at depth 2 one of several entries
    in one step), a finish and the drain: launch, fence and consume of
    one dispatch carry one number, the consume notes the load as of the
    dispatch, and the four series stay of one length after every step."""
    from bigdl_tpu.serving import ServingEngine, VirtualClock

    eng = ServingEngine(_make_lm(), n_slots=3, clock=VirtualClock(),
                        dispatch_ahead=ahead)
    eng.submit([1, 2, 3, 4, 5], max_new_tokens=12)
    eng.submit([6, 7, 8, 9], max_new_tokens=5)      # finishes mid-run
    for _ in range(4):
        eng.step()
        _aligned(eng)
    eng.submit([9, 10, 11, 12], max_new_tokens=6)   # admission: a flush
    n_steps = eng._n_steps
    while not eng.idle():
        eng.step()
        _aligned(eng)
    eng.flush_window()
    launches, consumes = _check_record(recorder)
    assert consumes == launches         # nothing discarded: all read back
    m = eng.metrics.metrics
    # one gap sample a consumed dispatch but those that followed an idle
    # engine (the very first one here)
    assert _aligned(eng) == len(consumes) - 1
    # the admission's wave is counted once, on the first dispatch after
    # it, which was not chained; every other dispatch counts none
    waves = [ids["waves"] for n, ids, _ in recorder.events
             if n == "serving.decode.launch"]
    assert waves[0] == 1 and sum(waves) == 2 == \
        m.get("serving/prefill_batch")[1]
    wave_step = waves.index(1, 1)
    chained = [ids["chained"] for n, ids, _ in recorder.events
               if n == "serving.decode.launch"]
    assert chained[wave_step] == 0
    assert any(chained) == (ahead > 0)
    assert m.values("serving/step_waves")[wave_step - 1] == 1.0
    assert m.values("serving/step_chained") == \
        [float(c) for c in chained[1:]]
    # the series' rows are the launches' rows (overshoot rows included)
    rows = [ids["rows"] for n, ids, _ in recorder.events
            if n == "serving.decode.launch"]
    assert m.values("serving/step_rows") == [float(r) for r in rows[1:]]
    if ahead == 2:       # a flush read several entries back in one step
        per_step = {}
        stack = []
        for name, ids, depth in recorder.events:
            del stack[depth:]
            stack.append((name, ids))
            if name == "serving.consume":
                step = stack[0][1]["step"]
                per_step[step] = per_step.get(step, 0) + 1
        assert max(per_step.values()) >= 2 and n_steps in per_step
    notes = [ids for n, ids, _ in recorder.events if n == "serving.consume"]
    assert all(set(ids) == {"seq", "kv_held", "kv_fetched"} and
               ids["kv_fetched"] >= ids["kv_held"] > 0 for ids in notes)


@pytest.mark.faults
@pytest.mark.parametrize("ahead", [0, 1, 2])
def test_a_discarded_dispatch_keeps_its_seq_and_leaves_no_sample(
        recorder, ahead):
    """Garbage read back: the unhealthy entry's launch, fence and consume
    still carry one number; it and the entries chained behind it leave
    no gap sample, and the four series stay aligned through the replay."""
    from bigdl_tpu.serving import (
        FaultInjector, ServingEngine, VirtualClock, WatchdogConfig,
    )

    eng = ServingEngine(_make_lm(), n_slots=3, clock=VirtualClock(),
                        dispatch_ahead=ahead,
                        watchdog=WatchdogConfig(max_retries=None),
                        faults=FaultInjector(seed=4, p_garbage=0.3))
    for n in (5, 3, 7):
        eng.submit(list(range(1, n + 1)), max_new_tokens=8)
    while not eng.idle():
        eng.step()
        _aligned(eng)
    eng.flush_window()
    n_bad = eng._faults.counts["garbage"]
    assert n_bad >= 1
    launches, consumes = _check_record(recorder)
    # a discarded entry leaves no sample and takes the newer ones chained
    # behind it along, unread (launched, never fenced)
    assert _aligned(eng) <= len(consumes) - 1 - n_bad
    assert len(launches) >= len(consumes)
    if ahead == 2:
        assert len(launches) > len(consumes)
    assert all(len(r.output) == 8 for r in eng._finished.values())


def test_a_speculative_super_step_carries_its_seq_and_keeps_the_series_aligned(
        recorder):
    from bigdl_tpu.serving import (
        ServingEngine, SpeculativeConfig, VirtualClock,
    )

    eng = ServingEngine(_make_lm(), n_slots=3, clock=VirtualClock(),
                        speculative=SpeculativeConfig(_make_lm(seed=5), k=2))
    eng.submit([1, 2, 3, 4, 5], max_new_tokens=9)
    eng.submit([6, 7, 8, 9], max_new_tokens=4)      # one bucket: one wave
    for _ in range(2):
        eng.step()
        _aligned(eng)
    eng.submit([9, 10, 11], max_new_tokens=5)
    while not eng.idle():
        eng.step()
        _aligned(eng)
    launches = [ids for n, ids, _ in recorder.events
                if n == "serving.decode.launch"]
    assert [ids["seq"] for ids in launches] == \
        list(range(1, len(launches) + 1)) == _seqs(recorder, "fence")
    assert all(ids["chained"] == 0 for ids in launches)
    assert [ids["waves"] for ids in launches].count(1) == 2
    m = eng.metrics.metrics
    assert _aligned(eng) == len(launches) - 1
    assert m.values("serving/step_rows") == \
        [float(ids["rows"]) for ids in launches[1:]]
    assert set(m.values("serving/step_chained")) == {0.0}
    assert sum(m.values("serving/step_waves")) == 1.0


@pytest.mark.parametrize("kind", ["batched", "chunked", "prefix"])
def test_step_waves_counts_every_prefill_launch(kind):
    """A batched wave, each chunk of a streamed prompt and a prefix hit's
    suffix are one launch each, counted on the decode dispatch that
    follows them."""
    from bigdl_tpu.serving import ServingEngine, VirtualClock

    kw = {"batched": {}, "chunked": dict(admission="chunked", chunk_budget=4),
          "prefix": dict(prefix_cache=True)}[kind]
    eng = ServingEngine(_make_lm(), n_slots=3, clock=VirtualClock(), **kw)
    shared = [3, 1, 4, 1, 5, 9, 2, 6]
    eng.submit(shared + [5, 3], max_new_tokens=20)
    for _ in range(3):
        eng.step()
    m = eng.metrics.metrics
    before = m.get("serving/prefill_batch")[1]
    # arrives while the first decodes: 11 tokens to prefill (3 chunks of
    # <= 4; with the prefix cache the 8 shared ones are a hit and the
    # rest ONE suffix launch)
    eng.submit(shared + [8, 9, 7, 9], max_new_tokens=3)
    eng.drain()
    launched = m.get("serving/prefill_batch")[1] - before
    assert launched == {"batched": 1, "chunked": 3, "prefix": 1}[kind]
    if kind == "prefix":
        assert m.get("serving/prefix_hit_tokens")[0] == len(shared)
    waves = m.values("serving/step_waves")
    assert _aligned(eng) == len(waves)
    assert sum(waves) == launched and max(waves) >= 1.0
    # a wave or a suffix seats a row, so the step behind it was not
    # chained; a chunk leaves the running rows as they were, and the
    # decode in flight is extended past it
    behind = [c for c, w in zip(m.values("serving/step_chained"), waves) if w]
    assert (1.0 in behind) == (kind == "chunked")


def test_host_state_is_sampled_at_dispatch_for_the_rows_dispatched():
    """``on_step``'s host-state samples describe the program they are
    read beside: the rows it decoded at the positions it read, not the
    rows that run one dispatch later (an admission in between)."""
    from bigdl_tpu.serving import ServingEngine, VirtualClock

    eng = ServingEngine(_make_lm(), n_slots=4, clock=VirtualClock())
    eng.submit([1, 2, 3, 4, 5], max_new_tokens=10)
    for _ in range(3):
        eng.step()
    m = eng.metrics.metrics
    per_pos = eng.pool.kv_held_bytes(1)
    assert m.values("serving/kv_held_bytes") == \
        [per_pos * n for n in (5, 6)]
    eng.submit(list(range(1, 12)), max_new_tokens=4)
    eng.step()       # admits, then reads back the dispatch of ONE row
    assert m.values("serving/kv_held_bytes")[-1] == per_pos * 7
    assert m.values("serving/batch_active")[-1] == 1.0
    eng.step()       # the first dispatch of both rows
    assert m.values("serving/kv_held_bytes")[-1] == per_pos * (8 + 11)
    eng.step()       # chained: one position further than ``output`` says
    assert m.values("serving/kv_held_bytes")[-1] == per_pos * (9 + 12)
    n_samples = len(m.values("serving/kv_held_bytes"))
    assert n_samples == len(m.values("serving/kv_fetched_bytes")) == \
        len(m.values("serving/kv_used_share")) == \
        len(m.values("serving/batch_active"))
    eng.drain()


# -- the training loop --------------------------------------------------------

def _three_iterations(end_when):
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.nn import ClassNLLCriterion, Linear, LogSoftMax, Sequential
    from bigdl_tpu.optim import SGD, Optimizer

    rng = np.random.RandomState(1)
    samples = [Sample(rng.rand(8).astype(np.float32), np.int32(i % 3 + 1))
               for i in range(16)]
    model = Sequential().add(Linear(8, 3)).add(LogSoftMax())
    opt = Optimizer(model=model, dataset=DataSet.array(samples),
                    criterion=ClassNLLCriterion(), batch_size=4)
    opt.set_optim_method(SGD(learning_rate=0.1))
    opt.set_end_when(end_when)
    opt.optimize()
    return opt


@pytest.mark.parametrize("ahead", [False, True],
                         ids=["synchronous", "launched_ahead"])
def test_three_iterations_leave_equal_counts_in_every_phase(recorder, ahead):
    """One fetch, one dispatch, one sync and one sample of every series a
    STEP, whichever order the loop runs them in. An end trigger built
    without a peek reads results as far as the loop can tell, so every
    step is read before the next is launched: a ``train.iteration`` is
    fetch, dispatch, sync of ONE step. ``max_iteration`` is predictable
    from the counters, so the loop keeps a step in flight: the first
    pass launches step 1, the next two launch a step and read the one
    before, the last reads step 3."""
    from bigdl_tpu.optim import Trigger

    opt = _three_iterations(Trigger.max_iteration(3) if ahead
                            else Trigger(lambda s: s["neval"] > 3))

    counts = {n: opt.metrics.get(n)[1] for n in (
        "computing time", "data fetch time", "dispatch time",
        "loss sync time", "launched ahead")}
    assert set(counts.values()) == {3}, counts
    assert opt.metrics.values("launched ahead") == (
        [0.0, 1.0, 1.0] if ahead else [0.0, 0.0, 0.0])
    # the wall of a pass that reads a step holds its sync and the dispatch
    # made in it: the step's own, or launched ahead the NEXT step's
    wall, disp, sync = (opt.metrics.values(n) for n in (
        "computing time", "dispatch time", "loss sync time"))
    for i in range(3):
        inside = sync[i] + (disp[i] if not ahead else
                            disp[i + 1] if i < 2 else 0.0)
        assert wall[i] >= inside > 0.0
    its = [ids["step_num"] for n, ids, d in recorder.events
           if n == "train.iteration"]
    # a pass is named for the step it launches, else for the one it reads
    assert its == ([1, 2, 3, 3] if ahead else [1, 2, 3])
    inside = {n for n, _, d in recorder.events if d == 1}
    assert inside == {"train.fetch", "train.dispatch", "train.loss_sync"}
    phases = [n.split(".")[1] for n, _, d in recorder.events if d == 1]
    assert phases == (
        ["fetch", "dispatch", "fetch", "dispatch", "loss_sync",
         "fetch", "dispatch", "loss_sync", "loss_sync"] if ahead
        else ["fetch", "dispatch", "loss_sync"] * 3)
    # the feeder builds on a thread of its own and opens no span there:
    # train.fetch, the loop's wait for it, is the one fetch span, once a
    # step; the feeder's two series have a sample a batch handed over
    # (both peeks let it draw exactly the three)
    assert len(recorder.events) == len(its) + 3 * 3
    assert opt.metrics.get("batch build time")[1] == 3
    assert opt.metrics.get("input ready")[1] == 3
    assert set(opt.metrics.values("input ready")) <= {0.0, 1.0}


# -- names the benchmark's readers look for -----------------------------------

def _pallas_names(jaxpr):
    """Names of every pallas_call in a jaxpr, nested jaxprs included."""
    import jax

    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _pallas_names(sub)
    return out


def test_the_four_pallas_calls_carry_their_names():
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.decode_attention import decode_attention
    from bigdl_tpu.ops.flash_attention import flash_attention

    q = jnp.ones((1, 2, 128, 8), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, block=128,
                               interpret=True).sum()

    fwd = jax.make_jaxpr(loss)(q, q, q).jaxpr
    assert _pallas_names(fwd) == ["flash_fwd"]
    grad = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q).jaxpr
    assert sorted(_pallas_names(grad)) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]

    dec = jax.make_jaxpr(lambda q, k, v, pos: decode_attention(
        q, k, v, pos, impl="kernel", interpret=True, block=128))(
            jnp.ones((2, 2, 8)), jnp.ones((2, 128, 2, 8)),
            jnp.ones((2, 128, 2, 8)), jnp.array([3, 9], jnp.int32)).jaxpr
    assert _pallas_names(dec) == ["pooled_decode_attention"]


def _jit_name(fn) -> str:
    """The name a jitted callable's program carries in a profile."""
    inner = getattr(fn, "__wrapped__", fn)
    return "jit_" + getattr(inner, "__name__", "?")


def test_the_jitted_programs_carry_the_names_the_configs_use():
    """``jit_step`` / ``jit_sample_step`` are what the benchmark's
    configuration files point their readers at; ``jit_prefill`` and
    ``jit__scatter_impl`` are what its span readers look for — with an
    adapter bank too."""
    import jax

    from bigdl_tpu.nn import ClassNLLCriterion, Linear, LogSoftMax, Sequential
    from bigdl_tpu.optim import SGD, Optimizer
    from bigdl_tpu.serving import ServingEngine

    configs = ROOT / "benchmark" / "configs"
    wanted = {json.loads(p.read_text()).get(kind, {}).get(key)
              for p in configs.glob("*.json")
              for kind, key in (("train", "step_program"),
                                ("serve", "decode_program"))} - {None}
    assert wanted == {"jit_step", "jit_sample_step"}

    eng = ServingEngine(_make_lm(), n_slots=2)
    assert _jit_name(eng._step_fn) == "jit_sample_step"
    assert _jit_name(eng.pool._scatter) == "jit__scatter_impl"
    rid = eng.submit([1, 2, 3, 4, 5], max_new_tokens=2)
    eng.drain()
    assert len(eng.request(rid).output) == 2

    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.dataset.sample import Sample

    model = Sequential().add(Linear(4, 2)).add(LogSoftMax())
    samples = [Sample(np.zeros(4, np.float32), np.int32(1))] * 2
    opt = Optimizer(model=model, dataset=DataSet.array(samples),
                    criterion=ClassNLLCriterion(), batch_size=2)
    opt.set_optim_method(SGD(learning_rate=0.1))
    step = opt._prepare()[0]
    assert _jit_name(step) == "jit_step"
    assert isinstance(step, type(jax.jit(lambda x: x)))


@pytest.mark.parametrize("with_bank", [False, True])
def test_batch_prefill_compiles_as_jit_prefill(with_bank):
    """One program, one name: the adapter bank's arity-pinning wrapper
    does not rename the batch prefill (it used to compile as jit_run)."""
    import jax.numpy as jnp

    from bigdl_tpu.models.transformer import (
        make_batch_decode_step, make_batch_prefill_step, serving_params,
    )
    from bigdl_tpu.serving.lora import AdapterBank

    lm = _make_lm()
    bank = AdapterBank(lm, rank=2, n_slots=2) if with_bank else None
    spec = None if bank is None else bank.spec
    fn = make_batch_prefill_step(lm, adapter=spec)
    _, init_carry = make_batch_decode_step(lm, adapter=spec)
    args = [serving_params(lm, jnp.float32), jnp.zeros((2, 8), jnp.int32),
            jnp.array([3, 5], jnp.int32), init_carry(2)]
    if bank is not None:
        args += [jnp.zeros((2,), jnp.int32), bank.device_arrays()]
    assert _jit_name(fn._jitted) == "jit_prefill"
    assert "module @jit_prefill" in fn._jitted.lower(*args).as_text()
