"""Records ``steps.xplane.pb``: a few dozen decode steps of a small
``ServingEngine`` on the chip, one prefill wave among them, with the
profiler's Python frames and jax's own host events off.

    chiprun -- python3 benchmark/tests/data/record_steps.py

A 2-layer GPT-2-shaped LM (128 hidden, 2 heads x 64, vocabulary 512, 8
slots x 256, bf16) serves two long requests; the trace starts after
eight steps, a third request arrives twenty steps into it (its wave is
launched between two decode dispatches of the span), and the trace stops
after forty-eight steps with a program still in flight. Every shape is
compiled by a warm-up before. The file keeps the two planes the readers
open: of the device's lines ``XLA Modules`` alone (the operations of ~50
steps would be most of the file), of the host's events the program's
``serving.*`` spans and the runtime's ``DoEnqueueProgram``. It lands in
``chiprun_out/steps.xplane.pb``, and the events that ``test_steps.py``
works its numbers from are printed.
"""

import pathlib
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[2]
sys.path[:0] = [str(ROOT), str(HERE)]

from record_spans import KEEP                   # noqa: E402

STEPS_BEFORE, STEPS_TRACED, WAVE_AT = 8, 48, 20
DEVICE_LINES = ("XLA Modules",)      # lines kept of a device plane
HOST_EVENT = "DoEnqueueProgram"      # kept of the host plane, beside spans


def slim(blob: bytes) -> bytes:
    """The ``XSpace`` with the planes named in ``KEEP``: of the device
    the line ``XLA Modules``, of the host the program's spans and the
    runtime's enqueues, and only the event metadata a kept event points
    to."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace.FromString(blob)
    planes = list(space.planes)
    del space.planes[:]
    for plane in planes:
        print("plane", repr(plane.name), plane.ByteSize(), "bytes")
        if plane.name not in KEEP:
            continue
        device = plane.name.startswith("/device")
        names = {k: m.name for k, m in plane.event_metadata.items()}
        lines, used = list(plane.lines), set()
        del plane.lines[:]
        for line in lines:
            if device and line.name not in DEVICE_LINES:
                continue
            events = [e for e in line.events
                      if device or names[e.metadata_id] == HOST_EVENT
                      or names[e.metadata_id].startswith("serving.")]
            if events:
                del line.events[:]
                line.events.extend(events)
                plane.lines.append(line)
                used.update(e.metadata_id for e in events)
        for unused in set(names) - used:
            del plane.event_metadata[unused]
        space.planes.append(plane)
    return space.SerializeToString()


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving import SamplingParams, ServingEngine
    from bigdl_tpu.utils.random_gen import RNG

    from benchmark import step_join

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record on the chip: jax's platform is "
                         f"{jax.devices()[0].platform!r}")
    RNG.set_seed(11)
    lm = TransformerLM(512, hidden_size=128, n_heads=2, n_layers=2,
                       max_len=256)
    lm._ensure_params()
    lm.evaluate()
    eng = ServingEngine(lm, n_slots=8, compute_dtype=jnp.bfloat16)
    rng = np.random.RandomState(11)

    def submit(n_prompt, n_new, seed=None):
        return eng.submit(
            list(rng.randint(1, 512, size=n_prompt)), max_new_tokens=n_new,
            sampling=None if seed is None else SamplingParams(
                temperature=0.8, top_k=50, seed=seed))

    for n in (24, 37, 30):               # every shape of the run compiles
        submit(n, 4)
    eng.drain()
    submit(24, 200)
    submit(37, 200, seed=3)
    for _ in range(STEPS_BEFORE):
        eng.step()
    trace_dir = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1        # the program's spans, not jax's
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for i in range(STEPS_TRACED):
        if i == WAVE_AT:
            submit(30, 12)
        eng.step()
    jax.profiler.stop_trace()
    eng.drain()

    found = sorted(pathlib.Path(trace_dir).glob(
        "plugins/profile/*/*.xplane.pb"))
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    kept = out / "steps.xplane.pb"
    (out / "steps_whole.xplane.pb").write_bytes(found[-1].read_bytes())
    kept.write_bytes(slim(found[-1].read_bytes()))
    print("bytes", found[-1].stat().st_size, "->", kept.stat().st_size)

    profile = jax.profiler.ProfileData.from_file(str(kept))
    for plane in profile.planes:
        for line in plane.lines:
            events = [(e.name, int(e.start_ns), int(e.duration_ns),
                       {k: v for k, v in e.stats
                        if not k.startswith(("_", "device_"))})
                      for e in line.events]
            if line.name == "XLA Modules":
                print("MODULES", events)
            enqueues = [e for e in events if e[0] == "DoEnqueueProgram"]
            if enqueues:
                print("ENQUEUES", line.name, enqueues)
            spans = [e for e in events if e[0].startswith("serving.")]
            if spans:
                print("SPANS", line.name, spans)
    for name in ("serving/decode_gap_s", "serving/step_rows",
                 "serving/step_waves", "serving/step_chained"):
        print("SERIES", name, eng.metrics.metrics.values(name))
    print("JOIN", step_join.join_file(str(kept), "jit_sample_step"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
