"""Records ``spans.xplane.pb``: three iterations of the program's own
training loop at toy size, on the chip, with the profiler's Python
frames and jax's own host events off so that the file stays small.

    chiprun -- python3 benchmark/tests/data/record_spans.py

One causal ``MultiHeadAttention`` layer (128 hidden, 2 heads x 64, 256
positions, batch 2) trains through ``Optimizer.optimize()`` against a
mean-squared error, with flash attention; the end trigger starts the
trace before iteration 4 and stops it after iteration 6, so the file
holds the loop's ``train.*`` spans and the ``flash_*`` kernels under the
names ``jax.grad`` gives them. The trace lands in
``chiprun_out/spans.xplane.pb``; the events that
``test_span_reduce.py`` works its numbers from are printed.
"""

import pathlib
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT))

FIRST, LAST = 4, 6                   # traced iterations (neval)
KEEP = ("/device:TPU:0", "/host:CPU")        # planes the readers read


def _varint(buf: bytes, i: int):
    value = shift = 0
    while True:
        value |= (buf[i] & 0x7F) << shift
        shift += 7
        i += 1
        if not buf[i - 1] & 0x80:
            return value, i


def keep_planes(blob: bytes) -> bytes:
    """The ``XSpace`` with only the planes named in ``KEEP`` (field 1 is
    the repeated ``XPlane``, whose field 2 is its name): the chip adds
    planes of its own that no reader opens. Prints each plane's size."""
    out, i = b"", 0
    while i < len(blob):
        start = i
        tag, i = _varint(blob, i)
        if tag & 7 != 2:
            raise SystemExit(f"unexpected wire type in XSpace: tag {tag}")
        size, i = _varint(blob, i)
        body, i = blob[i:i + size], i + size
        name = ""
        if tag >> 3 == 1:
            j = 0
            while j < len(body):
                t, j = _varint(body, j)
                if t & 7 == 2:
                    n, j = _varint(body, j)
                    if t >> 3 == 2:
                        name = body[j:j + n].decode()
                        break
                    j += n
                else:
                    _, j = _varint(body, j)
            print("plane", repr(name), size, "bytes")
        if tag >> 3 != 1 or name in KEEP:
            out += blob[start:i]
    return out


def main() -> int:
    import jax
    import numpy as np

    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.nn import MSECriterion, Sequential
    from bigdl_tpu.nn.attention import MultiHeadAttention
    from bigdl_tpu.optim import SGD, Optimizer, Trigger
    from bigdl_tpu.utils.random_gen import RNG

    from benchmark import span_reduce, trace_reduce

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record on the chip: jax's platform is "
                         f"{jax.devices()[0].platform!r}")
    RNG.set_seed(7)
    rng = np.random.default_rng(7)
    xs = rng.standard_normal((8, 2, 256, 128)).astype(np.float32)
    samples = [Sample(x, y) for x, y in xs]
    model = Sequential().add(MultiHeadAttention(128, 2, causal=True))
    opt = Optimizer(model=model, dataset=DataSet.array(samples, seed=7),
                    criterion=MSECriterion(), batch_size=2)
    opt.set_compute_dtype("bf16")
    opt.set_optim_method(SGD(learning_rate=0.01))
    trace_dir = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1        # the program's spans, not jax's

    def end_when(state) -> bool:
        if state["neval"] == FIRST:
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        elif state["neval"] == LAST + 1:
            jax.profiler.stop_trace()
            return True
        return False

    opt.set_end_when(Trigger(end_when, lambda state: False))
    opt.optimize()

    found = sorted(pathlib.Path(trace_dir).glob(
        "plugins/profile/*/*.xplane.pb"))
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / "spans.xplane.pb").write_bytes(
        keep_planes(found[-1].read_bytes()))
    print("bytes", found[-1].stat().st_size, "->",
          (out / "spans.xplane.pb").stat().st_size)

    kept = str(out / "spans.xplane.pb")
    profile = jax.profiler.ProfileData.from_file(kept)
    for plane in profile.planes:
        for line in plane.lines:
            if plane.name == "/device:TPU:0" and line.name == "XLA Modules":
                print("MODULES", [(e.name, e.start_ns, e.duration_ns)
                                  for e in line.events])
            if plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                print("KERNELS", [(trace_reduce.op_name(e.name),
                                   e.start_ns, e.duration_ns)
                                  for e in line.events if "flash" in e.name])
            if plane.name == trace_reduce.HOST_PLANE:
                spans = [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events
                         if e.name.startswith(span_reduce.PROGRAM_SPANS)]
                if spans:
                    print("SPANS", line.name, spans)
    print("REDUCED", span_reduce.reduce_file(kept))
    print("TRACE_REDUCE", trace_reduce.reduce_file(kept))
    return 0


if __name__ == "__main__":
    sys.exit(main())
