"""Host data-plane throughput: RECS shards on disk → train step (r2 #3).

Every bench.py number feeds device-resident synthetic tensors; the
reference's defining constraint was keeping executors fed from SeqFiles
(``dataset/DataSet.scala`` — SeqFileFolder; SURVEY §7). This bench measures
each stage of OUR host pipeline against the device's ~2,500 img/s appetite:

  1. decode   — SeqFileDataSet raw RECS decode rate (disk → Samples)
  2. produce  — native C++ pipeline (crop/flip/normalize, off-GIL) rate
  3. transfer — host→device rate for finished batches
  4. train    — end-to-end ResNet-50 train step consuming the pipeline
                with the optimizer's prefetch overlap

Prints one line per stage plus a sustained end-to-end img/s and the ratio
vs the device-resident number measured in the same session.

Run: python benchmarks/input_pipeline_bench.py [--n-images 2048] [--iters 30]
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import numpy as np

IMAGENET_MEAN = (0.485 * 255, 0.456 * 255, 0.406 * 255)
IMAGENET_STD = (0.229 * 255, 0.224 * 255, 0.225 * 255)


def _encode_u8(img: np.ndarray) -> bytes:
    """uint8 image payload (compact, like the reference's byte records —
    the stock encode_array stores f32, 4x the bytes)."""
    import struct

    img = np.ascontiguousarray(img, np.uint8)
    return bytes([img.ndim]) + struct.pack(
        f"<{img.ndim}I", *img.shape) + img.tobytes()


def _decode_u8(label: int, payload: bytes):
    import struct

    from bigdl_tpu.dataset.sample import Sample

    nd = payload[0]
    dims = struct.unpack_from(f"<{nd}I", payload, 1)
    arr = np.frombuffer(payload, np.uint8, offset=1 + 4 * nd).reshape(dims)
    return Sample(arr.copy(), np.int32(label))


def make_recs(tmp, n, hw=224, n_shards=8):
    from bigdl_tpu.dataset.seqfile import write_shards

    rng = np.random.default_rng(0)
    recs = [(int(i % 1000) + 1,
             _encode_u8(rng.integers(0, 256, (hw, hw, 3), dtype=np.uint8)))
            for i in range(n)]
    write_shards(recs, tmp, n_shards=n_shards)
    return tmp


def bench_decode(tmp, n):
    from bigdl_tpu.dataset.seqfile import SeqFileDataSet

    ds = SeqFileDataSet(tmp, decoder=_decode_u8)
    t0 = time.perf_counter()
    cnt = 0
    for s in ds._iter_once(shuffle=False):
        cnt += 1
    dt = time.perf_counter() - t0
    assert cnt == n
    return n / dt


def _pipeline(images, labels, batch):
    from bigdl_tpu.dataset.native_pipeline import NativeImagePipeline

    return NativeImagePipeline(
        images, labels, batch_size=batch, crop=(224, 224), pad=4,
        mean=IMAGENET_MEAN, std=IMAGENET_STD, hflip=True,
        queue_depth=6, n_workers=4)


def bench_produce(images, labels, batch, n_batches):
    pipe = _pipeline(images, labels, batch)
    it = pipe.data(train=True)
    next(it)  # warm the worker pool
    t0 = time.perf_counter()
    for _ in range(n_batches):
        next(it)
    dt = time.perf_counter() - t0
    return batch * n_batches / dt


def bench_transfer(images, labels, batch, n_batches):
    import jax

    pipe = _pipeline(images, labels, batch)
    it = pipe.data(train=True)
    bufs = [next(it) for _ in range(4)]
    x = jax.device_put(np.asarray(bufs[0].get_input()))
    x.block_until_ready()
    t0 = time.perf_counter()
    for i in range(n_batches):
        b = bufs[i % len(bufs)]
        x = jax.device_put(np.asarray(b.get_input()))
    x.block_until_ready()
    float(x.ravel()[0])
    dt = time.perf_counter() - t0
    imgs = batch * n_batches
    mb = imgs * 3 * 224 * 224 * 4 / 1e6
    return imgs / dt, mb / dt


def bench_train(images, labels, batch, iters, u8: bool = True):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models.resnet import ResNet
    from bigdl_tpu.nn.criterion import CrossEntropyCriterion
    from bigdl_tpu.optim.optim_method import SGD
    from bigdl_tpu.optim.train_step import make_train_step
    from bigdl_tpu.utils.random_gen import RNG

    RNG.set_seed(7)
    model = ResNet(class_num=1000, opt={"depth": 50, "shortcutType": "B"})
    model._ensure_params()
    sgd = SGD(learning_rate=0.1, momentum=0.9, weight_decay=1e-4)
    if u8:
        # the DEFAULT RECS feed: uint8 NHWC over the wire, normalize on
        # device (4x fewer transfer bytes; host skips float conversion)
        from bigdl_tpu.dataset.native_pipeline import NativeImagePipeline

        pipe = NativeImagePipeline(
            images, labels, batch_size=batch, crop=(224, 224), pad=4,
            mean=IMAGENET_MEAN, std=IMAGENET_STD, hflip=True,
            queue_depth=6, n_workers=4, output="u8_nhwc")
        preprocess = pipe.device_normalizer()
    else:
        pipe = _pipeline(images, labels, batch)
        preprocess = None
    step = jax.jit(make_train_step(model, CrossEntropyCriterion(), sgd,
                                   compute_dtype=jnp.bfloat16,
                                   device_preprocess=preprocess),
                   donate_argnums=(0, 1))
    params, ms = jax.device_put(model.params), model.state
    opt_state = jax.device_put(sgd.init_state(params))
    rng = jax.random.PRNGKey(0)

    it = pipe.data(train=True)

    def place(b):
        return (jax.device_put(np.asarray(b.get_input())),
                jax.device_put(np.asarray(b.get_target()).astype(np.int32)))

    x, y = place(next(it))
    params, opt_state, ms, loss = step(params, opt_state, ms, rng, x, y)
    float(loss)
    nxt = place(next(it))
    t0 = time.perf_counter()
    for _ in range(iters):
        x, y = nxt
        params, opt_state, ms, loss = step(params, opt_state, ms, rng, x, y)
        nxt = place(next(it))   # overlaps device compute
    float(loss)
    dt = time.perf_counter() - t0
    return batch * iters / dt


def device_resident_rate(batch, iters):
    """Same-session device-resident reference (bench.py methodology)."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models.resnet import ResNet
    from bigdl_tpu.nn.criterion import CrossEntropyCriterion
    from bigdl_tpu.optim.optim_method import SGD
    from bigdl_tpu.optim.train_step import make_train_step
    from bigdl_tpu.utils.random_gen import RNG

    RNG.set_seed(7)
    model = ResNet(class_num=1000, opt={"depth": 50, "shortcutType": "B"})
    model._ensure_params()
    sgd = SGD(learning_rate=0.1, momentum=0.9, weight_decay=1e-4)
    step = jax.jit(make_train_step(model, CrossEntropyCriterion(), sgd,
                                   compute_dtype=jnp.bfloat16),
                   donate_argnums=(0, 1))
    params, ms = jax.device_put(model.params), model.state
    opt_state = jax.device_put(sgd.init_state(params))
    rng = jax.random.PRNGKey(0)
    x = jax.device_put(jnp.zeros((batch, 3, 224, 224), jnp.float32))
    y = jax.device_put(np.ones((batch,), np.int32))
    params, opt_state, ms, loss = step(params, opt_state, ms, rng, x, y)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt_state, ms, loss = step(params, opt_state, ms, rng, x, y)
    float(loss)
    return batch * iters / (time.perf_counter() - t0)


def bench_lenet_produce(n=8192, batch=512, n_batches=24):
    """LeNet-scale (28×28×1) host production rate — the config where host
    work dominates device time (the chip trains LeNet at ~56k img/s)."""
    from bigdl_tpu.dataset.native_pipeline import NativeImagePipeline

    rng = np.random.default_rng(1)
    imgs = rng.integers(0, 256, (n, 28, 28, 1), dtype=np.uint8)
    labels = (np.arange(n) % 10 + 1).astype(np.int32)
    pipe = NativeImagePipeline(imgs, labels, batch_size=batch,
                               crop=(28, 28), mean=(33.3,), std=(78.6,),
                               hflip=False, queue_depth=6, n_workers=4)
    it = pipe.data(train=True)
    next(it)
    t0 = time.perf_counter()
    for _ in range(n_batches):
        next(it)
    return batch * n_batches / (time.perf_counter() - t0)


def jpeg_bytes(img: np.ndarray, quality: int = 85) -> bytes:
    import io

    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray(img).save(buf, format="JPEG", quality=quality)
    return buf.getvalue()


def decode_jpeg_sample(label: int, payload: bytes):
    import io

    from PIL import Image

    from bigdl_tpu.dataset.sample import Sample

    arr = np.asarray(Image.open(io.BytesIO(payload)).convert("RGB"),
                     np.uint8)
    return Sample(arr, np.int32(label))


def make_hadoop_jpeg_corpus(out_dir: str, n: int, hw: int = 224,
                            n_parts: int = 3) -> float:
    """Synthesize n JPEG images into Hadoop SequenceFiles (ImageNet
    convention: Text key 'name label', BytesWritable JPEG payload) —
    smooth gradients + noise so the files compress like photos rather
    than random bytes. Returns total MB written."""
    from bigdl_tpu.dataset.hadoop_seqfile import SequenceFileWriter

    rng = np.random.default_rng(0)
    yy, xx = np.mgrid[0:hw, 0:hw].astype(np.float32) / hw
    per = (n + n_parts - 1) // n_parts
    total = 0
    for part in range(n_parts):
        path = os.path.join(out_dir, f"part-{part:05d}")
        with SequenceFileWriter(path) as w:
            for i in range(part * per, min((part + 1) * per, n)):
                base = np.stack([
                    (np.sin(xx * (3 + i % 5)) * 0.5 + 0.5),
                    (yy * ((i % 7) / 7.0 + 0.2)) % 1.0,
                    (xx * yy + 0.1 * (i % 11)) % 1.0], -1)
                img = np.clip(base * 255 + rng.normal(0, 12, base.shape),
                              0, 255).astype(np.uint8)
                w.append(f"img_{i} {i % 1000 + 1}", jpeg_bytes(img))
        total += os.path.getsize(path)
    return total / 1e6


def bench_hadoop_jpeg_chain(n_images: int, batch: int, iters: int,
                            train: bool = True) -> None:
    """The ImageNet-format dress rehearsal (round-5 verdict item #6):
    Hadoop SequenceFile (JPEG) → convert_to_recs → SeqFileDataSet with a
    JPEG decoder → native u8 pipeline → u8 transfer + device normalize →
    ResNet-50 train step."""
    from bigdl_tpu.dataset.hadoop_seqfile import convert_to_recs
    from bigdl_tpu.dataset.seqfile import SeqFileDataSet

    with tempfile.TemporaryDirectory() as hd, \
            tempfile.TemporaryDirectory() as recs:
        t0 = time.perf_counter()
        mb = make_hadoop_jpeg_corpus(hd, n_images)
        print(f"hadoop-jpeg: wrote {n_images} JPEGs / {mb:.1f} MB "
              f"SequenceFiles in {time.perf_counter() - t0:.1f}s",
              flush=True)

        t0 = time.perf_counter()
        convert_to_recs(hd, recs, n_shards=4)
        conv = n_images / (time.perf_counter() - t0)
        print(f"hadoop-convert: {conv:8.1f} img/s  (SequenceFile -> RECS "
              "shards)", flush=True)

        ds = SeqFileDataSet(recs, decoder=decode_jpeg_sample)
        t0 = time.perf_counter()
        samples = list(ds._iter_once(shuffle=False))
        dec = len(samples) / (time.perf_counter() - t0)
        assert len(samples) == n_images
        print(f"jpeg-decode: {dec:8.1f} img/s  (RECS -> PIL decode -> "
              "u8 HWC Sample)", flush=True)

        images = np.stack([np.asarray(s.feature(), np.uint8)
                           for s in samples])
        labels = [int(s.label()) for s in samples]
        prod = bench_produce(images, labels, min(batch, n_images),
                             max(iters // 2, 4))
        print(f"hadoop-produce: {prod:8.1f} img/s  (native pipeline on "
              "the decoded corpus)", flush=True)
        if train:
            rate = bench_train(images, labels, min(batch, n_images),
                               max(iters // 2, 4), u8=True)
            print(f"hadoop-train: {rate:8.1f} img/s  (end-to-end u8 feed "
                  "+ device normalize, ResNet-50)", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-images", type=int, default=2048)
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--hadoop-jpeg", action="store_true",
                    help="run ONLY the Hadoop-SequenceFile JPEG dress "
                         "rehearsal (few hundred images)")
    ap.add_argument("--hadoop-n", type=int, default=384)
    args = ap.parse_args()

    from bigdl_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.hadoop_jpeg:
        bench_hadoop_jpeg_chain(args.hadoop_n, args.batch, args.iters)
        return

    lenet_rate = bench_lenet_produce()
    print(f"lenet-produce: {lenet_rate:8.1f} img/s  (28x28x1, host augment "
          f"+ normalize)", flush=True)

    with tempfile.TemporaryDirectory() as tmp:
        make_recs(tmp, args.n_images)
        shard_mb = sum(os.path.getsize(os.path.join(tmp, f))
                       for f in os.listdir(tmp)) / 1e6
        print(f"wrote {args.n_images} records / {shard_mb:.0f} MB of .recs "
              f"shards", flush=True)

        dec = bench_decode(tmp, args.n_images)
        print(f"decode   : {dec:8.1f} img/s  (SeqFileDataSet, disk->Sample)",
              flush=True)

        # keep decoded images resident (the reference caches decoded
        # ImageFrames in executor memory the same way)
        from bigdl_tpu.dataset.seqfile import SeqFileDataSet

        ds = SeqFileDataSet(tmp, decoder=_decode_u8)
        samples = list(ds._iter_once(shuffle=False))
        images = np.stack([np.asarray(s.feature(), np.uint8)
                           for s in samples])
        labels = [int(s.label()) for s in samples]

        prod = bench_produce(images, labels, args.batch, args.iters)
        print(f"produce  : {prod:8.1f} img/s  (native crop/flip/normalize)",
              flush=True)

        xfer, mbs = bench_transfer(images, labels, args.batch,
                                   max(args.iters // 3, 8))
        print(f"transfer : {xfer:8.1f} img/s  ({mbs:.0f} MB/s host->device)",
              flush=True)

        # fix-plan datum: shipping uint8 NHWC and normalizing on-device
        # cuts transfer bytes 4x (the TPU-native input design; the f32
        # normalize then fuses into the first conv's prologue)
        import jax

        u8 = images[:args.batch]
        x = jax.device_put(u8)
        x.block_until_ready()
        t0 = time.perf_counter()
        reps = max(args.iters // 3, 8)
        for _ in range(reps):
            x = jax.device_put(u8)
        x.block_until_ready()
        float(np.asarray(x[0, 0, 0, 0]))
        u8_rate = args.batch * reps / (time.perf_counter() - t0)
        print(f"xfer-u8  : {u8_rate:8.1f} img/s  (uint8 NHWC, device-side "
              f"normalize plan)", flush=True)

        ref = device_resident_rate(args.batch, args.iters)
        print(f"resident : {ref:8.1f} img/s  (device-resident reference)",
              flush=True)

        e2e_f32 = bench_train(images, labels, args.batch, args.iters,
                              u8=False)
        print(f"train-f32: {e2e_f32:8.1f} img/s  (RECS-fed, f32 host "
              f"normalize — the old default)", flush=True)
        e2e = bench_train(images, labels, args.batch, args.iters)
        print(f"train    : {e2e:8.1f} img/s  (RECS-fed, uint8 transfer + "
              f"device normalize — DEFAULT)", flush=True)

        print(json.dumps({
            "metric": "resnet50_recs_fed_train_images_per_sec",
            "value": round(e2e, 1),
            "unit": "images/sec/chip",
            "vs_device_resident": round(e2e / ref, 3),
            "f32_feed": round(e2e_f32, 1),
            "stages": {"decode": round(dec, 1), "produce": round(prod, 1),
                       "transfer": round(xfer, 1),
                       "transfer_u8": round(u8_rate, 1),
                       "device_resident": round(ref, 1)},
        }))


if __name__ == "__main__":
    main()
