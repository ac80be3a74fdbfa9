"""The traffic generator: same seed same schedule, another seed another
order of the SAME sizes, every length inside its clips, the rate."""

import collections

from benchmark import harness, traffic

MIX = harness.load_json(harness.HERE / "traffic" / "chat-steady.json")
VOCAB = 50257


def _schedule(seed, horizon=250.0):
    return traffic.serve_schedule(MIX, seed, horizon, VOCAB)


def test_same_seed_same_schedule():
    assert _schedule(3_000_000_019) == _schedule(3_000_000_019)


def test_another_seed_gives_the_same_schedule_with_other_tokens():
    a, b = _schedule(1), _schedule(2)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert [(len(r.prompt), r.max_new_tokens) for r in a] == \
        [(len(r.prompt), r.max_new_tokens) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert [r.sampling_seed for r in a] != [r.sampling_seed for r in b]
    # another order_seed: the same sizes in each block, in another order
    c = traffic.serve_schedule(dict(MIX, order_seed=7), 1, 250.0, VOCAB)
    block = traffic.block_size(MIX)
    assert [r.due_s for r in a] != [r.due_s for r in c]
    for field in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
        assert collections.Counter(map(field, a[:block])) == \
            collections.Counter(map(field, c[:block]))
    # a block lasts block_s seconds whatever the order
    assert a[block - 1].due_s < MIX["block_s"] < a[block].due_s
    assert c[block - 1].due_s < MIX["block_s"] < c[block].due_s
    # so a window of whole blocks holds the same requests in every run
    for s in (a, c):
        assert sum(20 <= r.due_s < 60 for r in s) == 4 * block


def test_the_order_repeats_with_the_window_so_the_ramp_mirrors_its_end():
    block, ramp = traffic.block_size(MIX), MIX["ramp_s"]
    n_ramp = ramp // MIX["block_s"]
    s = traffic.serve_schedule(MIX, 3, ramp + 40.0, VOCAB, period_s=40.0)
    sizes = [(len(r.prompt), r.max_new_tokens) for r in s]
    gaps = [round(b.due_s - a.due_s, 9) for a, b in zip(s, s[1:])]
    assert len(s) == (n_ramp + 4) * block
    # the ramp's blocks are ordered like the window's last blocks
    assert sizes[:n_ramp * block] == sizes[4 * block:]
    assert gaps[:n_ramp * block - 1] == gaps[4 * block:]
    assert sizes[:block] != sizes[n_ramp * block:(n_ramp + 1) * block]
    assert [r.prompt for r in s[:block]] != \
        [r.prompt for r in s[4 * block:5 * block]]


def test_lengths_inside_their_clips():
    p, o = MIX["prompt_len"], MIX["output_len"]
    for r in _schedule(7):
        assert p["min"] <= len(r.prompt) <= p["max"]
        assert o["min"] <= r.max_new_tokens <= o["max"]
        assert len(r.prompt) + r.max_new_tokens <= 768
        assert min(r.prompt) >= 1 and max(r.prompt) <= VOCAB


def test_mean_rate_over_1000_draws():
    rate = MIX["arrivals"]["rate_per_s"]
    s = _schedule(11, horizon=1000 / rate * 1.05)
    assert len(s) >= 1000
    measured = 999 / (s[999].due_s - s[0].due_s)
    assert abs(measured - rate) / rate < 0.10
    assert all(b.due_s > a.due_s for a, b in zip(s, s[1:]))


def test_medians_and_sampling_split():
    s = _schedule(5)
    prompts = sorted(len(r.prompt) for r in s)
    outputs = sorted(r.max_new_tokens for r in s)
    assert abs(prompts[len(s) // 2] - MIX["prompt_len"]["median"]) <= 8
    assert abs(outputs[len(s) // 2] - MIX["output_len"]["median"]) <= 4
    greedy = sum(r.sampling_seed is None for r in s)
    assert abs(greedy - len(s) / 2) <= 1


def test_warmup_covers_every_prefill_bucket_of_the_mix():
    from bigdl_tpu.serving.admission import bucket_len

    hit = {bucket_len(len(r.prompt) - 1, 1024) for r in _schedule(9)} | \
        {bucket_len(len(r.prompt), 1024) for r in _schedule(9)}
    warmed = set()
    for r in traffic.warmup_requests(MIX, 0, VOCAB):
        warmed |= {bucket_len(len(r.prompt) - 1, 1024),
                   bucket_len(len(r.prompt), 1024)}
    assert hit <= warmed, (sorted(hit), sorted(warmed))


def test_train_samples_are_seeded_and_shaped():
    job = harness.load_json(harness.HERE / "traffic" / "lm-1k.json")
    cfg = {"vocab_size": VOCAB}
    a = traffic.train_samples(job, 4, cfg)
    b = traffic.train_samples(job, 4, cfg)
    c = traffic.train_samples(job, 5, cfg)
    assert len(a) == 64
    f, lab = a[0].feature(), a[0].label()
    assert f.shape == (1024,) and f.dtype.name == "int32"
    assert lab.shape == (1024,) and lab.dtype.name == "float32"
    assert (f == b[0].feature()).all() and (f != c[0].feature()).any()
    assert (lab[:-1] == f[1:]).all()            # next-token labels
    assert f.min() >= 1 and f.max() <= VOCAB
