"""The one generator of inputs: it reads a mix's or a job's data file
and a seed, and returns what the runner sends. A new mix or job is a new
data file; this module names none.

Every seed gives the SAME schedule of sizes and arrivals, so that runs
with different seeds do the same work: the sizes and gaps are the
quantiles of the file's distributions (stratified, not sampled), cut into
blocks of ``block_s`` seconds (``rate x block_s`` requests) and shuffled
within each block by the file's own ``order_seed``; ``--seed`` draws the
token ids, the sampling seeds and (in the runner) the weights. On the chip
the order alone moved the p95 of the gap between tokens by a third and of
the time to first token by 80% (PERF.md, PR 25), so the order is part of
the mix, not of the seed. A ramp and a window that are whole numbers of
blocks hold exactly the same requests in every run.
"""

from __future__ import annotations

import math
import statistics
from typing import NamedTuple, Optional

import numpy as np


class Request(NamedTuple):
    due_s: float                 # offset from the generator's start
    prompt: list                 # 1-based token ids
    max_new_tokens: int
    sampling_seed: Optional[int]     # None: greedy


def _quantiles(n: int):
    return [(i + 0.5) / n for i in range(n)]


def length_set(dist: dict, n: int):
    """The ``n`` stratified quantiles of a clipped lognormal, as whole
    numbers, smallest first."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    normal = statistics.NormalDist()
    out = []
    for q in _quantiles(n):
        x = dist["median"] * math.exp(dist["sigma"] * normal.inv_cdf(q))
        out.append(int(min(max(round(x), dist["min"]), dist["max"])))
    return out


def gap_set(arrivals: dict, n: int, total_s: float = None):
    """The ``n`` stratified quantiles of the gap between arrivals, scaled
    so that they sum to ``n / rate``."""
    rate = float(arrivals["rate_per_s"])
    if arrivals["process"] == "poisson":
        gaps = [-math.log(1.0 - q) for q in _quantiles(n)]
    elif arrivals["process"] == "uniform":
        gaps = [1.0] * n
    else:
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    scale = (total_s or n / rate) / sum(gaps)
    return [g * scale for g in gaps]


def block_size(mix: dict) -> int:
    return max(1, round(mix["arrivals"]["rate_per_s"] * mix["block_s"]))


def serve_schedule(mix: dict, seed: int, horizon_s: float, vocab: int,
                   period_s: float = None):
    """Requests due in ``[0, horizon_s)``, in order of arrival.

    With ``period_s`` (the window's length) the ORDER of sizes and gaps
    repeats with that period, counted from the end of the ramp: the
    ramp's blocks are ordered like the window's last blocks, so what the
    ramp leaves in flight when the window opens is what the window
    leaves in flight when it closes, and the tokens emitted inside it
    are those of its own requests. Token ids are drawn anew throughout."""
    block = block_size(mix)
    prompts = length_set(mix["prompt_len"], block)
    outputs = length_set(mix["output_len"], block)
    gaps = gap_set(mix["arrivals"], block, float(mix["block_s"]))
    sampled_every = int(mix["sampling"]["every"])
    rng = np.random.default_rng(int(seed))
    order_rng = np.random.default_rng(int(mix["order_seed"]))
    # every arrival half the smallest gap early, so that none falls on
    # a block's boundary; the gaps between arrivals stay the same set
    early = min(gaps) / 2
    n_ramp = round(mix["ramp_s"] / mix["block_s"])
    period = max(1, round(period_s / mix["block_s"])) if period_s else None
    orders = {}                  # a block's place in the period -> orders
    out, n_block = [], 0
    while n_block * mix["block_s"] < horizon_s:
        place = (n_block - n_ramp) % period if period else n_block
        if place not in orders:
            orders[place] = [order_rng.permutation(block) for _ in range(3)]
        order_p, order_o, order_g = orders[place]
        t = n_block * float(mix["block_s"])
        for j in range(block):
            t += gaps[order_g[j]]
            plen = prompts[order_p[j]]
            prompt = rng.integers(1, vocab + 1, size=(plen,)).tolist()
            sampled = sampled_every > 0 and len(out) % sampled_every == 1
            out.append(Request(
                t - early, prompt, outputs[order_o[j]],
                int(rng.integers(0, 2 ** 31 - 1)) if sampled else None))
        n_block += 1
    return [r for r in out if r.due_s < horizon_s]


def warmup_requests(mix: dict, seed: int, vocab: int):
    """One short request for each prompt length the file lists under
    ``warmup_prompt_lens``: the prefill shapes this mix can hit."""
    rng = np.random.default_rng(int(seed))
    out = []
    for i, plen in enumerate(mix["warmup_prompt_lens"]):
        prompt = rng.integers(1, vocab + 1, size=(int(plen),)).tolist()
        out.append(Request(0.0, prompt, int(mix["warmup_new_tokens"]),
                           1000 + i if i % 2 else None))
    return out


def _draw(spec: dict, n: int, rng, config: dict, feature=None):
    shape = (n, *spec["shape"])

    def bound(x):            # a number, or the name of a configuration key
        return int(config[x]) if isinstance(x, str) else int(x)

    if spec["draw"] == "normal":
        x = rng.standard_normal(shape, dtype=np.float32)
    elif spec["draw"] == "uniform_int":
        x = rng.integers(bound(spec["low"]), bound(spec["high"]) + 1,
                         size=shape)
    elif spec["draw"] == "next_of_feature":
        # the label of position t is the feature at t + 1; the last
        # position's label is drawn
        last = rng.integers(bound(spec["low"]), bound(spec["high"]) + 1,
                            size=(n, 1))
        x = np.concatenate([feature[:, 1:], last], axis=1)
    else:
        raise ValueError(f"unknown draw {spec['draw']!r}")
    return x.astype(spec["dtype"])


def train_samples(job: dict, seed: int, config: dict):
    """The job's ``n_samples`` seeded ``Sample``s, drawn in bulk."""
    from bigdl_tpu.dataset.sample import Sample

    rng = np.random.default_rng(int(seed))
    n = int(job["n_samples"])
    feats = _draw(job["feature"], n, rng, config)
    labels = _draw(job["label"], n, rng, config, feature=feats)
    return [Sample(feats[i], labels[i]) for i in range(n)]
