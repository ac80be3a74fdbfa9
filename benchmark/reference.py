"""The comparison that decides ``correct`` for serving, kept with the
benchmark: counter identities of a drained engine, and each served
token against the configuration's plain reference.

Copies of ``chip_smoke.py``'s ``_check_served``, ``_reference_slack``
and ``SERVE_SLACK_OF_SPREAD``; they return what failed instead of
asserting, so that a run reports ``correct: false`` with the reason.
"""

from __future__ import annotations

import importlib.util

import numpy as np

from benchmark import harness

#: a served token's logit in the reference forward may sit this far
#: under the best logit its sampling allowed and still be a rounding
#: tie, as a fraction of the logits' spread (best minus median over the
#: vocabulary); a token from a wrong cache row or position misses by
#: about the whole spread
SERVE_SLACK_OF_SPREAD = 0.06
#: the served loss of the first training iteration (bf16 compute) and
#: the reference's float32 loss on the same batch and weights
TRAIN_LOSS_RTOL = 0.005


def load_reference(config: dict):
    """The module the configuration names as its plain reference (a
    path from the root of the checkout), or None."""
    path = config.get("reference")
    if not path:
        return None
    spec = importlib.util.spec_from_file_location(
        "benchmark_config_reference", harness.ROOT / path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def check_served(eng, reqs: dict, vocab: int, n_before: int) -> list:
    """What must hold of any drained engine, whatever the numerics.
    ``reqs``: rid -> traffic.Request of everything submitted after the
    warm-up's ``n_before`` requests. Returns the identities that do not
    hold, as strings."""
    bad = []
    tokens = 0
    for rid, req in reqs.items():
        r = eng.request(rid)
        out = None if r is None else np.asarray(r.output, np.int32)
        if r is None or r.finish_reason not in ("length", "stop"):
            bad.append(f"request {rid}: finish_reason "
                       f"{getattr(r, 'finish_reason', None)!r}")
            continue
        tokens += len(out)
        if out.shape != (req.max_new_tokens,):
            bad.append(f"request {rid}: {out.shape[0]} tokens, "
                       f"{req.max_new_tokens} asked")
        elif out.min() < 1 or out.max() > vocab:
            bad.append(f"request {rid}: token outside 1..{vocab}")
        elif not np.isfinite(eng.logprobs(rid)).all():
            bad.append(f"request {rid}: non-finite logprob")
    m, summary = eng.metrics.metrics, eng.metrics.summary()
    n = len(reqs)
    if m.get("serving/submitted")[1] - n_before != n:
        bad.append("serving/submitted != requests submitted")
    if m.get("serving/finished")[1] - n_before != n:
        bad.append("serving/finished != requests submitted")
    for name in ("serving/retries", "serving/finish_error", "serving/shed",
                 "serving/preempted"):
        if summary.get(name):
            bad.append(f"{name} = {summary[name]}")
    return bad[:10]


class Reference:
    """Teacher-forced check of served tokens against the cache-free
    plain forward: each served token's reference logit, measured from
    the best logit the request's sampling allowed (the top one for
    greedy rows, the ``top_k``-th for sampled rows)."""

    def __init__(self, config: dict, lm, mix: dict) -> None:
        import jax

        self.config, self.lm, self.mix = config, lm, mix
        self.ref_len = mix["prompt_len"]["max"] + mix["output_len"]["max"]
        self.new_max = mix["output_len"]["max"]
        module = load_reference(config)
        self.fn = jax.jit(lambda p, tok, at: module.logits_at(
            p, tok, at, config))

    def _logits(self, tokens, at):
        return np.asarray(self.fn(self.lm.params, tokens, at), np.float32)

    def warm_up(self) -> None:
        self._logits(np.ones((self.ref_len,), np.int32),
                     np.zeros((self.new_max,), np.int32))

    def check(self, schedule, outs: dict, seed: int) -> dict:
        """``outs``: schedule index -> served tokens. A seeded sample of
        ``reference_sample`` greedy and as many sampled requests."""
        rng = np.random.default_rng(seed)
        n = int(self.mix["reference_sample"])
        keys = sorted(outs)
        greedy = [k for k in keys if schedule[k].sampling_seed is None]
        sampled = [k for k in keys if schedule[k].sampling_seed is not None]
        chosen = [int(k) for group in (greedy, sampled) for k in
                  rng.permutation(group)[:n]]
        worst, spreads = 0.0, []
        for k in chosen:
            req, out = schedule[k], outs[k]
            seq = list(req.prompt) + [int(t) for t in out]
            tokens = np.ones((self.ref_len,), np.int32)
            tokens[:len(seq)] = seq
            at = np.zeros((self.new_max,), np.int32)
            at[:len(out)] = len(req.prompt) - 1 + np.arange(len(out))
            logits = self._logits(tokens, at)[:len(out)]
            if not np.isfinite(logits).all():
                return {"ok": False, "why": "non-finite reference logits"}
            kth = 1 if req.sampling_seed is None \
                else int(self.mix["sampling"]["top_k"])
            for row, tok in zip(logits, out):
                best = np.partition(row, -kth)[-kth]
                worst = max(worst, float(best - row[int(tok) - 1]))
                spreads.append(float(row.max() - np.median(row)))
        if not spreads:
            return {"ok": False, "why": "no finished request to check"}
        spread = float(np.mean(spreads))
        return {"ok": worst <= SERVE_SLACK_OF_SPREAD * spread,
                "requests": len(chosen), "tokens": len(spreads),
                "worst_logit_shortfall": worst, "logit_spread": spread,
                "allowed": SERVE_SLACK_OF_SPREAD * spread}
