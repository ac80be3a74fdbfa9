"""Set and hold ``TIE_MARGIN`` of a routed configuration's reference
(``configs/afmoe_reference.py``): ONE run of the cell, exactly as
``run.py`` makes it, whose checked sample is then read again with the
reference's logits and tie distances apart, so that every margin can be
tried on the same served tokens. Made when the cell is defined, and
again when the program's arithmetic changes; the two readings go into
the reference file beside the constant. It prints, before ``run.py``'s
own lines end:

- the checked sample (which requests, their contexts);
- for each margin: the share of tokens judged, the worst shortfall among
  them, and how many miss the comparison's slack;
- reading (a): the largest tie distance at which a served token misses
  the slack (what the margin must cover);
- (every token's shortfall, tie distance and spread also go to
  ``chiprun_out/tie_margin_<sample seed>.json``);
- reading (b), the control ``float8``: the same served tokens against
  the reference computed in the nearest precision BELOW the
  configuration's bfloat16: every norm's and every MLP's output rounded
  to float8-e4m3's 4-bit significand (float32's range, so nothing under-
  or overflows). Under the margin that stands it must come out as not
  correct;
- two controls with a mechanism left out of the reference, which must
  come out as not correct too: ``no_gate`` (the attention gate's matrix
  zeroed: a constant gate, which the norm that follows removes) and
  ``no_window`` (the sliding layers see every earlier key; it bears
  only on positions past the window, so it also shows that the sample
  holds some).

    python3 benchmark/tie_margin.py --workload <name> --seed <n> [--seconds 40]
"""

import json
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmark import reference, run                       # noqa: E402
from benchmark.harness import say                          # noqa: E402

MARGINS = (0.0, 0.01, 0.02, 0.03, 0.04, 0.06, 0.08, 0.12, 0.2)


class Probe(reference.Reference):
    """The comparison as it stands, then the same sample margin by
    margin."""

    def check(self, schedule, outs, seed):
        import jax
        import jax.numpy as jnp

        verdict = super().check(schedule, outs, seed)
        module, config = reference.load_reference(self.config), self.config
        stands = float(module.TIE_MARGIN)

        def reader(config=config):
            return jax.jit(lambda p, tok, at: module.logits_and_ties(
                p, tok, at, config))

        def float8(x):              # a 4-bit significand, float32's range
            m, e = jnp.frexp(x)
            return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)

        params = self.lm.params
        norm, mlp = module._rms_norm, module._swiglu
        module._rms_norm = lambda *a: float8(norm(*a))
        module._swiglu = lambda *a: float8(mlp(*a))
        try:
            rounded = reader()
            rounded(params, np.ones((self.ref_len,), np.int32),
                    np.zeros((self.new_max,), np.int32))     # traced here
        finally:
            module._rms_norm, module._swiglu = norm, mlp
        no_gate = dict(params, layers=[dict(layer, attn=dict(
            layer["attn"], wg=jnp.zeros_like(layer["attn"]["wg"])))
            for layer in params["layers"]])
        readers = {                 # name -> (forward, its parameters)
            "plain": (reader(), params), "float8": (rounded, params),
            "no_gate": (reader(), no_gate),
            "no_window": (reader(dict(config, sliding_window=self.ref_len)),
                          params)}

        # the sample, drawn as Reference.check draws it
        rng = np.random.default_rng(seed)
        n = int(self.mix["reference_sample"])
        keys = sorted(outs)
        groups = ([k for k in keys if schedule[k].sampling_seed is None],
                  [k for k in keys if schedule[k].sampling_seed is not None])
        chosen = [int(k) for g in groups for k in rng.permutation(g)[:n]]
        window = config.get("sliding_window", 0)
        say("tie_margin sample", json.dumps([{
            "request": k, "prompt": len(schedule[k].prompt),
            "served": len(outs[k]),
            "context": len(schedule[k].prompt) + len(outs[k]),
            "past_window": len(schedule[k].prompt) + len(outs[k]) > window,
            "sampled": schedule[k].sampling_seed is not None}
            for k in chosen]))
        rows = {name: [] for name in readers}   # (shortfall, tie, spread)
        for k in chosen:
            req, out = schedule[k], outs[k]
            seq = list(req.prompt) + [int(t) for t in out]
            tokens = np.ones((self.ref_len,), np.int32)
            tokens[:len(seq)] = seq
            at = np.zeros((self.new_max,), np.int32)
            at[:len(out)] = len(req.prompt) - 1 + np.arange(len(out))
            kth = 1 if req.sampling_seed is None \
                else int(self.mix["sampling"]["top_k"])
            for name, (fn, tree) in readers.items():
                logits, tie = (np.asarray(a, np.float32)[:len(out)]
                               for a in fn(tree, tokens, at))
                best = np.partition(logits, -kth, axis=1)[:, -kth]
                served = logits[np.arange(len(out)), np.asarray(out) - 1]
                rows[name] += zip(best - served, tie,
                                  logits.max(1) - np.median(logits, 1))
        out_dir = ROOT / "chiprun_out"          # listed in .gitignore
        out_dir.mkdir(exist_ok=True)
        (out_dir / f"tie_margin_{seed}.json").write_text(json.dumps({
            name: np.asarray(data, np.float64).round(5).tolist()
            for name, data in rows.items()}))
        for name, data in rows.items():
            short, tie, spread = np.asarray(data, np.float64).T
            slack = reference.SERVE_SLACK_OF_SPREAD * spread.mean()
            misses = short > slack
            say(f"tie_margin {name}", json.dumps({
                "tokens": len(short), "slack_of_the_whole_sample": slack,
                "largest_tie_distance_of_a_miss":
                    float(tie[misses].max()) if misses.any() else None,
                "misses": int(misses.sum())}))
            for margin in (sorted({*MARGINS, stands}) if name == "plain"
                           else (0.0, stands)):
                judged = tie >= margin
                # as the comparison reads it: an unjudged row has no
                # spread, so the slack shrinks with the share judged
                allowed = reference.SERVE_SLACK_OF_SPREAD \
                    * spread[judged].sum() / len(spread)
                worst = float(short[judged].max()) if judged.any() else 0.0
                say(f"tie_margin {name}", json.dumps({
                    "margin": margin, "stands": margin == stands,
                    "judged_share": float(judged.mean()),
                    "worst_judged_shortfall": worst, "allowed": allowed,
                    "ok": bool(worst <= allowed),
                    "judged_over_allowed":
                        int((short[judged] > allowed).sum())}))
        return verdict


def main(argv=None) -> int:
    reference.Reference = Probe
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
