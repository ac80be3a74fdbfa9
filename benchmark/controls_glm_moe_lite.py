"""Set and hold ``TIE_MARGIN`` of ``configs/glm_moe_lite_reference.py``
and show that the comparison's tolerance does its work on a
latent-attention cell: ONE run of the cell, exactly as ``run.py`` makes
it, whose checked sample is then read again with the reference's logits
and tie distances apart (every margin tried on the same served tokens),
and against altered references that must each come out as NOT correct
under the margin that stands. ``tie_margin.py`` does the same for the
``afmoe`` cell; its two mechanism controls name that family's gate and
window, so this family brings its own:

- ``float8``, the nearest precision BELOW the configuration's bfloat16:
  every norm's output (the latents ``c_q`` and ``c_kv``, what the cache
  holds, among them) and every MLP's output rounded to float8-e4m3's
  4-bit significand (float32's range, so nothing under- or overflows);
- ``no_kv_norm``: the RMSNorm of the key/value latent left out;
- ``k_rope_unrotated``: the shared rotary key taken without its rotation;
- ``no_rope_term``: the scores without ``q_rope . k_rope`` at all, which
  is what a cache row cut to its 512 latent columns computes.

Every token's shortfall, tie distance and spread also go to
``chiprun_out/controls_glm_<sample seed>.json``.

    python3 benchmark/controls_glm_moe_lite.py --workload <name> --seed <n> [--seconds 40]
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
LEFT_OUT = {"no_kv_norm": ("kv_norm",),
            "k_rope_unrotated": ("k_rope_rotation",),
            "no_rope_term": ("rope_term",)}


class Controls(reference.Reference):
    """The comparison as it stands, then the same sample margin by
    margin and against each altered reference."""

    def check(self, schedule, outs, seed):
        import jax
        import jax.numpy as jnp

        verdict = super().check(schedule, outs, seed)
        module, config = reference.load_reference(self.config), self.config
        stands = float(module.TIE_MARGIN)
        warm = (np.ones((self.ref_len,), np.int32),
                np.zeros((self.new_max,), np.int32))

        def reader(leave_out=()):
            return jax.jit(lambda p, tok, at: module.logits_and_ties(
                p, tok, at, config, leave_out))

        def float8(x):              # a 4-bit significand, float32's range
            m, e = jnp.frexp(x)
            return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)

        routed = module._ROUTED     # whose norm and MLP the forward uses
        norm, mlp = routed._rms_norm, routed._swiglu
        routed._rms_norm = lambda *a: float8(norm(*a))
        routed._swiglu = lambda *a: float8(mlp(*a))
        try:
            rounded = reader()
            rounded(self.lm.params, *warm)                   # traced here
        finally:
            routed._rms_norm, routed._swiglu = norm, mlp
        readers = {"plain": reader(), "float8": rounded,
                   **{name: reader(out) for name, out in LEFT_OUT.items()}}

        # the sample, drawn as Reference.check draws it
        rng = np.random.default_rng(seed)
        n = int(self.mix["reference_sample"])
        keys = sorted(outs)
        groups = ([k for k in keys if schedule[k].sampling_seed is None],
                  [k for k in keys if schedule[k].sampling_seed is not None])
        chosen = [int(k) for g in groups for k in rng.permutation(g)[:n]]
        say("controls sample", json.dumps([{
            "request": k, "prompt": len(schedule[k].prompt),
            "served": len(outs[k]),
            "context": len(schedule[k].prompt) + len(outs[k]),
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
            for name, fn in readers.items():
                logits, tie = (np.asarray(a, np.float32)[:len(out)]
                               for a in fn(self.lm.params, tokens, at))
                best = np.partition(logits, -kth, axis=1)[:, -kth]
                served = logits[np.arange(len(out)), np.asarray(out) - 1]
                rows[name] += zip(best - served, tie,
                                  logits.max(1) - np.median(logits, 1))
        out_dir = ROOT / "chiprun_out"          # listed in .gitignore
        out_dir.mkdir(exist_ok=True)
        (out_dir / f"controls_glm_{seed}.json").write_text(json.dumps({
            name: np.asarray(data, np.float64).round(5).tolist()
            for name, data in rows.items()}))
        for name, data in rows.items():
            short, tie, spread = np.asarray(data, np.float64).T
            slack = reference.SERVE_SLACK_OF_SPREAD * spread.mean()
            misses = short > slack
            say(f"controls {name}", json.dumps({
                "tokens": len(short), "slack_of_the_whole_sample": slack,
                "largest_tie_distance_of_a_miss":
                    float(tie[misses].max()) if misses.any() else None,
                "misses": int(misses.sum())}))
            for margin in sorted({*MARGINS, stands}):
                judged = tie >= margin
                # as the comparison reads it: an unjudged row has no
                # spread, so the slack shrinks with the share judged
                allowed = reference.SERVE_SLACK_OF_SPREAD \
                    * spread[judged].sum() / len(spread)
                worst = float(short[judged].max()) if judged.any() else 0.0
                say(f"controls {name}", json.dumps({
                    "margin": margin, "stands": margin == stands,
                    "judged_share": float(judged.mean()),
                    "worst_judged_shortfall": worst, "allowed": allowed,
                    "ok": bool(worst <= allowed),
                    "judged_over_allowed":
                        int((short[judged] > allowed).sum())}))
        return verdict


def main(argv=None) -> int:
    reference.Reference = Controls
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
