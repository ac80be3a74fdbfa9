"""Admission: what a wave of prefill costs the step it rides in, and how
much of the prefill block was computed for no request."""

import statistics


def admit_step_ms(obs, args):
    """Median wall time of the ``eng.step()`` calls in which
    ``serving/prefill_batch`` gained a sample (a prefill was issued)."""
    walls = [end - start for start, end, prefills in obs["spans"]["steps"]
             if prefills > 0]
    return statistics.median(walls) * 1e3 if walls else None


def prefill_pad_share(obs, args):
    """1 - true rows / padded rows over the window's prefill calls."""
    true = sum(obs["series"].get("serving/prefill_batch", []))
    padded = sum(obs["series"].get("serving/prefill_batch_padded", []))
    return 100.0 * (1.0 - true / padded) if padded else None
