"""Declared device→host synchronization points for the serving plane.

The async dispatch-ahead refactor (ROADMAP "raw speed" item) lives or
dies on ONE discipline: the super-step loop must never force a device
sync it did not declare. jax dispatches asynchronously — the host is
free to queue the next chunk prefill or draft chain while the decode
step runs on device — until something reads a device value back
(``np.asarray``, ``float()``, ``.item()``, a Python branch on an
array), at which point the host silently stalls on the whole pending
pipeline. Those implicit syncs are exactly what the ASY3xx analyzer
rules inventory (docs/analysis.md); this module is the other half of
the contract — the ONE idiom a deliberate sync is allowed to wear, so
every host-crossing in the hot path is named, machine-checked, and
enumerable (``python -m bigdl_tpu.analysis --report sync-points``).

Two idioms, both over a CLOSED site vocabulary (:data:`FENCE_SITES`,
the ``FINISH_REASONS`` pattern — an unknown site raises here and the
analyzer's ASY302 flags it statically):

* :func:`fence` — the READBACK fence: one batched ``jax.device_get``
  of several small values (the per-step token/logprob/emit-count
  readback). Batching matters: N separate ``np.asarray`` calls are N
  host round-trips; one ``device_get`` of the tuple is one. The
  returned values are host ``np.ndarray``s — everything downstream is
  plain Python and never syncs again.
* :func:`fence_wait` — the COMPLETION fence: ``jax.block_until_ready``
  on a tree, no copy. This is what a *timer* needs — a phase timing
  read off the clock before the dispatched work finished measures
  launch latency, not work (the lie ASY305 flags) — and the designated
  home of ``block_until_ready`` (ASY302 flags the raw spelling on any
  hot-path-reachable function outside this module).

The async refactor's job is then mechanical: every ``fence``/
``fence_wait`` site in the sync-point inventory is a place the loop
currently stops; moving one later (a delayed consumer) or deleting one
(batched host bookkeeping) is a reviewable one-line diff the analyzer
keeps honest.
"""

from __future__ import annotations

#: THE closed fence-site vocabulary. Every deliberate device→host sync
#: in the serving plane names one of these; the analyzer extracts this
#: frozenset (cross-module) and ASY302 flags both unknown site strings
#: and ``block_until_ready`` spelled outside this module.
FENCE_SITES = frozenset({
    "decode",    # the per-step token/logprob readback — consumed by the
                 # engine's DELAYED consumer (the dispatch-ahead window;
                 # see DELAYED_CONSUMER_SITES below)
    "verify",    # the speculative super-step's verify readback
    "draft",     # completion of the chained draft dispatches (timing)
    "prefill",   # vocabulary-reserved: the prefill completion fences
                 # were DELETED in PR 15 (prefill dispatches overlap
                 # the decode step — docs/async_readiness.md's
                 # cashed-in entries), so no shipped site spells this
                 # today; the name stays legal for a deliberate
                 # prefill wait (e.g. a debugging pin) so re-adding
                 # one is a diff, not a vocabulary change
    "transfer",  # KV-row handoff serialization (disagg.pack_payload):
                 # one batched readback of every payload leaf
})


#: THE closed dispatch-ahead vocabulary, the FENCE_SITES pattern lifted
#: to the multi-step window (PR 20 — the cashed-in async refactor).
#:
#: ``WINDOW_KNOBS`` names the engine knobs a dispatch-ahead window may
#: be bounded by: the analyzer's ASY308 demands every window-depth
#: guard (a ``len(<window>)`` comparison controlling dispatch or
#: consumption) reference one of these attributes — a bare loop
#: counter or a literal depth is vocabulary drift, exactly like an
#: unknown fence site string.
WINDOW_KNOBS = frozenset({
    "dispatch_ahead",   # ServingEngine(dispatch_ahead=W): in-flight
                        # decode dispatches beyond the one being
                        # consumed (W=0 = consume-immediately, the
                        # pre-window engine)
})

#: ``DELAYED_CONSUMER_SITES`` names the fence sites whose readback is
#: allowed to sit BEHIND the window — consumed by the delayed consumer
#: one-or-more dispatches after it was issued. Exactly the sites here
#: may appear in a window-consuming unit; any other fence reachable
#: from a window-DISPATCHING unit re-serializes the window by accident
#: and ASY309 flags it. The census in tests/test_serving_async.py
#: proves the serving tree has exactly ONE such site.
DELAYED_CONSUMER_SITES = frozenset({
    "decode",   # the engine's per-step token/logprob readback — THE
                # delayed-consumer site (ServingEngine._consume_window
                # fences the OLDEST in-flight dispatch while newer
                # ones keep the device fed). The speculative plane's
                # "verify" site stays an immediate consumer: each
                # super-step's draft budgets are a host decision made
                # from the previous verify readback, so its window
                # depth is structurally 0 (docs/serving.md).
})


#: THE closed span vocabulary of the serving plane (``ServingMetrics.
#: span`` / ``metrics.span`` raise on anything else, the FENCE_SITES
#: pattern): the names a profile of a serve carries on the dispatching
#: thread's line, each ``serving.<name>``. The benchmark attributes every
#: device idle gap to the innermost of these covering it, so a name here
#: is part of the yardstick — renaming one breaks the comparison of one
#: PR's ``breakdown`` with the next.
#:
#: A span is HOST time. One that brackets an un-fenced dispatch ends in
#: ``.launch`` (or its series in ``_host_s``) and measures the enqueue,
#: by design: ASY305's point stands, device time comes from the trace.
#: Only ``fence`` blocks on the device (its series: ``fence_wait_s``).
#: A span wraps the BODY of the function it names, never the call to
#: it: the function's own frame on the profile's python3 line is then
#: the longer event, and a reader that attributes an idle gap to the
#: shortest event covering it lands on the span.
SPAN_NAMES = frozenset({
    "step",            # one ServingEngine.step(): the body of
                       # _step_impl (step=<n>)
    "admit",           # _admit + the chunk pump (rids= of the requests
                       # it bound; series admit_host_s on steps that
                       # bound >= 1)
    "prefill.launch",  # one prefill dispatch — bucket, prefix suffix,
                       # chunk, per-request: the body of the prefill
                       # steps' host wrappers (models/transformer.py
                       # prefill_checked; rows=, padded=, bucket=)
    "pool.write",      # KVPool.write_prefill / write_sampling /
                       # restore_row / free: host rows + the launches
                       # of the scatters and resets
    "decode.build",    # host-built token/active rows, slot
                       # configuration, their uploads
    "decode.launch",   # the knob upload + the decode dispatch
    "consume",         # the delayed consumer (_consume_window): its
                       # fence, then the per-token bookkeeping
    "fence",           # THE blocked wait inside it: the decode/verify
                       # readback (series fence_wait_s)
})


def _check_site(site: str) -> None:
    if site not in FENCE_SITES:
        raise ValueError(
            f"unknown fence site {site!r} — add it to "
            f"fences.FENCE_SITES first; known: {sorted(FENCE_SITES)}")


def fence(site: str, *values):
    """THE declared readback: one batched ``jax.device_get`` of
    ``values``, returning host ``np.ndarray``s (a single value comes
    back bare, several as a tuple). The one place per super-step the
    host is ALLOWED to wait on the device — downstream bookkeeping
    runs on the returned host arrays and never syncs again."""
    import jax

    _check_site(site)
    out = jax.device_get(tuple(values))
    return out[0] if len(out) == 1 else out


def fence_wait(site: str, tree):
    """THE declared completion wait: ``jax.block_until_ready`` on
    ``tree`` (returned unchanged, still on device — no copy). Timers
    bracket device work with this so the elapsed time measures the
    work, not the launch."""
    import jax

    _check_site(site)
    return jax.block_until_ready(tree)
