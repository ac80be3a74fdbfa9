"""Readers over the program's own spans and names in the traced run
(``span_reduce.py``) and over the reduced trace's programs. Where the
program has no such span, program or kernel name (the parent of the PR
that added them), each returns None and the metric is left out."""

from benchmark import harness, kernel_costs, span_reduce


def _spans(obs):
    """This run's trace reduced by the program's spans; None where no
    device was traced or no span is in it."""
    if not obs.get("trace"):
        return None
    path = span_reduce.newest_trace(harness.ROOT / ".cache" / "bench_trace")
    return span_reduce.reduce_file(str(path)) if path else None


def program_ms(obs, args):
    """Device milliseconds of ``args["program"]``'s ``XLA Modules``
    events per call of it, or per call of ``args["per"]`` (the scatter
    programs a prefill wave brings, per wave)."""
    programs = (obs.get("trace") or {}).get("programs", {})
    program = programs.get(args["program"])
    per = programs.get(args.get("per", args["program"]))
    if not program or not per:
        return None
    return program["total_s"] * 1e3 / per["count"]


def idle_pct(obs, args):
    """Device idle under the spans ``args["under"]`` names (whatever is
    nested in them), or under no program span at all
    (``"unattributed": true``), as a share of the traced span."""
    r = _spans(obs)
    if r is None:
        return None
    if args.get("unattributed"):
        seconds = r["idle_innermost_s"].get(span_reduce.UNATTRIBUTED, 0.0)
    else:
        seconds = sum(r["idle_under_s"].get(n, 0.0) for n in args["under"])
    return 100.0 * seconds / r["window_s"]


def kernel_roofline(obs, args):
    """The least time the chip could take for the kernel's work in the
    traced iterations (``args["costs"]`` computes one iteration's
    operations and bytes from the cell's shapes, ``args["pass"]`` picks
    the pass) over the summed device time of the operations whose name
    holds one of ``args["ops"]``."""
    r = _spans(obs)
    step = (obs.get("trace") or {}).get("programs", {}).get(
        obs["settings"].get("step_program"))
    if r is None or not step:
        return None
    measured = span_reduce.op_seconds(r, args["ops"])
    if not measured:
        return None
    cost = harness.resolve(args["costs"])(
        obs["config"], obs["settings"])[args["pass"]]
    least, _ = kernel_costs.roofline_seconds(
        cost["flops"], cost["bytes"], obs["peaks"])
    return 100.0 * least * step["count"] / measured
