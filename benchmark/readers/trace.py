"""Readers over the reduced device trace (``trace_reduce.py``)."""


def program_mean_ms(obs, args):
    """Mean device duration of one program's ``XLA Modules`` events; the
    program's name comes from the configuration (``args["settings_key"]``
    names the key of the cell's settings that holds it)."""
    trace = obs.get("trace")
    if not trace:
        return None
    program = trace["programs"].get(obs["settings"][args["settings_key"]])
    return program["mean_ms"] if program else None


def idle_pct(obs, args):
    trace = obs.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
