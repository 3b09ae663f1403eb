"""Share of the traced window the chip spends in the per-prompt-length
prefill programs (one B=1 prefill per admitted request)."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    t = run.trace.program_seconds().get(run.counters.get("prefill_program"))
    if not t:
        return None
    return 100.0 * t / run.trace.window_s
