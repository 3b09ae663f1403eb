"""Idle time of the first chip per decode chunk while the serving engine's
host code runs: the part of the chip's idle gaps in the window that lies
under the program's ``serve.step`` spans (every other engine span lies
inside one), placed on the trace's clock by
``program_spans.trace_offset_s``, over its counter ``serve.chunks``.
None where the spans cannot be placed, or no chip is traced."""
from bench import program_spans, tracing


def read(run):
    got = program_spans.records(run)
    if got is None or run.trace is None or not run.trace.devices:
        return None
    spans, counters = got
    chunks = counters.get("serve.chunks")
    off = program_spans.trace_offset_s(run, spans, "serve.step")
    if not chunks or off is None:
        return None
    engine = tracing.union([(s.start_ns * 1e-9 + off, s.end_ns * 1e-9 + off)
                            for s in program_spans.named(spans,
                                                         "serve.step")])
    idle = tracing.gaps(run.trace._busy(run.trace.devices[0]),
                        *run.trace.window)
    under = tracing.subtract(idle, tracing.subtract(idle, engine))
    return 1e3 * tracing.total(under) / chunks
