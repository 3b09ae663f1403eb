"""95th percentile of admission to first token (engine clock), over the
requests admitted in the window and finished in it: the program's
``serve.request`` records, ``t_first - t_admit``, of the requests whose
``serve.prefill`` span the program recorded (it records spans only while
the trace runs, so a request admitted before the window has none).
Admission is the request's prefill dispatch; the first token is the
first harvest that holds one. The sample's size goes to stderr."""
import sys

from bench import program_spans


def read(run):
    import numpy as np
    got = program_spans.records(run)
    if got is None:
        return None
    spans = got[0]
    admitted = {s.ids.get("nonce")
                for s in program_spans.named(spans, "serve.prefill")}
    waits = [s.ids["t_first"] - s.ids["t_admit"]
             for s in program_spans.named(spans, "serve.request")
             if s.ids.get("nonce") in admitted
             and s.ids.get("t_first") is not None]
    if not waits:
        return None
    print(f"first_token_wait_ms: p95 of {len(waits)} requests admitted "
          f"and finished in the window", file=sys.stderr)
    return 1e3 * float(np.percentile(np.asarray(waits, np.float64), 95))
