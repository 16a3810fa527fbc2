"""Device time of one decode program (TLM.decode_step over every slot),
from the trace: the mean over the traced executions launched inside the
benchmark's bench.decode spans, in milliseconds."""


def read(run):
    t = run.trace.get("program_s", {}).get("decode")
    return 1e3 * sum(t) / len(t) if t else None
