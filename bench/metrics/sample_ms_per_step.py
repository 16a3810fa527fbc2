"""Host time of sampling and emission per Engine.step(): the program's
``serve.sample`` span (each slot's token, its emission and the stream
callback) less the retirements (``serve.retire``) inside it, per
``serve.step`` of the traced stretch, in milliseconds."""
import program_trace as P


def read(run):
    ev = P.for_run(run)
    return None if ev is None else P.span_ms_per_step(
        ev, *P.HOST_METRICS["sample_ms_per_step"])
