"""Host time of admission per Engine.step(): the program's ``serve.admit``
span less the prefill call (``serve.prefill``) and the retirements
(``serve.retire``) inside it, per ``serve.step`` of the traced stretch, in
milliseconds. What is left: the prefix match, the fresh cache and its
gathered pages, the slot write's dispatch, the first token's sampling."""
import program_trace as P


def read(run):
    ev = P.for_run(run)
    return None if ev is None else P.span_ms_per_step(
        ev, *P.HOST_METRICS["admit_ms_per_step"])
