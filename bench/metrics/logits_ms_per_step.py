"""Host time of bringing the decode step's logits to the host per
Engine.step(): the program's ``serve.logits`` span per ``serve.step`` of
the traced stretch, in milliseconds. Where the launch was not waited on,
it holds the wait for the decode program too."""
import program_trace as P


def read(run):
    ev = P.for_run(run)
    return None if ev is None else P.span_ms_per_step(
        ev, *P.HOST_METRICS["logits_ms_per_step"])
