"""Device self time outside the Engine's prefill and decode programs (the
eager cache init, page gather, slot write and page store) per
``serve.step`` of the traced stretch, in milliseconds."""
import program_trace as P


def read(run):
    ev = P.for_run(run)
    return None if ev is None else P.eager_ms_per_step(ev)
