"""Device self time of the decode program under the ``qmm.wquant`` scope
(each projection's weight scale and int8 quantization, redone on every
call), per decode execution in the traced stretch, in milliseconds."""
import program_trace as P


def read(run):
    ev = P.for_run(run)
    return None if ev is None else P.scope_ms(ev, "qmm.wquant")
