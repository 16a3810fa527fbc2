"""Device self time of the decode program under the ``qmm.core`` scope
(the backend's integer product: the MXU int8 matmul or the emulated
multiplier), per decode execution in the traced stretch, in
milliseconds."""
import program_trace as P


def read(run):
    ev = P.for_run(run)
    return None if ev is None else P.scope_ms(ev, "qmm.core")
