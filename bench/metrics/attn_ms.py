"""Device self time of the decode program under the ``attn`` scope
(rotary, the cache write, scores, softmax and the value product over the
pool), per decode execution in the traced stretch, in milliseconds."""
import program_trace as P


def read(run):
    ev = P.for_run(run)
    return None if ev is None else P.scope_ms(ev, "attn")
