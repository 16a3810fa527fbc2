"""Device self time of the decode program under the ``layer_scan`` scope
(the layer loop slicing each layer's weights and KV cache out of the
stacked arrays and writing the cache back into a new pool), per decode
execution in the traced stretch, in milliseconds."""
import program_trace as P


def read(run):
    ev = P.for_run(run)
    return None if ev is None else P.scope_ms(ev, "layer_scan")
