"""Host time of publishing finished sequences to the prefix cache per
Engine.step(): the program's ``serve.publish`` spans (PrefixCache.insert:
the radix-tree insert and every eviction it triggers) per ``serve.step``
of the traced stretch, in milliseconds."""
import program_trace as P


def read(run):
    ev = P.for_run(run)
    return None if ev is None else P.span_ms_per_step(
        ev, *P.HOST_METRICS["publish_ms_per_step"])
