"""Whole-step share of the chip's int8 peak: the integer operations
(bench/ops.py) of the tokens served in the window (prompt tokens prefilled,
without padding and prefix-cache hits, plus output tokens) over the window
and the int8 peak of the device kind, in percent."""


def read(run):
    tokens = run.delta["prefill_tokens"] + run.emitted
    ops = tokens * run.ops_per_token
    return 100.0 * ops / (run.win.w1 - run.win.w0) / run.peak["int8_ops"]
