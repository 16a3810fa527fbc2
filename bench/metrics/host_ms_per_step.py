"""Host time per Engine.step(): the step's wall time minus the wall time of
the compiled prefill and decode calls inside it (each waited on), averaged
over the window's steps. What is left is admission, page bookkeeping,
sampling and emission on the host."""


def read(run):
    calls = sorted(run.spans.calls, key=lambda c: c[1])
    own, i = [], 0
    for s, e in run.spans.steps:
        if not run.win.w0 <= s <= run.win.w1:
            continue
        inside = 0.0
        while i < len(calls) and calls[i][1] < s:
            i += 1
        j = i
        while j < len(calls) and calls[j][2] <= e:
            inside += calls[j][2] - calls[j][1]
            j += 1
        own.append((e - s) - inside)
    return 1e3 * sum(own) / len(own) if own else None
