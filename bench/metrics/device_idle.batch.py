"""Share of the traced stretch in which no operation ran on the device:
1 - (union of device-operation intervals) / (traced stretch), in percent,
averaged over the chips used. Read in the offline-batch cells."""


def read(run):
    t = run.trace
    if not t.get("window_s") or not t.get("busy_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
