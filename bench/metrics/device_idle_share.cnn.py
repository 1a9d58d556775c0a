"""Share of an untraced round in which no op runs on the chip, in %: one
less the chip's busy time per traced round (the union of its op
intervals in the trace, over the rounds traced after the window) over
the untraced window's mean round.  The profiler slows the host loop but
not the chip's ops, so the trace gives the device's work a round and the
window gives the round's length."""


def read(run):
    c = run.counters
    if run.trace is None or not c.get("traced_rounds") or not c.get("rounds"):
        return None
    busy_per_round = run.trace["busy_s"] / c["traced_rounds"]
    return 100.0 * (1.0 - busy_per_round / (c["window_s"] / c["rounds"]))
