"""iter_us (device trace): device busy time of the traced slice over the
event-loop iterations it ran (its largest lane's n_events), in
microseconds per iteration."""


def read(ctx):
    tr = ctx["traced"]
    if not tr or not tr.get("busy_s") or not tr.get("iterations"):
        return None
    return tr["busy_s"] / tr["iterations"] * 1e6
