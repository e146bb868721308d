"""entry_idle_ms (device trace): device idle time of the traced call that
falls under the program's host spans ``repro.launch`` (the jitted call
is dispatched) and ``repro.compact_check`` (the host reads the overflow
flag), in milliseconds; on several chips the mean over them.  Read only
from a program that opens its entry span."""
SPANS = ("repro.launch", "repro.compact_check")


def read(ctx):
    tr = ctx["traced"]
    if not tr or not tr.get("entry"):
        return None
    return 1e3 * sum(tr["idle_by_label"].get(s, 0.0) for s in SPANS)
