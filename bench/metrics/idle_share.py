"""idle_share (device trace): the share of the traced slice's window in
which no XLA op ran on the device, averaged over the cell's chips, in
percent."""


def read(ctx):
    tr = ctx["traced"]
    if not tr or not tr.get("window_s") or tr.get("busy_s") is None:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
