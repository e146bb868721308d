"""seam_ms (device trace): mean device time between the end of one
window-step execution of a streamed replay and the start of the next, in
the traced slice, in milliseconds."""


def read(ctx):
    tr = ctx["traced"]
    runs = (tr or {}).get("program_runs") or []
    if len(runs) < 2:
        return None
    gaps = [b[0] - a[1] for a, b in zip(runs, runs[1:])]
    return 1e3 * sum(gaps) / len(gaps)
