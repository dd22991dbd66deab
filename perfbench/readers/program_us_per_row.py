"""Device time of the recover program in the trace over the rows the
device recovered in the traced window."""


def read(obs):
    tr = obs.get("trace")
    rows = obs.get("trace_rows")
    if not tr or not tr.get("program_s") or not rows or rows <= 0:
        return None
    return 1e6 * tr["program_s"] / rows
