"""The mean of one numeric field over the consensus-journal events of the
given types that all nodes recorded inside the window (``thw_journal``):
a proposer's phase as its own node timed it (``dt``), whichever node
proposed.  None where no journal was read or no such event fell in the
window."""


def read(obs, *, types: list, field: str, scale: float = 1.0):
    events = obs.get("journal")
    if events is None:
        return None
    vals = [e[field] for e in events
            if e.get("type") in types
            and obs["t_begin"] <= e.get("ts", 0) <= obs["t_end"]
            and isinstance(e.get(field), (int, float))
            and not isinstance(e.get(field), bool)]
    if not vals:
        return None
    return scale * sum(vals) / len(vals)
