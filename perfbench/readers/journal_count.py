"""How many consensus-journal events of the given types all nodes recorded
inside the window (``thw_journal``); ``min_version`` counts only rounds
after a failed one.  None where no journal was read."""


def read(obs, *, types: list, min_version: int | None = None):
    events = obs.get("journal")
    if events is None:
        return None
    return sum(1 for e in events
               if e.get("type") in types
               and obs["t_begin"] <= e.get("ts", 0) <= obs["t_end"]
               and (min_version is None
                    or e.get("version", 0) >= min_version))
