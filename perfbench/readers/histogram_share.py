"""The time that went into some registry histograms' observations inside
the window (count times mean, after less before, summed over ``names``),
as a share of the window in percent.  For the program's spans
(``span.self_seconds;name=...``) that is the share of wall time one layer
spent; several threads run, so the shares of a window may sum past 100.
None where the program has none of the histograms."""


def read(obs, *, names: list):
    if not obs.get("window_s"):
        return None
    total, found = 0.0, False
    for name in names:
        a, b = obs["after"].get(name), obs["before"].get(name) or {}
        if not isinstance(a, dict):
            continue
        found = True
        total += (a.get("count", 0) * a.get("mean", 0.0)
                  - b.get("count", 0) * b.get("mean", 0.0))
    if not found:
        return None
    return 100.0 * total / obs["window_s"]
