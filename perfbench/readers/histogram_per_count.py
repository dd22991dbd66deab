"""The time that went into some registry histograms' observations inside
the window (count times mean, after less before, summed over ``names``)
over the growth of some counters: a span's seconds for each of what it
worked through.  None where the program has none of the histograms, or
the counters did not grow."""

from perfbench.harness import delta


def read(obs, *, names: list, den: list, scale: float = 1.0):
    total, found = 0.0, False
    for name in names:
        a, b = obs["after"].get(name), obs["before"].get(name) or {}
        if not isinstance(a, dict):
            continue
        found = True
        total += (a.get("count", 0) * a.get("mean", 0.0)
                  - b.get("count", 0) * b.get("mean", 0.0))
    below = sum(delta(obs, p) for p in den)
    if not found or below <= 0:
        return None
    return scale * total / below
