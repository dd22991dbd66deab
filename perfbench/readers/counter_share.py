"""The growth of some registry numbers over the measured window
(``harness.delta``), summed over ``names``, as a share of the window in
percent.  For CPU seconds (``process.cpu_seconds``, the program's
``threads.cpu_seconds;role=<role>``) that is cores kept busy: 100 is one
core for the whole window, and several threads may add past it.  None
where the program has none of the names."""

from perfbench.harness import delta, pick


def read(obs, *, names: list):
    if not obs.get("window_s"):
        return None
    found = [n for n in names if pick(obs["after"], n) is not None]
    if not found:
        return None
    return 100.0 * sum(delta(obs, n) for n in found) / obs["window_s"]
