"""The mean of a registry histogram's observations made inside the window,
from its count and mean before and after (the registry's percentiles are
since the start of the process, so they are not read)."""


def read(obs, *, name: str, scale: float = 1.0):
    a, b = obs["after"].get(name), obs["before"].get(name) or {}
    if not isinstance(a, dict):
        return None
    n = a.get("count", 0) - b.get("count", 0)
    if n <= 0:
        return None
    total = a["count"] * a["mean"] - b.get("count", 0) * b.get("mean", 0.0)
    return scale * total / n
