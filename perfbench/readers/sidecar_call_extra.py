"""What the socket adds to a window: the mean of the clients' calls (the
span ``sidecar.call`` in every node process of ``obs["nodes"]``, from the
first byte sent to the last answer in hand) less the mean time a window
spent INSIDE the sidecar (``sidecar.served_seconds`` in the sidecar's own
registry: from its entry into the scheduler to its last row resolved,
which is the scheduler's wait and compute for that window), in
milliseconds.  What is left is the frame's way in (the client's send, the
reader's wake-up and read), the way back (the writer's wake-up, the
answers to bytes, the send, the client reader's wake-up) and the waiting
caller's.  None where a program has no such spans or the driver laid no
``nodes`` into the ``obs``."""

from perfbench.readers import histogram_mean

CALLS = ("span.seconds;name=sidecar.call,class=bulk",
         "span.seconds;name=sidecar.call,class=consensus")
SERVED = "sidecar.served_seconds"


def _grown(obs, name: str) -> tuple:
    """``(observations, their total)`` a histogram gained in the window."""
    a, b = obs["after"].get(name), obs["before"].get(name) or {}
    if not isinstance(a, dict):
        return 0, 0.0
    return (a.get("count", 0) - b.get("count", 0),
            a.get("count", 0) * a.get("mean", 0.0)
            - b.get("count", 0) * b.get("mean", 0.0))


def read(obs, *, scale: float = 1e3):
    calls = total = 0
    for node in obs.get("nodes") or []:
        for name in CALLS:
            n, t = _grown(node, name)
            calls, total = calls + n, total + t
    inside = histogram_mean.read(obs, name=SERVED)
    if calls <= 0 or inside is None:
        return None
    return scale * (total / calls - inside)
