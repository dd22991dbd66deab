"""How the sidecar's clients shared the rows it answered in the window,
from the per-connection breakdown of ``SidecarServer.stats()``
(``sidecar.served``, a list, which ``harness.pick`` does not walk): each
connection's ``rows``, after less before.

``min_share``: the least client's rows as a share of all clients' rows, in
percent (three even askers read 33.3; a client that was answered nothing
gives 0).  None where the program reports no sidecar, or where no client
was answered a row."""


def _clients(snapshot: dict) -> dict:
    served = (snapshot.get("sidecar") or {}).get("served") or []
    return {c["client"]: c for c in served}


def read(obs, *, stat: str):
    after, before = _clients(obs["after"]), _clients(obs["before"])
    rows = [c.get("rows", 0) - (before.get(i) or {}).get("rows", 0)
            for i, c in after.items()]
    if not rows or sum(rows) <= 0:
        return None
    if stat == "min_share":
        return 100.0 * min(rows) / sum(rows)
    raise ValueError(f"no client statistic {stat!r}")
