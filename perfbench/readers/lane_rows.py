"""How the scheduler's lanes shared the window's rows, from the per-lane
breakdown of ``VerifierScheduler.stats()`` (``scheduler.devices``, a list,
which ``harness.pick`` does not walk): each lane's ``rows`` and ``batches``,
after less before.

``min_share``: the least lane's rows as a share of all lanes' rows, in
percent (a lane that served nothing gives 0).  ``rows_per_window``: all
lanes' rows over all lanes' device windows.  None where the program
reports no lanes, or where no lane served a row."""


def _lanes(snapshot: dict) -> dict:
    devices = (snapshot.get("scheduler") or {}).get("devices") or []
    return {d["device"]: d for d in devices}


def read(obs, *, stat: str):
    after, before = _lanes(obs["after"]), _lanes(obs["before"])
    if not after:
        return None
    rows, batches = [], 0
    for i, d in after.items():
        b = before.get(i) or {}
        rows.append(d.get("rows", 0) - b.get("rows", 0))
        batches += d.get("batches", 0) - b.get("batches", 0)
    if sum(rows) <= 0 or batches <= 0:
        return None
    if stat == "min_share":
        return 100.0 * min(rows) / sum(rows)
    if stat == "rows_per_window":
        return sum(rows) / batches
    raise ValueError(f"no lane statistic {stat!r}")
