"""``program_us_per_row`` for a node whose scheduler drives several chips.
``trace.reduce`` gives ``program_s`` as the MEAN over the device planes,
while the rows of the traced window are those of all lanes together: on
four planes the plain reader shows a quarter of a row's device cost.
Multiplied by the scheduler's ``lanes`` it is the recover program's device
time on every chip over the rows they recovered."""

from perfbench.readers import program_us_per_row


def read(obs):
    per_plane = program_us_per_row.read(obs)
    lanes = (obs["after"].get("scheduler") or {}).get("lanes")
    if per_plane is None or not lanes:
        return None
    return per_plane * lanes
