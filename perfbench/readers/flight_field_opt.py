"""``flight_field`` for a field that an older program's flight recorder
does not write: None there, instead of a missing key."""

from perfbench.readers import flight_field


def read(obs, *, field: str, **args):
    if not any(field in f for f in obs.get("flights") or []):
        return None
    return flight_field.read(obs, field=field, **args)
