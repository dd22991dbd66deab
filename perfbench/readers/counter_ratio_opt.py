"""``counter_ratio`` for counters that an older program does not have:
None where the registry holds none of ``num``, instead of a 0 that would
read as "counted, and none"."""

from perfbench.harness import pick
from perfbench.readers import counter_ratio


def read(obs, *, num: list, **args):
    if all(pick(obs["after"], p) is None for p in num):
        return None
    return counter_ratio.read(obs, num=num, **args)
