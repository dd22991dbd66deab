"""The growth of some counters over the growth of others, across the
measured window: rows a batch, useful rows a padded row, host rows among
all rows."""

from perfbench.harness import delta


def read(obs, *, num: list, den: list, scale: float = 1.0):
    below = sum(delta(obs, p) for p in den)
    if below <= 0:
        return None
    return scale * sum(delta(obs, p) for p in num) / below
