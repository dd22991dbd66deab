"""A quantile of the client-side samples the driver took in the window
(``q`` from 0 to 1; 1 is the largest)."""

from perfbench.harness import quantile


def read(obs, *, sample: str, q: float):
    return quantile(obs["samples"].get(sample) or [], q)
