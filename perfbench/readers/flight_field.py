"""A quantile of one field of the scheduler's flight recorder
(``thw_flight`` / ``VerifierScheduler.flights()``) over the windows that
finished inside the measured window, of one class where ``klass`` is
given.  The recorder keeps the newest 4096 windows."""

from perfbench.harness import quantile


def read(obs, *, field: str, q: float, klass: str | None = None):
    vals = [f[field] for f in obs.get("flights") or []
            if obs["t_begin"] <= f.get("t_done", 0) <= obs["t_end"]
            and (klass is None or f.get("klass") == klass)
            and not f.get("diverted")]
    return quantile(vals, q)
