"""The sum of the samples the driver took in the window (milliseconds)
as a share of the window, in percent: the part of the window that went
into them."""


def read(obs, *, sample: str):
    vals = obs["samples"].get(sample)
    if not vals or not obs.get("window_s"):
        return None
    return 100.0 * sum(vals) / (obs["window_s"] * 1e3)
