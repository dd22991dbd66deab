"""The ONE bucket-rounding model shared by scheduler and verifier.

The device facade pads every batch up to a power-of-two bucket so only
O(log N) distinct graphs ever compile; the scheduler scores window
occupancy against the same buckets.  Those two used to carry private
copies of the rounding helper (``_bucket16`` in ``crypto/scheduler.py``
vs ``_bucket`` in ``crypto/verifier.py``) — a drift waiting to happen:
a scheduler that thinks a 17-row window fills a 16-bucket while the
verifier pads it to 32 reports fictional occupancy.  This module is the
single source of truth, and it must stay importable WITHOUT JAX (the
scheduler and the bench parent are JAX-free).
"""

from __future__ import annotations


def bucket_round(n: int, minimum: int = 16) -> int:
    """Smallest power-of-two-times-``minimum`` bucket holding ``n`` rows
    (``n <= 0`` maps to the minimum bucket): 1..16 -> 16, 17 -> 32,
    129 -> 256 at the default floor."""
    b = minimum
    while b < n:
        b *= 2
    return b


def lane_chunk_cap(max_batch: int, lanes: int, min_split: int = 1) -> int:
    """The most rows one mesh lane is handed of a window of at most
    ``max_batch`` rows: a larger window splits into near-equal chunks on
    distinct lanes (never into chunks below ``min_split``).  The
    scheduler places by it and the mesh verifier warms by it, so a lane
    is never handed a bucket its device has not run."""
    return max(min_split, -(-max_batch // lanes))
