"""Bytes that a stream call's work needs, counted from the work and not
from any kernel's shapes or padding, so that the share reads the same
work whatever implements it.

Per distinct key a call touches: one read and one write of the per-key
state the reference semantics keeps, at i64 per field:
token bucket {tokens, last_refill} (the TTL deadline follows from
last_refill), sliding window the two buckets' counts and deadlines and
the window start (``SWState``, 5 fields).  Per request lane: its key's
row as one i32 in, and one allow bit out.  Requests with one permit
carry no permits column.
"""

from __future__ import annotations

STATE_BYTES = {"token_bucket": 2 * 8, "sliding_window": 5 * 8}
LANE_IN_BYTES = 4
LANE_OUT_BYTES = 1 / 8


def stream_call_bytes(algorithm: str, lanes: int, uniques: int) -> float:
    return (uniques * 2 * STATE_BYTES[algorithm]
            + lanes * (LANE_IN_BYTES + LANE_OUT_BYTES))


def roofline_share(bytes_needed: float, busy_s: float,
                   peak_bytes_per_s: float) -> float:
    """Share (%) of the HBM roofline: the least time the bytes need at
    the peak, over the device's busy time."""
    return 100.0 * bytes_needed / (peak_bytes_per_s * busy_s)
