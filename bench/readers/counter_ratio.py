"""One obs counter over the sum of others: a share, or a number a request."""

from __future__ import annotations

from bench.readers import counter_sum


def read(args: dict, sources: dict):
    """``counter`` ÷ the sum of the counters under ``over`` (times 100 where
    ``percent``). None when nothing was counted under ``over``, or when the
    program never counted ``counter`` and it is not one of ``over`` (a share
    of what WAS counted is 0; a program without the counter reads nothing)."""
    snapshot = sources["snapshot"]
    name = args["counter"]
    over = sum(counter_sum(snapshot, n) for n in args["over"])
    counted = any(c["name"] == name for c in snapshot["counters"])
    if not over or not (counted or name in args["over"]):
        return None
    value = counter_sum(snapshot, name) / over
    return 100.0 * value if args.get("percent") else value
