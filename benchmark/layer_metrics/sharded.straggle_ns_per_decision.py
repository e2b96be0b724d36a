"""Shard lanes: ns per decision in the program's
``ratelimiter.stream.shard_straggle`` timer across the window of a
sharded cell: per chunk with two or more shards, the slowest lane's job
minus the median lane's, how long the busiest shard (the hottest key's)
holds the chunk.  A program without the timer reports nothing."""


def read(r):
    if r.driver != "stream_sharded":
        return None
    return r.per_decision_ns("ratelimiter.stream.shard_straggle")
