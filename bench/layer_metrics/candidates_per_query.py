"""Search step (``core/serve_search.py``): verified candidate slots per
query, the ``candidates`` of the program's ``batch.complete`` spans
(the per-query counts the search returns, summed over a batch's real
rows; on a sharded collection the psum over shards) over their
``rows``.  A count of work, not a time: it repeats exactly for a seed,
and at most L x M x B a shard.  Moves ``qps``: each candidate is a
vector the verify step reads.  A program whose spans do not carry the
count reads nothing."""


def read(ctx):
    spans = [s for s in ctx.spans
             if s.name == "batch.complete" and "candidates" in s.args]
    rows = sum(s.args["rows"] for s in spans)
    if not rows:
        return None
    return sum(s.args["candidates"] for s in spans) / rows
