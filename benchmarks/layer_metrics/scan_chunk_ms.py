"""Streaming scan: host time on the query's critical path per streamed
chunk.  Over the window's last queries (the server keeps 32 summaries):
the time the chunk loop waited for the prefetching reader (decode, pack
and transfer run ahead on its thread; `io.parquet.prefetch.consumer_idle_s`)
plus the loop's own time per chunk (`engine.stream.chunk_latency_s`:
dispatch of the fused segment; its timer starts after the chunk was
fetched), over the chunks."""


def read(ctx):
    trace_ids = {c.trace_id for c in ctx["loop"].clients}
    seconds = chunks = 0
    for q in ctx["snap_end"].get("queries", ()):
        hist = q.get("histograms", {}).get("engine.stream.chunk_latency_s")
        if q.get("trace_id") in trace_ids and hist and hist["count"]:
            seconds += hist["sum"] + q.get("timers", {}).get(
                "io.parquet.prefetch.consumer_idle_s", 0.0)
            chunks += hist["count"]
    return seconds / chunks * 1e3 if chunks else None
