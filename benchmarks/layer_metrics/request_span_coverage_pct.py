"""Client / bridge, the tracing's own guard: the share of a PLAN_EXECUTE
request's server time that lies in one of six timed, disjoint stretches of
it — growth of `bridge.plan.decode_s` + `engine.plan.prepare_s` +
`engine.sched.queue_wait_s` + `engine.precompute_s` + `engine.stream_s` +
`engine.post_stream_s` over growth of `bridge.op.plan_execute_s` (request
received -> reply written), times 100.  What is missing from 100 is host
time no span names: the frame's parse, the query context, `execute`'s own
walk before the precompute, the reply.  A program without the new spans
gives nothing to read."""

import span_reduce      # benchmarks/ is on the path of every reader

REQUEST = "bridge.op.plan_execute_s"
STRETCHES = ("bridge.plan.decode_s", "engine.plan.prepare_s",
             "engine.sched.queue_wait_s", "engine.precompute_s",
             "engine.stream_s", "engine.post_stream_s")
NEW = ("engine.plan.prepare_s", "engine.precompute_s")


def read(ctx):
    request_s, requests = span_reduce.hist_growth(ctx, REQUEST)
    grown = {name: span_reduce.hist_growth(ctx, name) for name in STRETCHES}
    if not requests or request_s <= 0 \
            or not all(grown[name][1] for name in NEW):
        return None
    return sum(seconds for seconds, _ in grown.values()) / request_s * 100
