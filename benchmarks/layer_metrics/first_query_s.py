"""Plan cache, optimizer, verifier (and, on a checkout's first run, the
compiler): the client's clock on the run's first PLAN_EXECUTE + export."""


def read(ctx):
    return ctx["warm"].get("first_query_s")
