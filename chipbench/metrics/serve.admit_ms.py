"""Mean host time of ``GraphServeEngine.prepare`` per request (two-hop
sampling, union block, bucket choice), in ms: the harness's span around
the engine instance's bound method."""


def read(r):
    t = r.span("bench.admit")
    return 1e3 * sum(t) / len(t) if t else None
