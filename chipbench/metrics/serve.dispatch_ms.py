"""Mean host time of ``GraphServeEngine.run_prepared`` per request (pad
into the bucket, host to device, the compiled bucket forward, readback),
in ms: the harness's span around the engine instance's bound method."""


def read(r):
    t = r.span("bench.dispatch")
    return 1e3 * sum(t) / len(t) if t else None
