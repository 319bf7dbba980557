"""Mean duration of the ``serve.loop`` span, one iteration of a replica's
loop (admission, the engine step, resolving finished requests), over the
traced stretch, on the profiler's clock."""
from phases import host_span_ms


def read(r):
    return host_span_ms(r, "serve.loop")
