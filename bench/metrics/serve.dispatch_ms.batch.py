"""Mean duration of the ``serve.dispatch`` span, the inputs' transfer and the
jitted step's dispatch, over the traced stretch, on the profiler's clock."""
from phases import host_span_ms


def read(r):
    return host_span_ms(r, "serve.dispatch")
