"""Mean duration of the ``serve.apply`` span, the engine's locked apply of
the step's tokens, over the traced stretch, on the profiler's clock."""
from phases import host_span_ms


def read(r):
    return host_span_ms(r, "serve.apply")
