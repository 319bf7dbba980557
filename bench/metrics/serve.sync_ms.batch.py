"""Mean duration of the ``serve.sync`` span, the host's wait for the step's
next tokens, over the traced stretch, on the profiler's clock."""
from phases import host_span_ms


def read(r):
    return host_span_ms(r, "serve.sync")
