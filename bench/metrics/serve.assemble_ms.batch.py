"""Mean duration of the ``serve.assemble`` span, the engine's locked snapshot
of its slots through the page table, over the traced stretch, on the
profiler's clock."""
from phases import host_span_ms


def read(r):
    return host_span_ms(r, "serve.assemble")
