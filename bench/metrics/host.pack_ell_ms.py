"""Host milliseconds per step of the program's ``graph.pack_ell`` span:
``pack_graph_parallel``'s thread pool packing the three ELL adjacency
pairs (the feature upload after it is outside the span)."""

import program_trace


def read(ctx):
    return program_trace.span_ms(ctx, "graph.pack_ell")
