"""Host milliseconds per step of the program's ``graph.relation_plan``
span: ``relation_plan_of`` building a graph's plan on a cache miss
(``ell_to_coo`` back out of the ELL, then ``build_relation_plan``)."""

import program_trace


def read(ctx):
    return program_trace.span_ms(ctx, "graph.relation_plan")
