"""Host milliseconds per step spent packing: the benchmark's own ``pack``
spans around ``pack_graph_parallel`` and ``relation_plan_of``."""


def read(ctx):
    t = ctx["spans"].get("pack")
    if not t or not ctx["steps"]:
        return None
    return t * 1e3 / ctx["steps"]
