"""Device self time a step under the sparse-expert layers' `router`,
`dispatch` and `combine` scopes: what dropless routing costs beside the
products themselves (the router's product and top-k, the sort, the gather
of each chunk's rows, the scatter-add back)."""

from perfbench import scope_times


def read(view):
    return scope_times.scope_ms(
        view, scope_times.MOE + r"(?:router|dispatch|combine)(?:/|$)")
