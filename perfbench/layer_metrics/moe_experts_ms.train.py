"""Device self time a step under the sparse-expert layers' `experts`
scopes: the grouped matrix products of the experts held, forward,
recomputed and backward (XLA's own expansion of `ragged_dot` among them, taken
by instruction name: `scope_times.EXPERT_PRODUCTS`)."""

from perfbench import scope_times


def read(view):
    return scope_times.scope_ms(view, scope_times.EXPERTS, scope_times.EXPERT_PRODUCTS)
