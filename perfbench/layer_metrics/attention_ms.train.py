"""Device self time a step under the attention layers' scopes (`qkv`,
`core`, `out` and whatever else the layer runs), forward, recomputed and
backward."""

from perfbench import scope_times


def read(view):
    return scope_times.scope_ms(view, scope_times.ATTENTION)
