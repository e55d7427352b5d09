"""Device self time a step under the `gated_mlp:<name>` layer scopes (a
dense gated feed-forward: a block's dense MLP, a sparse layer's shared
expert), forward, recomputed and backward."""

from perfbench import scope_times


def read(view):
    return scope_times.scope_ms(view, r"(?:^|[/(])gated_mlp:[^/)]*")
