"""Device self time a step under the attention layers' `latent_down` (the
input's product down to the latent and the shared rotary key lanes, the
latent's norm, the key lanes' turn) and `latent_up` (the latent's product up
to each head's key and value lanes) scopes, forward, recomputed and
backward: what a latent costs beside an ordinary head's key and value
products. A program with no such scope gives nothing to read."""

from perfbench import scope_times


def read(view):
    return scope_times.scope_ms(
        view, r"(?:^|[/(])multi_head_attention:[^/]*/(?:.*/)?latent_(?:down|up)(?:/|$)")
