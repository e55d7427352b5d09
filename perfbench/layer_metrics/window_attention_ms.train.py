"""Device self time a step under the attention scopes (`qkv`, `core`,
`gate`, `out`) of the layers the configuration's `layer_types` call
sliding, forward, recomputed and backward (`attention_ms.train` is the sum
over every attention layer)."""

from perfbench import attention_kinds


def read(view):
    return attention_kinds.kind_ms(view, "sliding_attention")
