"""Layer library.

Each reference C++ Layer subclass (/root/reference/paddle/gserver/layers/,
64 REGISTER_LAYER types) becomes a pure function
``(cfg, inputs, ctx) -> Argument`` registered by the same type string the
config_parser emits. Importing this package registers everything.
"""

from paddle_tpu.layers.base import LayerContext, layer_registry, register_layer, forward_layer
import paddle_tpu.layers.core  # noqa: F401
import paddle_tpu.layers.cost  # noqa: F401
import paddle_tpu.layers.sequence  # noqa: F401
import paddle_tpu.layers.recurrent  # noqa: F401
import paddle_tpu.layers.vision  # noqa: F401
import paddle_tpu.layers.misc  # noqa: F401
import paddle_tpu.layers.structured  # noqa: F401
import paddle_tpu.layers.attention  # noqa: F401
import paddle_tpu.layers.moe  # noqa: F401
import paddle_tpu.layers.gated_mlp  # noqa: F401

__all__ = ["LayerContext", "layer_registry", "register_layer", "forward_layer"]
