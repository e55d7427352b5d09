"""Network — topological execution of a layer graph.

TPU-native replacement for the reference's ``NeuralNetwork``
(/root/reference/paddle/gserver/gradientmachines/NeuralNetwork.cpp:230,279):
there, stateful Layer objects run hand-written forward then reverse-order
backward; here the whole walk happens inside a traced function, jax.grad
derives the backward, and XLA fuses across layer boundaries.

Sub-models: a ``recurrent_layer_group`` layer in the parent list hands off
to the recurrent-group executor (paddle_tpu.graph.recurrent_group), the
analog of RecurrentGradientMachine.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax

from paddle_tpu.graph.argument import Argument
from paddle_tpu.layers.base import LayerContext, forward_layer
from paddle_tpu.proto import LayerConfig, ModelConfig, SubModelConfig


def recompute_block(run):
    """``run`` under one jax.checkpoint whose backward recomputes all of it
    but the residuals an op module NAMED (`jax.ad_checkpoint.
    checkpoint_name`; the modules are imported here, when a block is
    traced: they pull in Pallas). A name is admitted where the residual is
    one that only a kernel's forward can remake, and is no larger than one
    of the kernel's own inputs: dear to redo, small to hold. Today the
    flash kernel's ``out`` and ``lse``; a block with no such kernel keeps
    nothing."""
    from paddle_tpu.ops import pallas_attention

    names = pallas_attention.KEPT_RESIDUALS
    return jax.checkpoint(
        run, policy=jax.checkpoint_policies.save_only_these_names(*names))


class Network:
    """Executable view of (a sub-model of) a ModelConfig."""

    def __init__(self, model: ModelConfig, submodel: Optional[SubModelConfig] = None):
        self.model = model
        self.layer_map: Dict[str, LayerConfig] = {l.name: l for l in model.layers}
        self.submodel_map: Dict[str, SubModelConfig] = {s.name: s for s in model.sub_models}
        if submodel is None and model.sub_models:
            submodel = self.submodel_map.get("root")
        self.submodel = submodel
        if submodel is not None:
            names = list(submodel.layer_names)
            if submodel.name == "root":
                # multi_nn (ref MultiNetwork, gradientmachines/MultiNetwork.h:
                # 25): plain non-recurrent sub-models are independent
                # sub-networks trained jointly — execute their layers after
                # the root's (each depends only on its own data layers)
                for s in model.sub_models:
                    if s.name != "root" and not s.is_recurrent_layer_group:
                        names.extend(n for n in s.layer_names if n not in names)
        else:
            names = [l.name for l in model.layers]
        self.layers: List[LayerConfig] = [self.layer_map[n] for n in names]
        if submodel is not None:
            self.input_layer_names = list(submodel.input_layer_names)
            self.output_layer_names = list(submodel.output_layer_names)
        else:
            self.input_layer_names = list(model.input_layer_names)
            self.output_layer_names = list(model.output_layer_names)

    def forward(self, ctx: LayerContext, in_args: Dict[str, Argument]) -> Dict[str, Argument]:
        """Run all layers; returns ctx.outputs (every layer's output)."""
        layers = self.layers
        for i, cfg in enumerate(layers):
            if cfg.name in ctx.outputs:
                continue
            if ctx.remat_blocks and cfg.remat_block and cfg.type not in (
                    "data", "recurrent_layer_group"):
                j = i
                while j < len(layers) and layers[j].remat_block == cfg.remat_block:
                    j += 1
                self._forward_block(ctx, layers[i:j])
                continue
            if cfg.type == "data":
                if cfg.name not in in_args:
                    raise KeyError(f"no data fed for input layer {cfg.name!r}")
                forward_layer(cfg, [in_args[cfg.name]], ctx)
            elif cfg.type == "recurrent_layer_group":
                from paddle_tpu.graph.recurrent_group import forward_recurrent_group

                forward_recurrent_group(self, cfg, ctx)
            else:
                ins = [self._lookup_input(ctx, ic.input_layer_name, ic.input_layer_argument)
                       for ic in cfg.inputs]
                forward_layer(cfg, ins, ctx)
        return ctx.outputs

    def _forward_block(self, ctx: LayerContext, block: List[LayerConfig]) -> None:
        """One run of layers that share a `remat_block`, under one
        jax.checkpoint: what the block reads of earlier layers (and the
        parameters) are its saved inputs, every layer output and published
        extra (`<layer>@<name>`) of it is a result, and backward recomputes
        the inside, all but the residuals an op module named
        (`recompute_block`). Side tables (`ctx.logits`, `ctx.nhwc`, ...) do
        not cross the block's edge."""
        inside = {c.name for c in block}
        reads = {(ic.input_layer_name, ic.input_layer_argument)
                 for c in block for ic in c.inputs
                 if ic.input_layer_name and ic.input_layer_name not in inside}
        read = {self._key(n, a): self._lookup_input(ctx, n, a) for n, a in sorted(reads)}

        def run(params, read):
            sub = dataclasses.replace(
                ctx, params=params, outputs=dict(read),
                state_updates={}, nhwc={}, logits={}, conv_stats={})
            for c in block:
                ins = [self._lookup_input(sub, ic.input_layer_name, ic.input_layer_argument)
                       for ic in c.inputs]
                forward_layer(c, ins, sub)
            made = {k: v for k, v in sub.outputs.items() if k not in read}
            return made, sub.state_updates

        with jax.named_scope(f"remat_block:{block[0].remat_block}"):
            made, updates = recompute_block(run)(ctx.params, read)
        ctx.outputs.update(made)
        ctx.state_updates.update(updates)

    @staticmethod
    def _key(name: str, arg_name: str = "") -> str:
        return f"{name}@{arg_name}" if arg_name else name

    def _lookup_input(self, ctx: LayerContext, name: str, arg_name: str = "") -> Argument:
        if not name:
            # parameter-only input slot (e.g. batch_norm moving stats)
            return Argument()
        key = self._key(name, arg_name)
        if key not in ctx.outputs:
            raise KeyError(
                f"layer output {key!r} not available; computed: {sorted(ctx.outputs)}"
            )
        return ctx.outputs[key]
