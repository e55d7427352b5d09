"""GradientMachine — parameters + jitted forward/loss/grad over a model.

TPU-native replacement for the reference's ``GradientMachine`` family
(/root/reference/paddle/gserver/gradientmachines/GradientMachine.h:73):
``forward``/``backward`` over stateful layers become pure functions of a
parameter pytree; ``MultiGradientMachine``'s thread-ring data parallelism
is subsumed by sharding the same functions over a mesh (see
paddle_tpu.parallel). Gradients come from jax.grad of the summed cost
outputs — replacing every hand-written Layer::backward.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.graph.argument import Argument
from paddle_tpu.graph.network import Network
from paddle_tpu.layers.base import LayerContext
from paddle_tpu.ops.init import init_parameter
from paddle_tpu.proto import ModelConfig, ParameterConfig

Array = jax.Array
Params = Dict[str, Array]


def compute_dtype_of(opt_config) -> Optional[Any]:
    """Resolve OptimizationConfig.dtype ('float32'|'bfloat16') to the
    narrow compute dtype, or None for plain f32 training."""
    name = getattr(opt_config, "dtype", "float32") or "float32"
    if name in ("bfloat16", "bf16"):
        return jnp.bfloat16
    if name in ("float32", "fp32", ""):
        return None
    raise ValueError(f"unsupported OptimizationConfig.dtype {name!r}")


class GradientMachine:
    def __init__(self, model: ModelConfig, dtype=jnp.float32, compute_dtype=None,
                 scan_unroll: int = 1, pallas_rnn: bool = False,
                 pallas_flat: bool = False,
                 conv_s2d: bool = False, conv_stats_mode: str = "",
                 pallas_decoder: bool = False):
        self.model = model
        self.network = Network(model)
        self.dtype = dtype
        # mixed precision: master params stay `dtype`; activations/matmuls
        # run in `compute_dtype` (bf16 on the MXU). None = everything in
        # `dtype` (see LayerContext.compute_dtype for the cast rules).
        self.compute_dtype = None if compute_dtype == jnp.float32 else compute_dtype
        # lax.scan unroll factor for recurrent layers/groups
        # (OptimizationConfig.scan_unroll)
        self.scan_unroll = max(1, int(scan_unroll))
        # recurrent layers via the fused Pallas kernels (ops/pallas_lstm)
        self.pallas_rnn = bool(pallas_rnn)
        # their transpose-free batch-major interface (A/B knob)
        self.pallas_flat = bool(pallas_flat)
        # stem conv space-to-depth rewrite (layers/vision.py)
        self.conv_s2d = bool(conv_s2d)
        # fused attention-GRU decoder groups (ops/pallas_attention_gru)
        self.pallas_decoder = bool(pallas_decoder)
        # fused 1x1-conv + BN-statistics mode ("gram" | "pallas" | "")
        self.conv_stats_mode = str(conv_stats_mode or "")
        if self.conv_stats_mode not in ("", "gram", "pallas"):
            # an unknown value would silently disable the feature and
            # poison the very A/B measurement the knob exists for
            raise ValueError(
                f"conv_stats_mode must be '', 'gram' or 'pallas', "
                f"got {conv_stats_mode!r}"
            )
        self.mesh = None  # set by the trainer when running on a mesh
        self.param_configs: Dict[str, ParameterConfig] = {p.name: p for p in model.parameters}
        # data layers whose every consumer is a cost layer carry targets/
        # labels/weights, not features — exempt them from the bf16 input
        # cast so loss math sees un-rounded values (code-review finding)
        data_names = {l.name for l in model.layers if l.type == "data"}
        consumers: Dict[str, set] = {}
        for layer in model.layers:
            for ic in layer.inputs:
                if ic.input_layer_name in data_names:
                    consumers.setdefault(ic.input_layer_name, set()).add(layer.type)
        costish = self.COST_TYPES | {"classification_error", "lambda_cost"}
        self.no_cast_inputs = frozenset(
            n for n, types in consumers.items() if types and types <= costish
        )

    # ------------------------------------------------------------- params

    def init_params(self, seed: int = 1) -> Params:
        rng = jax.random.PRNGKey(seed)
        params: Params = {}
        for i, (name, cfg) in enumerate(sorted(self.param_configs.items())):
            params[name] = init_parameter(jax.random.fold_in(rng, i), cfg, self.dtype)
        return params

    def trainable_mask(self) -> Dict[str, bool]:
        return {n: not c.is_static for n, c in self.param_configs.items()}

    # ------------------------------------------------------------ forward

    def forward(
        self,
        params: Params,
        in_args: Dict[str, Argument],
        pass_type: str = "test",
        rng: Optional[Array] = None,
        table_overrides=None,
        gen_capture=None,
        remat_blocks: bool = False,
    ) -> Tuple[Dict[str, Argument], Dict[str, Array]]:
        """Run the graph; returns (all layer outputs, state updates).

        ``gen_capture``: a dict sink making generator groups capture their
        prepared decode inputs instead of running the beam loop — the
        serving engine's prefill seam (graph/decode_step.py)."""
        ctx = LayerContext(
            params=params, model=self.model, pass_type=pass_type, rng=rng,
            dtype=self.dtype, mesh=self.mesh, table_overrides=table_overrides,
            compute_dtype=self.compute_dtype, no_cast_inputs=self.no_cast_inputs,
            scan_unroll=self.scan_unroll, pallas_rnn=self.pallas_rnn,
            pallas_flat=self.pallas_flat,
            conv_s2d=self.conv_s2d, conv_stats_mode=self.conv_stats_mode,
            pallas_decoder=self.pallas_decoder, gen_capture=gen_capture,
            remat_blocks=remat_blocks,
        )
        self.network.forward(ctx, in_args)
        return ctx.outputs, ctx.state_updates

    def output_args(self, outputs: Dict[str, Argument]) -> Dict[str, Argument]:
        return {n: outputs[n] for n in self.network.output_layer_names}

    # --------------------------------------------------------------- loss

    # layer types whose output is a differentiable per-sample cost — only
    # these contribute to the training loss (a prediction output like
    # maxid can legally sit next to the cost in output_layer_names)
    COST_TYPES = frozenset(
        {
            "multi-class-cross-entropy",
            "multi_class_cross_entropy_with_selfnorm",
            "square_error",
            "multi_binary_label_cross_entropy",
            "soft_binary_class_cross_entropy",
            "rank-cost",
            "huber",
            "lambda_cost",
            "ctc",
            "crf",
            "nce",
            "hsigmoid",
        }
    )

    def has_cost(self) -> bool:
        layer_map = self.network.layer_map
        return any(
            layer_map[n].type in self.COST_TYPES
            for n in self.network.output_layer_names
            if n in layer_map
        )

    def cost_layer_names(self):
        layer_map = self.network.layer_map
        return [
            n
            for n in self.network.output_layer_names
            if n in layer_map and layer_map[n].type in self.COST_TYPES
        ]

    def total_cost(self, outputs: Dict[str, Argument]) -> Array:
        """Mean per-sample cost summed across cost-layer outputs.

        The analog of Argument::sumCosts over the out args
        (/root/reference/paddle/parameter/Argument.h:168), normalized by
        batch size so gradients are per-sample means.
        """
        layer_map = self.network.layer_map
        total = None
        for name in self.network.output_layer_names:
            cfg = layer_map.get(name)
            if cfg is None or cfg.type not in self.COST_TYPES:
                continue
            arg = outputs[name]
            with jax.named_scope("cost"):
                c = jnp.mean(arg.value[:, 0])
                total = c if total is None else total + c
        if total is None:
            raise ValueError("no cost outputs among output layers")
        return total

    def loss_fn(
        self,
        params: Params,
        in_args: Dict[str, Argument],
        rng: Optional[Array] = None,
        pass_type: str = "train",
        remat_blocks: bool = False,
    ):
        outputs, state_updates = self.forward(
            params, in_args, pass_type, rng, remat_blocks=remat_blocks)
        return self.total_cost(outputs), (outputs, state_updates)

    # --------------------------------------------------- sparse prefetch

    def sparse_prefetch_plan(self):
        """Which sparse_update tables can take the row-sparse gradient path.

        The analog of GradientMachine::prefetch (/root/reference/paddle/
        trainer/TrainerInternal.cpp:91-95): sparse rows are identified from
        the *input ids*, before forward. A table qualifies when every use
        of the parameter is a table projection reading ids straight from a
        data layer (the reference has the same reach — it prefetches from
        inArgs only). Returns [(param_name, data_layer_name)]; parameters
        used any other way fall back to the dense-gradient row-scan path.
        """
        sparse_names = {
            n for n, c in self.param_configs.items() if c.sparse_update and not c.is_static
        }
        if not sparse_names:
            return []
        layer_map = self.network.layer_map
        uses: Dict[str, list] = {n: [] for n in sparse_names}
        for layer in self.model.layers:
            for ic in layer.inputs:
                pn = ic.input_parameter_name
                if pn not in sparse_names:
                    continue
                src = layer_map.get(ic.input_layer_name)
                ok = (
                    ic.proj_conf is not None
                    and ic.proj_conf.type == "table"
                    and src is not None
                    and src.type == "data"
                )
                uses[pn].append((ic.input_layer_name, ok))
            if layer.bias_parameter_name in sparse_names:
                uses[layer.bias_parameter_name].append(("", False))
        plan = []
        for pn, sites in sorted(uses.items()):
            if sites and all(ok for _, ok in sites):
                plan.extend((pn, ln) for ln, _ in sites)
        return plan

    def grad_fn(self, remat: str = "none", sparse: bool = True):
        """Returns f(params, in_args, rng) → (loss, grads, outputs, state_updates).

        Gradients for prefetchable sparse_update tables come back as
        RowSparseGrad (ids + occurrence rows, O(batch·seq) not O(V)) —
        see paddle_tpu.optimizer.sparse; everything else is dense.
        ``sparse=False`` forces dense gradients everywhere (needed when
        gradients must be accumulated across batches — RowSparseGrad
        shapes vary per batch).

        ``remat="full"`` (OptimizationConfig.remat) wraps the loss in a
        bare jax.checkpoint: backward recomputes the whole forward instead
        of storing activations — the HBM-for-FLOPs trade, for least
        memory; it keeps nothing, a kernel's named residuals neither (no
        cell or demo config runs it with the rule attention).
        ``remat="block"`` checkpoints each run of layers the config tagged
        with one ``remat_block`` (Network._forward_block): a block's
        activations are recomputed from its saved input, one block at a
        time, all but the residuals its kernels named."""
        plan = self.sparse_prefetch_plan() if sparse else []
        loss_fn = self.loss_fn
        if remat == "full":
            loss_fn = jax.checkpoint(loss_fn)
        elif remat == "block":
            loss_fn = functools.partial(self.loss_fn, remat_blocks=True)
        elif remat not in ("", "none"):
            raise ValueError(f"unsupported remat mode {remat!r}")

        def f(params: Params, in_args: Dict[str, Argument], rng: Optional[Array]):
            if not plan:
                (loss, (outputs, state_updates)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params, in_args, rng)
            else:
                loss, grads, outputs, state_updates = self._sparse_value_and_grad(
                    plan, params, in_args, rng, remat=remat
                )
            # static parameters get no gradient
            for n, cfg in self.param_configs.items():
                if cfg.is_static and n in grads:
                    grads[n] = jnp.zeros_like(grads[n])
            return loss, grads, outputs, state_updates

        return f

    def _sparse_value_and_grad(self, plan, params, in_args, rng, remat="none"):
        from paddle_tpu.optimizer.sparse import RowSparseGrad

        sparse_pnames = {pn for pn, _ in plan}
        # prefetch: gather the occurrence rows OUTSIDE autodiff and make
        # them the differentiable leaves; the table itself is frozen
        rows_in = {}
        for pn, dname in plan:
            ids = in_args[dname].ids
            rows_in[(pn, dname)] = jnp.take(params[pn], ids, axis=0)
        dense_params = {k: v for k, v in params.items() if k not in sparse_pnames}
        frozen = {k: jax.lax.stop_gradient(params[k]) for k in sparse_pnames}

        def loss2(dense_params, rows):
            full = dict(dense_params, **frozen)
            outputs, state_updates = self.forward(
                full, in_args, "train", rng, table_overrides=rows,
                remat_blocks=remat == "block",
            )
            return self.total_cost(outputs), (outputs, state_updates)

        if remat == "full":
            loss2 = jax.checkpoint(loss2)
        (loss, (outputs, state_updates)), (dgrads, rgrads) = jax.value_and_grad(
            loss2, argnums=(0, 1), has_aux=True
        )(dense_params, rows_in)
        grads: Dict[str, Any] = dict(dgrads)
        by_param: Dict[str, list] = {}
        for (pn, dname), rg in rgrads.items():
            ids = in_args[dname].ids.reshape(-1)
            by_param.setdefault(pn, []).append((ids, rg.reshape(ids.shape[0], -1)))
        for pn, pieces in by_param.items():
            ids = jnp.concatenate([i for i, _ in pieces])
            rows = jnp.concatenate([r for _, r in pieces])
            grads[pn] = RowSparseGrad(ids=ids, rows=rows, nrows=params[pn].shape[0])
        return loss, grads, outputs, state_updates

    # --------------------------------------------------- gradient checking

    def check_gradient(
        self,
        params: Params,
        in_args: Dict[str, Argument],
        epsilon: float = 1e-4,
        max_entries: int = 20,
        rng: Optional[Array] = None,
        rtol: float = 5e-2,
    ) -> Dict[str, float]:
        """Finite-difference check, the analog of Trainer::checkGradient
        (/root/reference/paddle/trainer/Trainer.cpp:313-387) and the
        test_LayerGrad methodology. Returns max relative diff per param.

        Runs in float64 (the reference's WITH_DOUBLE gradient-check mode) —
        fp32 finite differences are too noisy for small gradients.
        """
        saved = self.compute_dtype
        self.compute_dtype = None  # bf16 forward would swamp the FD signal
        try:
            with jax.enable_x64(True):
                return self._check_gradient_x64(params, in_args, epsilon, max_entries, rng, rtol)
        finally:
            self.compute_dtype = saved

    def _check_gradient_x64(self, params, in_args, epsilon, max_entries, rng, rtol):
        import numpy as np

        cast = lambda x: x.astype(jnp.float64) if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating) else x
        params = {k: cast(v) for k, v in params.items()}
        in_args = jax.tree_util.tree_map(cast, in_args)
        loss = jax.jit(lambda p: self.loss_fn(p, in_args, rng)[0])
        grads = jax.jit(jax.grad(lambda p: self.loss_fn(p, in_args, rng)[0]))(params)
        report = {}
        key = jax.random.PRNGKey(0)
        for name, g in grads.items():
            if self.param_configs[name].is_static:
                continue
            flat = np.asarray(g).ravel()
            n = flat.size
            key, sub = jax.random.split(key)
            idxs = np.asarray(jax.random.choice(sub, n, (min(max_entries, n),), replace=False))
            worst = 0.0
            base = np.asarray(params[name]).ravel()
            for i in idxs:
                p_plus = dict(params)
                v = base.copy()
                v[i] += epsilon
                p_plus[name] = jnp.asarray(v.reshape(params[name].shape))
                v2 = base.copy()
                v2[i] -= epsilon
                p_minus = dict(params)
                p_minus[name] = jnp.asarray(v2.reshape(params[name].shape))
                num = (float(loss(p_plus)) - float(loss(p_minus))) / (2 * epsilon)
                ana = float(flat[i])
                denom = max(abs(num), abs(ana), 1e-6)
                worst = max(worst, abs(num - ana) / denom)
            report[name] = worst
        return report
