"""FFModel — the central model-building and training API.

TPU-native equivalent of the reference's ``FFModel`` (reference
``include/flexflow/model.h:396-1281``, ``src/runtime/model.cc``): ~70
layer-builder methods append to an operator graph; ``compile()`` lowers the
graph plus optimizer/loss/metrics into executable form. Where the
reference lowers to a Legion task graph placed by the Unity search, we
lower to **one XLA SPMD program**: a jitted train step whose parallelism
comes from sharding annotations over a named device mesh — compilation
*is* the reference's ``begin_trace``/``end_trace`` replay (SURVEY.md §7
design mapping).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .core.mesh import set_mesh as _set_mesh
from .config import FFConfig, get_config
from .core.dtypes import DataType
from .core.graph import Graph, OpNode, TensorRef
from .core.mesh import DATA_AXIS, MODEL_AXIS, MachineSpec
from .core.tensor import TensorSpec
from .losses import get_loss
from .metrics import PerfMetrics, compute_metrics
from .optimizers import Optimizer, SGDOptimizer
from .ops.registry import OpContext, get_op

# Computation modes (reference CompMode / InferenceMode enums).
TRAINING = "training"
INFERENCE = "inference"


class Tensor:
    """Symbolic tensor handle returned by layer builders (reference
    ``FFModel`` returns ``Tensor`` layer outputs)."""

    __slots__ = ("model", "ref")

    def __init__(self, model: "FFModel", ref: TensorRef):
        self.model = model
        self.ref = ref

    @property
    def spec(self) -> TensorSpec:
        return self.model.graph.out_spec(self.ref)

    @property
    def shape(self) -> Tuple[int, ...]:
        return self.spec.shape

    @property
    def dtype(self) -> DataType:
        return self.spec.dtype

    def __repr__(self):
        return f"Tensor({self.spec!r} @node{self.ref.node_id}.{self.ref.out_idx})"


class FFModel:
    def __init__(self, config: Optional[FFConfig] = None, seed: int = 0):
        self.config = config or get_config()
        self.graph = Graph()
        self.input_nodes: List[int] = []
        self.seed = seed or self.config.seed
        self.optimizer: Optional[Optimizer] = None
        self.loss_type: Optional[str] = None
        self.metrics_names: Sequence[str] = ()
        self.mesh: Optional[Mesh] = None
        self.params = None
        self.opt_state = None
        self.model_state: Dict[int, Any] = {}
        self._train_step = None
        self._eval_step = None
        self._fwd = None
        self._output_ref: Optional[TensorRef] = None
        self._step_count = 0
        # sharding overrides installed by the parallelize pass
        self._param_pspecs: Optional[Dict[str, Any]] = None
        self._search_report = None
        # per-node activation constraints (SAMPLE/ATTR searched states)
        self._act_constraints: Dict[str, Any] = {}
        self._compile_args: Optional[Dict[str, Any]] = None
        self._recompile_state = None

    # ------------------------------------------------------------------
    # graph construction

    def _add(
        self,
        op_type: str,
        attrs: Dict[str, Any],
        inputs: Sequence[Tensor],
        name: str = "",
    ) -> Union[Tensor, Tuple[Tensor, ...]]:
        in_refs = [t.ref for t in inputs]
        in_specs = [self.graph.out_spec(r) for r in in_refs]
        out_specs = get_op(op_type).infer(in_specs, attrs)
        node = self.graph.add_node(op_type, attrs, in_refs, out_specs, name=name)
        outs = tuple(Tensor(self, TensorRef(node.id, i)) for i in range(len(out_specs)))
        return outs if len(outs) > 1 else outs[0]

    def create_tensor(
        self, shape: Sequence[int], dtype=DataType.FLOAT, name: str = "input"
    ) -> Tensor:
        dt = DataType.from_any(dtype)
        node = self.graph.add_node(
            "input",
            {"shape": tuple(shape), "dtype": dt.value},
            [],
            [TensorSpec(tuple(shape), dt)],
            name=name,
        )
        self.input_nodes.append(node.id)
        return Tensor(self, TensorRef(node.id, 0))

    # --- layer builders (reference model.h:407-805 names) --------------

    def dense(
        self,
        input: Tensor,
        out_dim: int,
        activation: Optional[str] = None,
        use_bias: bool = True,
        kernel_initializer=None,
        bias_initializer=None,
        kernel_regularizer=None,
        name: str = "",
    ) -> Tensor:
        """``kernel_regularizer``: ``("l1"|"l2", lambda)`` — the penalty
        joins the loss through the op aux-loss channel (reference
        Linear + REG_MODE_L1/L2, keras/regularizers.py)."""
        return self._add(
            "dense",
            dict(
                out_dim=out_dim,
                activation=activation,
                use_bias=use_bias,
                kernel_initializer=kernel_initializer,
                bias_initializer=bias_initializer,
                kernel_regularizer=(
                    tuple(kernel_regularizer) if kernel_regularizer else None
                ),
            ),
            [input],
            name,
        )

    def embedding(
        self,
        input: Tensor,
        num_entries: int,
        out_dim: int,
        aggr: str = "none",
        dtype=DataType.FLOAT,
        kernel_initializer=None,
        name: str = "",
    ) -> Tensor:
        return self._add(
            "embedding",
            dict(
                num_entries=num_entries,
                out_dim=out_dim,
                aggr=aggr,
                dtype=DataType.from_any(dtype).value,
                kernel_initializer=kernel_initializer,
            ),
            [input],
            name,
        )

    def constant(self, value, name: str = "") -> Tensor:
        """Inline constant tensor (frontend-imported buffers: position
        ids, masks)."""
        value = np.asarray(value)
        if value.dtype == np.int64:
            value = value.astype(np.int32)
        if value.dtype == np.float64:
            value = value.astype(np.float32)
        return self._add(
            "constant",
            dict(
                shape=tuple(value.shape),
                dtype=str(value.dtype),
                data=value.tobytes(),
            ),
            [],
            name,
        )

    def transformer_decoder_stack(
        self,
        input: Tensor,
        num_layers: int,
        num_heads: int,
        intermediate_size: int,
        num_kv_heads: Optional[int] = None,
        eps: float = 1e-6,
        rope_theta: float = 10000.0,
        remat: bool = True,
        remat_policy: Optional[str] = None,  # None (full) | "dots"
        attention: str = "xla",
        name: str = "",
    ) -> Tensor:
        """N fused causal decoder blocks over (B, S, D) hidden states as
        ONE graph node (ops/fused_transformer.py): scan-over-layers +
        remat + optional Pallas flash attention — the fast-path bridge
        that lets ``compile(auto_parallel=True)`` reach the same program
        quality as the hand-sharded ``models/transformer.make_train_step``
        (the reference's FusedOp + transformer substitutions,
        src/ops/fused.cc)."""
        return self._add(
            "transformer_decoder_stack",
            dict(
                num_layers=num_layers,
                num_heads=num_heads,
                num_kv_heads=num_kv_heads,
                intermediate_size=intermediate_size,
                eps=eps,
                rope_theta=rope_theta,
                remat=remat,
                remat_policy=remat_policy,
                attention=attention,
            ),
            [input],
            name,
        )

    def conv2d(
        self,
        input: Tensor,
        out_channels: int,
        kernel_h: int,
        kernel_w: int,
        stride_h: int = 1,
        stride_w: int = 1,
        padding_h: int = 0,
        padding_w: int = 0,
        activation: Optional[str] = None,
        groups: int = 1,
        use_bias: bool = True,
        kernel_initializer=None,
        bias_initializer=None,
        kernel_regularizer=None,
        name: str = "",
    ) -> Tensor:
        return self._add(
            "conv2d",
            dict(
                out_channels=out_channels,
                kernel_h=kernel_h,
                kernel_w=kernel_w,
                stride_h=stride_h,
                stride_w=stride_w,
                padding_h=padding_h,
                padding_w=padding_w,
                activation=activation,
                groups=groups,
                use_bias=use_bias,
                kernel_initializer=kernel_initializer,
                bias_initializer=bias_initializer,
                kernel_regularizer=(
                    tuple(kernel_regularizer) if kernel_regularizer else None
                ),
            ),
            [input],
            name,
        )

    def pool2d(
        self,
        input: Tensor,
        kernel_h: int,
        kernel_w: int,
        stride_h: int = 1,
        stride_w: int = 1,
        padding_h: int = 0,
        padding_w: int = 0,
        pool_type: str = "max",
        activation: Optional[str] = None,
        name: str = "",
    ) -> Tensor:
        return self._add(
            "pool2d",
            dict(
                kernel_h=kernel_h,
                kernel_w=kernel_w,
                stride_h=stride_h,
                stride_w=stride_w,
                padding_h=padding_h,
                padding_w=padding_w,
                pool_type=pool_type,
                activation=activation,
            ),
            [input],
            name,
        )

    def batch_norm(
        self, input: Tensor, relu: bool = True, eps: float = 1e-5,
        name: str = "",
    ) -> Tensor:
        return self._add("batch_norm", dict(relu=relu, eps=eps), [input], name)

    def layer_norm(
        self,
        input: Tensor,
        axes: Sequence[int] = (-1,),
        elementwise_affine: bool = True,
        eps: float = 1e-5,
        use_bias: bool = True,
        name: str = "",
    ) -> Tensor:
        return self._add(
            "layer_norm",
            dict(
                axes=tuple(axes),
                elementwise_affine=elementwise_affine,
                eps=eps,
                use_bias=use_bias,
            ),
            [input],
            name,
        )

    def rms_norm(self, input: Tensor, eps: float = 1e-6, dim: int = -1, name: str = "") -> Tensor:
        return self._add("rms_norm", dict(eps=eps, dim=dim), [input], name)

    def residual_rms_norm(
        self, input: Tensor, residual: Tensor, eps: float = 1e-6, name: str = ""
    ):
        return self._add("residual_rms_norm", dict(eps=eps), [input, residual], name)

    def residual_layer_norm(
        self,
        input: Tensor,
        residual1: Tensor,
        residual2: Optional[Tensor] = None,
        eps: float = 1e-5,
        elementwise_affine: bool = True,
        use_bias: bool = True,
        name: str = "",
    ):
        inputs = [input, residual1] + ([residual2] if residual2 is not None else [])
        return self._add(
            "residual_layer_norm",
            dict(eps=eps, elementwise_affine=elementwise_affine, use_bias=use_bias),
            inputs,
            name,
        )

    def add_bias_residual_layer_norm(
        self, input: Tensor, residual: Tensor, eps: float = 1e-5, name: str = ""
    ):
        return self._add(
            "add_bias_residual_layer_norm", dict(eps=eps), [input, residual], name
        )

    def sigmoid_silu_multi(self, x1: Tensor, x2: Tensor, name: str = "") -> Tensor:
        return self._add("sigmoid_silu_multi", {}, [x1, x2], name)

    def multihead_attention(
        self,
        query: Tensor,
        key: Tensor,
        value: Tensor,
        embed_dim: int,
        num_heads: int,
        kdim: int = 0,
        vdim: int = 0,
        dropout: float = 0.0,
        bias: bool = True,
        causal: bool = False,
        name: str = "",
    ) -> Tensor:
        return self._add(
            "multihead_attention",
            dict(
                embed_dim=embed_dim,
                num_heads=num_heads,
                kdim=kdim or None,
                vdim=vdim or None,
                dropout=dropout,
                bias=bias,
                causal=causal,
            ),
            [query, key, value],
            name,
        )

    def softmax(self, input: Tensor, axis: int = -1, name: str = "") -> Tensor:
        return self._add("softmax", dict(axis=axis), [input], name)

    def dropout(self, input: Tensor, rate: float = 0.5, name: str = "") -> Tensor:
        return self._add("dropout", dict(rate=rate), [input], name)

    def cast(self, input: Tensor, dtype, name: str = "") -> Tensor:
        return self._add(
            "cast", dict(dtype=DataType.from_any(dtype).value), [input], name
        )

    def concat(self, tensors: Sequence[Tensor], axis: int = 0, name: str = "") -> Tensor:
        return self._add("concat", dict(axis=axis), list(tensors), name)

    def split(self, input: Tensor, sizes: Sequence[int], axis: int = 0, name: str = ""):
        return self._add("split", dict(sizes=tuple(sizes), axis=axis), [input], name)

    def reshape(self, input: Tensor, shape: Sequence[int], name: str = "") -> Tensor:
        return self._add("reshape", dict(shape=tuple(shape)), [input], name)

    def transpose(self, input: Tensor, perm: Sequence[int], name: str = "") -> Tensor:
        return self._add("transpose", dict(perm=tuple(perm)), [input], name)

    def reverse(self, input: Tensor, axis: int = 0, name: str = "") -> Tensor:
        return self._add("reverse", dict(axis=axis), [input], name)

    def flat(self, input: Tensor, name: str = "") -> Tensor:
        return self._add("flat", {}, [input], name)

    def reduce_sum(
        self, input: Tensor, axes: Sequence[int], keepdims: bool = False, name: str = ""
    ) -> Tensor:
        return self._add(
            "reduce", dict(op="sum", axes=tuple(axes), keepdims=keepdims), [input], name
        )

    def mean(
        self, input: Tensor, axes: Sequence[int], keepdims: bool = False, name: str = ""
    ) -> Tensor:
        return self._add(
            "reduce", dict(op="mean", axes=tuple(axes), keepdims=keepdims), [input], name
        )

    def gather(self, input: Tensor, index: Tensor, axis: int = -1, name: str = "") -> Tensor:
        return self._add("gather", dict(axis=axis), [input, index], name)

    def batch_matmul(self, a: Tensor, b: Tensor, name: str = "") -> Tensor:
        return self._add("batch_matmul", {}, [a, b], name)

    # elementwise builders
    # --- MoE builders (reference model.h:509-645) ----------------------

    def top_k(self, input: Tensor, k: int, name: str = ""):
        """Router top-k values+indices (reference ``FFModel::top_k``)."""
        return self._add("top_k", dict(k=k), [input], name)

    def group_by(
        self,
        input: Tensor,
        probs: Tensor,
        k: int,
        capacity_factor: float = 1.25,
        name: str = "",
    ):
        """Dispatch tokens into per-expert buckets (reference
        ``FFModel::group_by``; alpha → capacity_factor)."""
        return self._add(
            "group_by",
            dict(k=k, capacity_factor=capacity_factor),
            [input, probs],
            name,
        )

    def aggregate(
        self,
        expert_out: Tensor,
        combine: Tensor,
        probs: Tensor,
        load_balance_lambda: float = 1e-2,
        name: str = "",
    ):
        """Weighted combine + load-balance loss (reference
        ``FFModel::aggregate`` with λ)."""
        return self._add(
            "aggregate",
            dict(load_balance_lambda=load_balance_lambda),
            [expert_out, combine, probs],
            name,
        )

    def aggregate_spec(
        self,
        expert_out: Tensor,
        combine: Tensor,
        probs: Tensor,
        name: str = "",
    ):
        """Spec-mode combine: fixed routing, no gate gradient / aux loss
        (reference ``FFModel::aggregate_spec``, ops/aggregate_spec.h:14)."""
        return self._add(
            "aggregate_spec", {}, [expert_out, combine, probs], name
        )

    def cache(self, input: Tensor, name: str = ""):
        """Memoize an activation across batches; inference serves the
        cached copy (reference ``FFModel::cache``, ops/cache.h:8)."""
        return self._add("cache", {}, [input], name)

    def moe(
        self,
        input: Tensor,
        num_experts: int,
        top_k: int,
        expert_hidden: int,
        capacity_factor: float = 1.25,
        activation: str = "relu",
        load_balance_lambda: float = 1e-2,
        use_bias: bool = False,
        name: str = "",
    ) -> Tensor:
        """Fused MoE layer (reference ``FFModel::moe``, model.h:622-645)."""
        return self._add(
            "moe",
            dict(
                num_experts=num_experts,
                top_k=top_k,
                expert_hidden=expert_hidden,
                capacity_factor=capacity_factor,
                activation=activation,
                load_balance_lambda=load_balance_lambda,
                use_bias=use_bias,
            ),
            [input],
            name,
        )

    def experts(
        self,
        input: Tensor,
        idx: Tensor,
        gates: Tensor,
        num_experts: int,
        top_k: int,
        expert_hidden: int,
        capacity_factor: float = 2.0,
        activation: str = "gelu",
        name: str = "",
    ) -> Tensor:
        """Fused inference experts on precomputed routing (reference
        ``FFModel::experts``, src/ops/experts.cc)."""
        return self._add(
            "experts",
            dict(
                num_experts=num_experts,
                top_k=top_k,
                expert_hidden=expert_hidden,
                capacity_factor=capacity_factor,
                activation=activation,
            ),
            [input, idx, gates],
            name,
        )

    def _unary(self, op, input, name="", scalar=None):
        attrs = {"op": op}
        if scalar is not None:
            attrs["scalar"] = scalar
        return self._add("element_unary", attrs, [input], name)

    def _binary(self, op, a, b, name=""):
        return self._add("element_binary", dict(op=op), [a, b], name)

    def relu(self, x, name=""):
        return self._unary("relu", x, name)

    def sigmoid(self, x, name=""):
        return self._unary("sigmoid", x, name)

    def tanh(self, x, name=""):
        return self._unary("tanh", x, name)

    def elu(self, x, name=""):
        return self._unary("elu", x, name)

    def gelu(self, x, name=""):
        return self._unary("gelu", x, name)

    def identity(self, x, name=""):
        return self._unary("identity", x, name)

    def exp(self, x, name=""):
        return self._unary("exp", x, name)

    def sin(self, x, name=""):
        return self._unary("sin", x, name)

    def cos(self, x, name=""):
        return self._unary("cos", x, name)

    def pow(self, x, exponent, name=""):
        return self._unary("pow", x, name, scalar=exponent)

    def scalar_multiply(self, x, scalar, name=""):
        return self._unary("scalar_multiply", x, name, scalar=scalar)

    def scalar_add(self, x, scalar, name=""):
        return self._unary("scalar_add", x, name, scalar=scalar)

    def scalar_sub(self, x, scalar, name=""):
        return self._unary("scalar_sub", x, name, scalar=scalar)

    def scalar_truediv(self, x, scalar, name=""):
        return self._unary("scalar_truediv", x, name, scalar=scalar)

    def scalar_compare(self, x, op: str, scalar, name=""):
        """Elementwise compare against a scalar → 0/1 mask in x's dtype
        (op in gt/lt/ge/le/eq)."""
        return self._unary(f"scalar_{op}", x, name, scalar=scalar)

    def add(self, a, b, name=""):
        return self._binary("add", a, b, name)

    def subtract(self, a, b, name=""):
        return self._binary("subtract", a, b, name)

    def multiply(self, a, b, name=""):
        return self._binary("multiply", a, b, name)

    def divide(self, a, b, name=""):
        return self._binary("divide", a, b, name)

    def max(self, a, b, name=""):
        return self._binary("max", a, b, name)

    def min(self, a, b, name=""):
        return self._binary("min", a, b, name)

    # ------------------------------------------------------------------
    # execution

    def _node_attrs(self, node: OpNode) -> Dict[str, Any]:
        d = node.attrs_dict
        d["_node"] = node.id
        return d

    def run_graph(
        self,
        params,
        inputs: Dict[str, Any],
        *,
        training: bool,
        rng=None,
        state=None,
        upto: Optional[TensorRef] = None,
        batch_meta=None,
    ):
        """Interpret the graph — the analog of the reference's per-op task
        launch loop (``FFModel::forward``, reference ``model.cc:2782``),
        except the whole loop is traced into one XLA program under jit."""
        ctx = OpContext(
            training=training,
            rng=rng,
            mesh=self.mesh,
            state=state or {},
            state_updates={} if training else None,
            batch_meta=batch_meta,
        )
        vals: Dict[Tuple[int, int], Any] = {}
        target = upto.node_id if upto is not None else len(self.graph.nodes) - 1
        for node in self.graph.nodes:
            if node.id > target:
                break
            if node.op_type == "input":
                if node.name not in inputs:
                    raise KeyError(f"missing input {node.name!r}")
                vals[(node.id, 0)] = inputs[node.name]
                continue
            op = get_op(node.op_type)
            in_vals = [vals[(r.node_id, r.out_idx)] for r in node.inputs]
            outs = op.forward(
                params.get(node.name, {}), in_vals, self._node_attrs(node), ctx
            )
            spec = self._act_constraints.get(node.name)
            if spec is not None:
                # searched SAMPLE/ATTR states: GSPMD can't infer these
                # from weight shardings, so pin the output layout
                outs = tuple(
                    jax.lax.with_sharding_constraint(o, spec)
                    if hasattr(o, "ndim") and o.ndim >= len(spec)
                    else o
                    for o in outs
                )
            for i, o in enumerate(outs):
                vals[(node.id, i)] = o
        out_ref = upto if upto is not None else TensorRef(target, 0)
        return vals[(out_ref.node_id, out_ref.out_idx)], (ctx.state_updates or {})

    def init_params(self, key=None):
        key = key if key is not None else jax.random.PRNGKey(self.seed)
        params = {}
        for node in self.graph.nodes:
            if node.op_type == "input":
                continue
            op = get_op(node.op_type)
            in_specs = [self.graph.out_spec(r) for r in node.inputs]
            w = op.init(jax.random.fold_in(key, node.id), in_specs, node.attrs_dict)
            if w:
                params[node.name] = w
        return params

    def init_state(self):
        state = {}
        for node in self.graph.nodes:
            op = get_op(node.op_type)
            fn = getattr(op, "init_state", None)
            if fn is None:
                continue
            in_specs = [self.graph.out_spec(r) for r in node.inputs]
            st = fn(in_specs, node.attrs_dict)
            if st:
                state[node.id] = st
        return state

    # ------------------------------------------------------------------
    # compile

    def _make_mesh(self) -> Mesh:
        spec = self.config.machine_spec()
        return spec.make_mesh()

    def _run_unity_search(
        self, output: Optional[Tensor], comp_mode: str
    ) -> Optional[TensorRef]:
        """Unity-style auto-parallelization (reference compile step 2:
        GRAPH_OPTIMIZE_TASK_ID → graph_optimize_task, model.cc:3337,
        graph.cc:2108). Rewrites self.graph, sets mesh degrees and the
        weight-sharding override from the found strategy; honors the
        import/export strategy files (config.h:171-172).

        Returns the ``output`` re-resolved against the (possibly
        rewritten) graph, or None when no output was given. Rewrites
        re-number node ids but preserve NAMES (substitutions.rebuild),
        so mid-graph outputs — metric taps, multi-head graphs — survive
        the search by name."""
        from . import search as unity
        from .core.mesh import MachineSpec

        cfgf = self.config
        out_name = (
            self.graph.nodes[output.ref.node_id].name
            if output is not None
            else None
        )
        out_idx = output.ref.out_idx if output is not None else 0
        # the output coordinate is minted against the PRE-search graph:
        # only rewrite generations from here on may redirect it
        out_gen = self.graph.alias_generation()
        if cfgf.import_strategy_file:
            strategy = unity.ParallelStrategy.load(cfgf.import_strategy_file)
            if strategy.graph is not None:
                # The exported search rewrote the graph: adopt the
                # rewritten graph so the imported per-node choices bind
                # to the node ids they were searched for (reference
                # deserializes graph + views together, graph.cc:2225).
                self.graph = strategy.graph
                self.input_nodes = [
                    n.id for n in self.graph.nodes if n.op_type == "input"
                ]
        else:
            # The search owns the ICI axes not explicitly configured:
            # fixed pipeline/expert/sequence degrees carve the device
            # count down first (the reference likewise fixes inference
            # PP outside its search).
            fixed = (
                cfgf.pipeline_parallelism_degree
                * cfgf.expert_parallelism_degree
                * cfgf.sequence_parallelism_degree
            )
            assert cfgf.num_devices % fixed == 0, (
                f"num_devices={cfgf.num_devices} not divisible by fixed "
                f"pipe*expert*seq degrees = {fixed}"
            )
            budget = cfgf.search_budget if cfgf.search_budget > 0 else 32
            extra_rules = None
            if cfgf.substitution_json_file:
                from .search.substitutions import load_substitutions_json

                extra_rules = load_substitutions_json(
                    cfgf.substitution_json_file
                )
            topo = None
            if cfgf.machine_config_file:
                from .search.machine_model import TPUTopology

                topo = TPUTopology.from_file(cfgf.machine_config_file)
                if topo.num_chips != cfgf.num_devices // fixed:
                    raise ValueError(
                        f"machine config {cfgf.machine_config_file!r} "
                        f"describes {topo.num_chips} chips but the "
                        f"search places over {cfgf.num_devices // fixed} "
                        "devices (num_devices / fixed pipe*expert*seq "
                        "degrees) — the cost model would rank against a "
                        "machine that doesn't exist"
                    )
            if cfgf.search_calibrate_chip:
                import dataclasses as _dc

                from .search.machine_model import (
                    TPUChip, TPUTopology, calibrate_chip,
                )

                topo = topo or TPUTopology(
                    chip=TPUChip.v5e(), num_chips=cfgf.num_devices // fixed
                )
                topo = _dc.replace(topo, chip=calibrate_chip(topo.chip))
                self._calibrated_chip = topo.chip
            graph2, strategy, report = unity.optimize(
                self.graph,
                cfgf.num_devices // fixed,
                topo,
                training=(comp_mode == TRAINING),
                budget=budget,
                alpha=cfgf.search_alpha,
                measured=cfgf.search_measured,
                measured_cache=cfgf.search_measured_cache,
                enable_sample=cfgf.enable_sample_parallel,
                enable_attribute=cfgf.enable_attribute_parallel,
                enable_parameter=cfgf.enable_parameter_parallel,
                # a user-fixed expert degree was already carved out of
                # the searched device count — don't enumerate it again
                allow_expert=cfgf.expert_parallelism_degree == 1,
                extra_rules=extra_rules,
            )
            self.graph = graph2
            self._search_report = report
        strategy.stamp(self.graph)
        self._strategy = strategy
        self._param_pspecs = strategy.weight_pspecs(self.graph)
        self._act_constraints = strategy.activation_constraints(self.graph)
        if strategy.machine.expert > 1:
            cfgf.expert_parallelism_degree = strategy.machine.expert
        cfgf.tensor_parallelism_degree = strategy.machine.model
        cfgf.data_parallelism_degree = (
            cfgf.num_devices
            // cfgf.tensor_parallelism_degree
            // cfgf.pipeline_parallelism_degree
            // cfgf.expert_parallelism_degree
            // cfgf.sequence_parallelism_degree
        )
        if cfgf.export_strategy_file:
            strategy.save(cfgf.export_strategy_file, graph=self.graph)
        if out_name is None:
            return None
        # follow rewrite aliases: a fused-away output (e.g. relu folded
        # into dense) resolves to the node its value was redirected to
        node, out_idx = self.graph.resolve_name(
            out_name, out_idx, start_gen=out_gen
        )
        if node is None:
            raise ValueError(
                f"output node {out_name!r} was rewritten away by the "
                "search with no redirect; name an op the substitutions "
                "keep so the output can be re-resolved after rewrites"
            )
        return TensorRef(node.id, out_idx)

    def _param_shardings(self):
        """PartitionSpec tree matching params, from per-op TP rules (or the
        parallelize pass's overrides)."""
        if self._param_pspecs is not None:
            return self._param_pspecs
        pspecs = {}
        for node in self.graph.nodes:
            if node.op_type == "input":
                continue
            op = get_op(node.op_type)
            in_specs = [self.graph.out_spec(r) for r in node.inputs]
            w = jax.eval_shape(
                lambda: op.init(jax.random.PRNGKey(0), in_specs, node.attrs_dict)
            )
            if w:
                pspecs[node.name] = op.weight_pspecs(
                    in_specs, node.attrs_dict, MODEL_AXIS
                )
        return pspecs

    def compile(
        self,
        optimizer: Optional[Optimizer] = None,
        loss_type: str = "sparse_categorical_crossentropy",
        metrics: Sequence[str] = ("accuracy",),
        comp_mode: str = TRAINING,
        output: Optional[Tensor] = None,
        auto_parallel: bool = False,
        _output_name: Optional[Tuple[str, int, int]] = None,
    ):
        """Lower the graph to jitted step functions (reference
        ``FFModel::compile``, model.cc:3314). With ``auto_parallel`` the
        Unity-style search (flexflow_tpu.search) picks mesh degrees +
        per-op shardings and may rewrite the graph; otherwise the
        config's explicit degrees apply (plus an import-strategy file,
        the reference's ``--import-strategy``)."""
        if self.config.quantization_type is not None or self.config.cpu_offload:
            # The reference too applies these only to serving
            # (file_loader.cc:651, SERVE.md offload docs). Raise rather
            # than silently training in bf16.
            raise NotImplementedError(
                "quantization/offload apply to the serving path: pass "
                "quantization=/offload= to serve.LLM.compile (training "
                "quantization is not supported, matching the reference)"
            )
        self.optimizer = optimizer or SGDOptimizer(lr=self.config.learning_rate)
        self.loss_type = loss_type
        self.metrics_names = tuple(metrics)
        if output is None and _output_name is not None:
            # recompile path: the Tensor handle is long stale — the
            # declared output survives by NAME (+ rewrite aliases from
            # its minting generation on: re-running the rewrite that
            # produced this coordinate would mis-redirect it).
            # Unresolvable = the alter() renamed it away: raising beats
            # silently reverting to the final node (a metric tap).
            o_name, o_idx, o_gen = _output_name
            node, idx = self.graph.resolve_name(o_name, o_idx, o_gen)
            if node is None:
                raise ValueError(
                    f"declared output {o_name!r} no longer resolves "
                    "after the graph was altered; keep the output op's "
                    "name stable across recompiles"
                )
            output = Tensor(self, TensorRef(node.id, idx))
        out_ref = output.ref if output is not None else None
        if auto_parallel or self.config.import_strategy_file:
            # rewrites re-number node ids; the search re-resolves the
            # output by NAME (mid-graph outputs / metric taps supported)
            out_ref = self._run_unity_search(output, comp_mode)
        self._compile_args = dict(
            optimizer=optimizer, loss_type=loss_type, metrics=metrics,
            comp_mode=comp_mode,
            # the output Tensor's node ref goes stale once a search (or
            # a recompile alter) rewrites the graph; recompiles pass the
            # NAME and re-resolve against the current graph instead
            output=None,
            # name + out_idx + the generation the coordinate is valid
            # from (it refers to the CURRENT, post-search graph)
            _output_name=(
                (
                    self.graph.nodes[out_ref.node_id].name,
                    out_ref.out_idx,
                    self.graph.alias_generation(),
                )
                if out_ref is not None
                else None
            ),
            auto_parallel=auto_parallel,
        )
        self.mesh = self._make_mesh()
        if self._param_pspecs is None and self.config.tensor_parallelism_degree > 1:
            from .parallel.tp import apply_tensor_parallel

            apply_tensor_parallel(self.graph, self.config.tensor_parallelism_degree)
        self._output_ref = out_ref if out_ref is not None else TensorRef(
            len(self.graph.nodes) - 1, 0
        )

        # The reference asserts CE losses consume a softmax op's output and
        # differentiates through probabilities; mirror that by detecting an
        # explicit softmax sink (loss_functions.cc:121-200).
        out_node = self.graph.nodes[self._output_ref.node_id]
        from_logits = out_node.op_type != "softmax"
        loss_fn = get_loss(loss_type, from_logits=from_logits)
        sparse = "sparse" in loss_type
        mesh = self.mesh

        param_pspecs = self._param_shardings()

        def to_sharding(tree_pspecs):
            return jax.tree.map(
                lambda p: NamedSharding(mesh, p),
                tree_pspecs,
                is_leaf=lambda x: isinstance(x, P),
            )

        # ---- initialise params/opt-state on device, sharded ----
        init_key = jax.random.PRNGKey(self.seed)
        with _set_mesh(mesh):
            params_shardings = to_sharding(param_pspecs)
            self.params = jax.jit(
                self.init_params, out_shardings=params_shardings
            )(init_key)
            self.model_state = self.init_state()
            self.opt_state = self.optimizer.init(self.params)

        data_sharding = NamedSharding(mesh, P(DATA_AXIS))
        repl = NamedSharding(mesh, P())
        opt = self.optimizer

        def train_step(params, opt_state, state, rng, inputs, labels):
            def lossf(p):
                preds, st_up = self.run_graph(
                    p,
                    inputs,
                    training=True,
                    rng=rng,
                    state=state,
                    upto=self._output_ref,
                )
                loss = loss_fn(preds, labels)
                # auxiliary losses collected by ops (MoE load-balance,
                # reference aggregate λ term)
                aux = st_up.pop("__aux__", None)
                if aux:
                    loss = loss + jnp.sum(jnp.stack(aux))
                return loss, (preds, st_up)

            (loss, (preds, st_up)), grads = jax.value_and_grad(
                lossf, has_aux=True
            )(params)
            new_params, new_opt = opt.update(grads, opt_state, params)
            new_state = dict(state)
            new_state.update(st_up)
            mvals = compute_metrics(
                self.metrics_names, preds, labels, sparse_labels=sparse,
                from_logits=from_logits,
            )
            return new_params, new_opt, new_state, loss, mvals

        def eval_step(params, state, inputs, labels):
            preds, _ = self.run_graph(
                params, inputs, training=False, state=state, upto=self._output_ref
            )
            loss = loss_fn(preds, labels)
            mvals = compute_metrics(
                self.metrics_names, preds, labels, sparse_labels=sparse,
                from_logits=from_logits,
            )
            return loss, mvals

        def fwd(params, state, inputs):
            preds, _ = self.run_graph(
                params, inputs, training=False, state=state, upto=self._output_ref
            )
            return preds

        self._train_step = jax.jit(train_step, donate_argnums=(0, 1, 2))
        self._eval_step = jax.jit(eval_step)
        self._fwd = jax.jit(fwd)
        self._data_sharding = data_sharding
        return self

    # ------------------------------------------------------------------
    # data feeding + loops

    def _input_names(self) -> List[str]:
        return [self.graph.nodes[i].name for i in self.input_nodes]

    def _shard_batch(self, arrays: Dict[str, np.ndarray]):
        out = {}
        for k, v in arrays.items():
            spec = P(DATA_AXIS) if np.ndim(v) >= 1 else P()
            out[k] = jax.device_put(v, NamedSharding(self.mesh, spec))
        return out

    def fit(
        self,
        x: Union[np.ndarray, Dict[str, np.ndarray], "Any"],
        y: Optional[np.ndarray] = None,
        batch_size: Optional[int] = None,
        epochs: Optional[int] = None,
        shuffle: bool = True,
        verbose: bool = True,
    ) -> PerfMetrics:
        """Training loop (reference ``FFModel.fit``, flexflow_cffi.py:3537).
        ``x`` may be a :class:`flexflow_tpu.data.SingleDataLoader` (the
        native prefetching feed) instead of arrays."""
        assert self._train_step is not None, "call compile() first"
        from .data import SingleDataLoader

        if isinstance(x, SingleDataLoader):
            # the loader owns batching/shuffling — conflicting args are
            # a caller error, not something to silently ignore
            assert y is None and batch_size is None, (
                "a SingleDataLoader carries its own labels, batch size "
                "and shuffle settings; don't pass y/batch_size with one"
            )
            loader = x
            steps = loader.batches_per_epoch
            name = self._input_names()[0]

            def epoch_batches(_epoch):
                for _ in range(steps):
                    xb, yb = loader.next_batch()
                    yield {name: xb}, yb

        else:
            assert y is not None, "fit(x, y) requires labels (or a loader)"
            bs = batch_size or self.config.batch_size
            names = self._input_names()
            if not isinstance(x, dict):
                x = {names[0]: x}
            n = len(y)
            steps = n // bs
            # seed with the step counter so repeated fit() calls (keras'
            # per-epoch loop, checkpoint resume) continue the shuffle
            # sequence instead of replaying the first permutation
            rng = np.random.default_rng(self.seed + self._step_count)

            def epoch_batches(_epoch):
                order = rng.permutation(n) if shuffle else np.arange(n)
                for s in range(steps):
                    idx = order[s * bs : (s + 1) * bs]
                    yield {k: v[idx] for k, v in x.items()}, y[idx]

        epochs = epochs or self.config.epochs
        perf = PerfMetrics()
        profiling = self.config.profiling
        if profiling:
            from .profiling import StepTimes

            self.step_times = StepTimes()
        for epoch in range(epochs):
            perf = PerfMetrics()
            for xb, yb in epoch_batches(epoch):
                # per-step mesh context: a recompile triggered by
                # recompile_on_condition may install a NEW mesh mid-epoch
                with _set_mesh(self.mesh):
                    batch = self._shard_batch(xb)
                    yb_dev = self._shard_batch({"y": yb})["y"]
                    step_rng = jax.random.PRNGKey(
                        self.seed * 1000003 + self._step_count
                    )
                    t0 = time.perf_counter() if profiling else 0.0
                    (
                        self.params,
                        self.opt_state,
                        self.model_state,
                        loss,
                        mvals,
                    ) = self._train_step(
                        self.params,
                        self.opt_state,
                        self.model_state,
                        step_rng,
                        batch,
                        yb_dev,
                    )
                    self._step_count += 1
                    perf.update(jax.device_get(loss), jax.device_get(mvals))
                self._maybe_recompile()
                if profiling:
                    # device_get above synced the step; wall time
                    # includes host feed — the number a user can act
                    # on (reference --profiling prints per-op times)
                    self.step_times.record(time.perf_counter() - t0)
            if verbose:
                msg = f"epoch {epoch}: {perf.report()}"
                if profiling:
                    msg += f" | {self.step_times.report()}"
                print(msg)
        return perf

    def evaluate(
        self,
        x: Union[np.ndarray, Dict[str, np.ndarray]],
        y: np.ndarray,
        batch_size: Optional[int] = None,
    ) -> Dict[str, float]:
        assert self._eval_step is not None, "call compile() first"
        bs = batch_size or self.config.batch_size
        names = self._input_names()
        if not isinstance(x, dict):
            x = {names[0]: x}
        n = len(y)
        perf = PerfMetrics()
        with _set_mesh(self.mesh):
            for s in range(n // bs):
                sl = slice(s * bs, (s + 1) * bs)
                batch = self._shard_batch({k: v[sl] for k, v in x.items()})
                yb = self._shard_batch({"y": y[sl]})["y"]
                loss, mvals = self._eval_step(
                    self.params, self.model_state, batch, yb
                )
                perf.update(jax.device_get(loss), jax.device_get(mvals))
        return perf.averages()

    def forward(self, inputs: Union[np.ndarray, Dict[str, Any]]):
        assert self._fwd is not None, "call compile() first"
        if not isinstance(inputs, dict):
            inputs = {self._input_names()[0]: inputs}
        with _set_mesh(self.mesh):
            return self._fwd(self.params, self.model_state, inputs)

    # ------------------------------------------------------------------
    # recompile-on-condition (reference RecompileState, recompile.h:26-41
    # + FFModel::recompile_on_condition, model.cc:2789 — the MoE example
    # uses it to rebalance experts mid-training)

    def recompile_on_condition(self, trigger, alter) -> None:
        """Register a per-step condition: when ``trigger(model)`` returns
        True, ``alter(model)`` may mutate the graph/config and the model
        recompiles in place. Parameters of unchanged layers (same name
        and shapes) carry over; new/resized layers re-initialize, and
        optimizer state resets (the reference rebuilds task launchers the
        same way)."""
        from .recompile import RecompileState

        self._recompile_state = RecompileState(trigger=trigger, alter=alter)

    def _maybe_recompile(self) -> bool:
        state = getattr(self, "_recompile_state", None)
        if state is None or not state.trigger(self):
            return False
        state.alter(self)
        old_params = self.params
        old_lr = (self.opt_state or {}).get("lr")
        assert self._compile_args is not None
        self.compile(**self._compile_args)
        if old_lr is not None and "lr" in self.opt_state:
            # a scheduler-set LR survives the recompile
            self.opt_state["lr"] = jax.device_put(
                old_lr, self.opt_state["lr"].sharding
            )
        # carry over parameters whose layer name + leaf shapes survived
        for name, leaves in (old_params or {}).items():
            if name not in self.params:
                continue
            try:
                new = self.params[name]
                if jax.tree.structure(new) == jax.tree.structure(leaves) and all(
                    a.shape == b.shape
                    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(leaves))
                ):
                    self.params[name] = jax.tree.map(
                        lambda old, cur: jax.device_put(old, cur.sharding),
                        leaves,
                        new,
                    )
            except Exception as e:
                import warnings

                warnings.warn(
                    f"recompile: layer {name!r} could not carry its "
                    f"weights over ({e}); it re-initialized", stacklevel=2,
                )
                continue
        state.recompilations += 1
        return True

    # ------------------------------------------------------------------
    # profiling (reference --profiling per-op timing + Legion Prof)

    def profile_ops(self, iters: int = 5) -> Dict[str, float]:
        """Per-op on-device forward times in ms (see profiling.profile_ops)."""
        from .profiling import profile_ops

        return profile_ops(self, iters=iters)

    def profile_trace(self, logdir: str):
        """jax.profiler capture context: ``with model.profile_trace(d): fit()``."""
        from .profiling import trace

        return trace(logdir)

    # ------------------------------------------------------------------
    # checkpoint / resume (orbax; beyond the reference — SURVEY.md §5
    # asks for async sharded checkpointing where the reference has only
    # host get_tensor/set_tensor)

    def _train_state(self) -> Dict[str, Any]:
        assert self.params is not None, "call compile() first"
        return {
            "params": self.params,
            "opt_state": self.opt_state,
            "model_state": self.model_state,
            "step": np.asarray(self._step_count, np.int64),
        }

    def save_checkpoint(self, directory: str, *, wait: bool = False) -> None:
        """Async-save params + optimizer state + model state + step."""
        from .checkpoint import save_train_state

        save_train_state(
            directory, self._step_count, self._train_state(), wait=wait
        )

    def restore_checkpoint(
        self, directory: str, step: Optional[int] = None
    ) -> None:
        """Restore into a compiled model (shardings come from the live
        state, so each process loads only its own shards)."""
        from .checkpoint import restore_train_state

        restored = restore_train_state(
            directory, self._train_state(), step=step
        )
        self.params = restored["params"]
        self.opt_state = restored["opt_state"]
        self.model_state = restored["model_state"]
        self._step_count = int(restored["step"])

    # ------------------------------------------------------------------
    # weight access (reference ParallelTensorBase::get_tensor/set_tensor)

    def validate_search(self, iters: int = 5) -> Dict[str, float]:
        """Compare the Unity search's predicted step time against the
        real compiled step on the current devices (the closing of the
        simulator-fidelity loop the reference gets from re-measuring
        with ``inner_measure_operator_cost``). Returns predicted /
        measured seconds and their ratio."""
        assert self._train_step is not None, "call compile() first"
        assert self._search_report is not None, (
            "validate_search needs an auto_parallel compile"
        )
        bs = self.config.batch_size
        rng = np.random.default_rng(0)
        x = {}
        for i in self.input_nodes:
            node = self.graph.nodes[i]
            spec = node.out_specs[0]
            if "int" in str(spec.dtype):
                x[node.name] = rng.integers(
                    0, 8, size=spec.shape
                ).astype(np.int32)
            else:
                x[node.name] = rng.normal(size=spec.shape).astype(np.float32)
        out_id = self._output_ref.node_id if self._output_ref else -1
        out_shape = self.graph.nodes[out_id].out_specs[0].shape
        n_out = out_shape[-1]
        loss_type = (self._compile_args or {}).get(
            "loss_type", "sparse_categorical_crossentropy"
        )
        if loss_type.startswith("sparse"):
            # labels match the output's leading dims: (B,) for a
            # classifier head, (B, S) for a sequence model
            y = rng.integers(
                0, max(2, n_out), size=tuple(out_shape[:-1]) or (bs,)
            ).astype(np.int32)
        else:  # dense targets (categorical CE / MSE)
            y = rng.normal(size=tuple(out_shape)).astype(np.float32)
        import time as _time

        # snapshot: timing runs real (donated) optimizer steps on noise;
        # the trained state must survive this diagnostic untouched
        live = (self.params, self.opt_state, self.model_state)
        snap = jax.device_get(live)
        shardings = jax.tree.map(lambda a: a.sharding, live)
        try:
            with _set_mesh(self.mesh):
                batch = self._shard_batch(x)
                yb = self._shard_batch({"y": y})["y"]
                key = jax.random.PRNGKey(0)
                params, opt, st = live
                # warm
                params, opt, st, loss, _ = self._train_step(
                    params, opt, st, key, batch, yb
                )
                jax.block_until_ready(loss)
                t0 = _time.perf_counter()
                for _ in range(iters):
                    params, opt, st, loss, _ = self._train_step(
                        params, opt, st, key, batch, yb
                    )
                jax.block_until_ready(loss)
                measured = (_time.perf_counter() - t0) / iters
        finally:
            # the first warm step donated the live buffers — restore even
            # when the timing loop dies, or every later fit() hits
            # "Array has been deleted"
            with _set_mesh(self.mesh):
                self.params, self.opt_state, self.model_state = jax.tree.map(
                    jax.device_put, snap, shardings
                )
        predicted = float(self._search_report.best_cost)
        return {
            "predicted_s": predicted,
            "measured_s": measured,
            "ratio": predicted / max(measured, 1e-12),
        }

    def export_dot(self, path: str, strategy=None) -> None:
        """Write the (strategy-colored, when available) computation graph
        as graphviz dot — reference ``--export-strategy-computation-
        graph-file`` (config.h:173-175)."""
        strategy = strategy or getattr(self, "_strategy", None)
        text = (
            strategy.to_dot(self.graph)
            if strategy is not None
            else self.graph.to_dot()
        )
        with open(path, "w") as f:
            f.write(text)

    def set_learning_rate(self, lr: float) -> None:
        """Change the LR in place (device scalar in opt_state — no
        recompile; the reference's ``Optimizer::set_learning_rate``)."""
        assert self.opt_state is not None and "lr" in self.opt_state, (
            "call compile() first"
        )
        cur = self.opt_state["lr"]
        new = jnp.asarray(lr, jnp.float32)
        if isinstance(cur.sharding, NamedSharding):
            new = jax.device_put(new, cur.sharding)
        # else: before the first train step the scalar is still the
        # UNCOMMITTED device-0 array compile() made; committing the
        # replacement would pin it there and the next train_step fails
        # with mixed device sets (params already live on the mesh — the
        # LearningRateScheduler-before-first-epoch case). Leave it
        # uncommitted and let jit place it with everything else.
        self.opt_state["lr"] = new

    def get_weights(self, layer_name: str):
        return jax.device_get(self.params[layer_name])

    def set_weights(self, layer_name: str, weights: Dict[str, np.ndarray]):
        cur = self.params[layer_name]
        self.params[layer_name] = jax.tree.map(
            lambda c, w: jax.device_put(jnp.asarray(w, c.dtype), c.sharding),
            cur,
            dict(weights),
        )
