"""ComputationGraph — the DAG-network facade.

Reference: ``nn/graph/ComputationGraph.java:89-103`` (vertices + topological
order), ``:599-747`` (fit), ``:1012-1036`` (output), ``:1088``
(calcBackpropGradients), builder ``nn/conf/ComputationGraphConfiguration.java:379``
(GraphBuilder) and ``:211`` (validate).

Functional redesign: the graph is data (names, edges, vertex configs);
forward is a pure fold over the topological order; backprop through the DAG
(the reference's hand-routed epsilon fan-out across Merge/ElementWise/Subset
vertices) is ``jax.grad``.  One jitted train step, multi-input multi-output.
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.backend.rng import KeyStream
from deeplearning4j_tpu.models.common import (
    LazyScoreMixin, cast_to_compute, notify_listeners,
)
from deeplearning4j_tpu.observability import (
    crash_dump, fit_telemetry, instrument, step_guard,
)
from deeplearning4j_tpu.nn import losses as losses_mod
from deeplearning4j_tpu.nn.conf import (
    TrainingIntrospection, TrainingNumerics, TrainingStability, UpdaterConfig,
)
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, layer_from_dict
from deeplearning4j_tpu.nn.layers.dense import OutputLayer
from deeplearning4j_tpu.models.vertices import (
    GraphVertex,
    LastTimeStepVertex,
    vertex_from_dict,
)


@dataclasses.dataclass(frozen=True)
class GraphNode:
    name: str
    inputs: Tuple[str, ...]
    layer: Optional[Layer] = None          # LayerVertex
    vertex: Optional[GraphVertex] = None   # function vertex

    def to_dict(self):
        return {
            "name": self.name,
            "inputs": list(self.inputs),
            "layer": self.layer.to_dict() if self.layer else None,
            "vertex": self.vertex.to_dict() if self.vertex else None,
        }

    @staticmethod
    def from_dict(d):
        return GraphNode(
            name=d["name"],
            inputs=tuple(d["inputs"]),
            layer=layer_from_dict(d["layer"]) if d.get("layer") else None,
            vertex=vertex_from_dict(d["vertex"]) if d.get("vertex") else None,
        )


@dataclasses.dataclass(frozen=True)
class GraphConfiguration:
    inputs: Tuple[str, ...]
    outputs: Tuple[str, ...]
    nodes: Tuple[GraphNode, ...]           # in insertion order
    updater: UpdaterConfig
    input_types: Optional[Dict[str, dict]] = None
    seed: int = 12345
    backprop_type: str = "standard"
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 20
    optimization_algo: str = "stochastic_gradient_descent"
    num_iterations: int = 1
    compute_dtype: Optional[str] = None  # mixed precision, as MLN conf
    # training-stability engine (nn.conf.TrainingStability), as MLN conf
    stability: Optional[Any] = None
    # training-introspection engine (nn.conf.TrainingIntrospection)
    introspection: Optional[Any] = None
    # precision-ledger engine (nn.conf.TrainingNumerics)
    numerics: Optional[Any] = None

    def topological_order(self) -> List[str]:
        """Kahn's algorithm over the DAG (reference
        ``ComputationGraph.topologicalSortOrder`` :780)."""
        indeg = {n.name: 0 for n in self.nodes}
        children: Dict[str, List[str]] = {name: [] for name in list(self.inputs) + [n.name for n in self.nodes]}
        for n in self.nodes:
            for inp in n.inputs:
                if inp not in children:
                    raise ValueError(f"Vertex '{n.name}' references unknown input '{inp}'")
                children[inp].append(n.name)
                if inp not in self.inputs:
                    indeg[n.name] += 1
        order, queue = [], [n.name for n in self.nodes if indeg[n.name] == 0]
        while queue:
            v = queue.pop(0)
            order.append(v)
            for c in children.get(v, []):
                indeg[c] -= 1
                if indeg[c] == 0:
                    queue.append(c)
        if len(order) != len(self.nodes):
            raise ValueError("Graph has a cycle")
        return order

    def validate(self):
        by_name = {n.name: n for n in self.nodes}
        for out in self.outputs:
            if out not in by_name:
                raise ValueError(f"Output '{out}' is not a vertex")
            node = by_name[out]
            if node.layer is None or not isinstance(node.layer, OutputLayer):
                raise ValueError(
                    f"Output '{out}' must be an OutputLayer/RnnOutputLayer "
                    f"(got {type(node.vertex or node.layer).__name__})"
                )
        self.topological_order()

    def to_yaml(self) -> str:
        """YAML form (reference ComputationGraphConfiguration YAML mapper)."""
        import yaml

        return yaml.safe_dump(json.loads(self.to_json()), sort_keys=False)

    @staticmethod
    def from_yaml(s: str) -> "GraphConfiguration":
        import yaml

        return GraphConfiguration.from_json(json.dumps(yaml.safe_load(s)))

    def to_json(self) -> str:
        return json.dumps(
            {
                "format_version": 1,
                "inputs": list(self.inputs),
                "outputs": list(self.outputs),
                "nodes": [n.to_dict() for n in self.nodes],
                "updater": self.updater.to_dict(),
                "input_types": self.input_types,
                "seed": self.seed,
                "backprop_type": self.backprop_type,
                "tbptt_fwd_length": self.tbptt_fwd_length,
                "tbptt_back_length": self.tbptt_back_length,
                "optimization_algo": self.optimization_algo,
                "num_iterations": self.num_iterations,
                "compute_dtype": self.compute_dtype,
                "stability": (self.stability.to_dict()
                              if self.stability else None),
                "introspection": (self.introspection.to_dict()
                                  if self.introspection else None),
                "numerics": (self.numerics.to_dict()
                             if self.numerics else None),
            },
            indent=2,
        )

    @staticmethod
    def from_json(s: str) -> "GraphConfiguration":
        d = json.loads(s)
        return GraphConfiguration(
            inputs=tuple(d["inputs"]),
            outputs=tuple(d["outputs"]),
            nodes=tuple(GraphNode.from_dict(nd) for nd in d["nodes"]),
            updater=UpdaterConfig.from_dict(d["updater"]),
            input_types=d.get("input_types"),
            seed=d["seed"],
            backprop_type=d.get("backprop_type", "standard"),
            tbptt_fwd_length=d.get("tbptt_fwd_length", 20),
            tbptt_back_length=d.get("tbptt_back_length", 20),
            optimization_algo=d.get("optimization_algo", "stochastic_gradient_descent"),
            num_iterations=d.get("num_iterations", 1),
            compute_dtype=d.get("compute_dtype"),
            stability=(TrainingStability.from_dict(d["stability"])
                       if d.get("stability") else None),
            introspection=(TrainingIntrospection.from_dict(d["introspection"])
                           if d.get("introspection") else None),
            numerics=(TrainingNumerics.from_dict(d["numerics"])
                      if d.get("numerics") else None),
        )


class GraphBuilder:
    """Fluent DAG builder (reference ``GraphBuilder`` :379,:498)."""

    def __init__(self, parent):
        self._parent = parent
        self._inputs: List[str] = []
        self._outputs: List[str] = []
        self._nodes: List[GraphNode] = []
        self._input_types: Dict[str, InputType] = {}
        self._compute_dtype: Optional[str] = None
        self._backprop_type = "standard"
        self._tbptt_fwd = 20
        self._tbptt_back = 20

    def add_inputs(self, *names: str) -> "GraphBuilder":
        self._inputs.extend(names)
        return self

    def compute_dtype(self, dtype: str) -> "GraphBuilder":
        """Mixed-precision compute policy: params/optimizer fp32, forward/
        backward math in ``dtype`` (same policy as ListBuilder.compute_dtype)."""
        if dtype not in ("float32", "bfloat16", "float16"):
            raise ValueError(f"unsupported compute_dtype '{dtype}'")
        self._compute_dtype = None if dtype == "float32" else dtype
        return self

    def backprop_type(self, kind: str, fwd_length: int = 20,
                      back_length: int = 20) -> "GraphBuilder":
        """``standard`` or ``truncated_bptt`` (reference GraphBuilder
        ``backpropType``/``tBPTTLength``)."""
        if kind not in ("standard", "truncated_bptt"):
            raise ValueError(f"unknown backprop type '{kind}'")
        self._backprop_type = kind
        self._tbptt_fwd = fwd_length
        self._tbptt_back = back_length
        return self

    def set_input_types(self, **types: InputType) -> "GraphBuilder":
        self._input_types.update(types)
        return self

    def add_layer(self, name: str, layer: Layer, *inputs: str) -> "GraphBuilder":
        self._nodes.append(GraphNode(name, tuple(inputs), layer=layer.with_name(name)))
        return self

    def add_vertex(self, name: str, vertex: GraphVertex, *inputs: str) -> "GraphBuilder":
        self._nodes.append(GraphNode(name, tuple(inputs), vertex=vertex))
        return self

    def set_outputs(self, *names: str) -> "GraphBuilder":
        self._outputs = list(names)
        return self

    def build(self) -> GraphConfiguration:
        p = self._parent
        conf = GraphConfiguration(
            inputs=tuple(self._inputs),
            outputs=tuple(self._outputs),
            nodes=tuple(self._nodes),
            updater=p._updater,
            input_types={k: v.to_dict() for k, v in self._input_types.items()} or None,
            seed=p._seed,
            optimization_algo=p._optimization_algo,
            num_iterations=p._num_iterations,
            compute_dtype=self._compute_dtype,
            backprop_type=self._backprop_type,
            tbptt_fwd_length=self._tbptt_fwd,
            tbptt_back_length=self._tbptt_back,
            stability=p._stability,
            introspection=p._introspection,
            numerics=p._numerics,
        )
        conf.validate()
        # shape inference pass: complete layers with n_in from input types
        if self._input_types:
            conf = _infer_shapes(conf, self._input_types, p)
        else:
            conf = dataclasses.replace(
                conf,
                nodes=tuple(
                    dataclasses.replace(n, layer=p._apply_global_defaults(n.layer))
                    if n.layer is not None else n
                    for n in conf.nodes
                ),
            )
        conf.validate()
        for n in conf.nodes:
            if n.layer is not None:
                n.layer.validate()
        return conf


def _infer_shapes(conf: GraphConfiguration, input_types: Dict[str, InputType], parent) -> GraphConfiguration:
    types: Dict[str, InputType] = dict(input_types)
    by_name = {n.name: n for n in conf.nodes}
    new_nodes: Dict[str, GraphNode] = {}
    for name in conf.topological_order():
        node = by_name[name]
        in_types = [types[i] for i in node.inputs]
        if node.layer is not None:
            layer = parent._apply_global_defaults(node.layer)
            layer = layer.setup(in_types[0])
            types[name] = layer.output_type(in_types[0])
            new_nodes[name] = dataclasses.replace(node, layer=layer)
        else:
            types[name] = node.vertex.output_type(in_types)
            new_nodes[name] = node
    return dataclasses.replace(
        conf, nodes=tuple(new_nodes[n.name] for n in conf.nodes)
    )


class ComputationGraph(LazyScoreMixin):
    """DAG-network facade mirroring MultiLayerNetwork's API surface."""

    def __init__(self, conf: GraphConfiguration):
        self.conf = conf
        self.nodes = {n.name: n for n in conf.nodes}
        self.topo = conf.topological_order()
        self.params: Dict[str, Dict[str, jax.Array]] = {}
        self.net_state: Dict[str, Dict[str, jax.Array]] = {}
        self.updater_state: Dict[str, Any] = {}
        self.listeners: List[Any] = []
        self.iteration = 0
        self._score = None  # lazy score_value (LazyScoreMixin)
        self._keys = KeyStream(conf.seed)
        self._jit_cache: Dict[Any, Any] = {}
        self._stab_rt = None   # StabilityRuntime, created on first fit
        # output-layer nodes in declared output order
        self.output_nodes = [self.nodes[o] for o in conf.outputs]
        # streaming rnnTimeStep state: node name -> carry; _stream_pos is
        # the host-side mirror of the caches' device position scalar
        # (None = poisoned by unequal per-input chunk lengths -> the
        # capacity check syncs device positions instead)
        self._rnn_state: Dict[str, Any] = {}
        self._stream_pos: Optional[int] = 0

    @property
    def layers(self):
        return tuple(n.layer for n in self.conf.nodes if n.layer is not None)

    def init(self, dtype=jnp.float32) -> "ComputationGraph":
        params, net_state = {}, {}
        for n in self.conf.nodes:
            if n.layer is not None and n.layer.has_params():
                params[n.name] = n.layer.init(self._keys.next(), dtype)
            else:
                params[n.name] = {}
            if n.layer is not None:
                st = n.layer.init_state()
                if st:
                    net_state[n.name] = jax.tree_util.tree_map(lambda a: a.astype(dtype), st)
        self.params = params
        self.net_state = net_state
        from deeplearning4j_tpu.optimize import updaters as upd

        self.updater_state = upd.init_state(
            self.conf.updater, {k: v for k, v in params.items() if v}
        )
        if self.conf.stability is not None:
            from deeplearning4j_tpu.resilience import stability

            # guard/scale state rides in the updater-state pytree: it
            # stacks, shards, donates, and checkpoints like Adam moments
            self.updater_state[stability.STATE_KEY] = (
                stability.initial_state(self.conf.stability))
        if self.conf.introspection is not None:
            from deeplearning4j_tpu.observability import introspection

            # per-layer stat vectors ride in the updater-state pytree too
            introspection.ensure_state(self)
        if self.conf.numerics is not None:
            from deeplearning4j_tpu.observability import numerics

            # precision ledger: same reserved-subtree transport
            numerics.ensure_state(self)
        return self

    def num_params(self) -> int:
        # tree_leaves: composite layers nest their params arbitrarily deep
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(self.params))

    def params_to_vector(self) -> np.ndarray:
        leaves = jax.tree_util.tree_leaves(self.params)
        if not leaves:
            return np.zeros((0,), np.float32)
        return np.concatenate([np.asarray(l).reshape(-1) for l in leaves])

    def set_params_vector(self, vec: np.ndarray) -> None:
        leaves, treedef = jax.tree_util.tree_flatten(self.params)
        total = sum(int(np.prod(l.shape)) for l in leaves)
        if total != vec.size:
            raise ValueError(f"param vector size {vec.size} != model size {total}")
        out, off = [], 0
        for l in leaves:
            n = int(np.prod(l.shape))
            out.append(jnp.asarray(vec[off : off + n], l.dtype).reshape(l.shape))
            off += n
        self.params = jax.tree_util.tree_unflatten(treedef, out)

    # ------------------------------------------------------------- forward
    def _forward(self, params, net_state, inputs: Dict[str, jax.Array], *,
                 train, rng, fmask=None, stop_at_preoutput=True,
                 carries=None):
        """Fold over topological order.  Output-layer nodes stop at
        preoutput (loss/activation applied by callers).  ``carries`` maps
        recurrent node name -> (h, c) initial state; the new carries are
        returned for TBPTT / rnnTimeStep (reference
        ``ComputationGraph.rnnActivateUsingStoredState`` :1719)."""
        acts: Dict[str, jax.Array] = dict(inputs)
        new_state = dict(net_state)
        cd = self.conf.compute_dtype
        if cd is not None:
            # mixed precision: parameters and inputs in the compute dtype
            params, vals = cast_to_compute(
                (params, [jnp.asarray(v) for v in acts.values()]), cd)
            acts = dict(zip(acts, vals))
        n_nodes = len(self.topo)
        rngs = jax.random.split(rng, n_nodes) if rng is not None else [None] * n_nodes
        out_names = set(self.conf.outputs)
        new_carries: Dict[str, Any] = {}
        for i, name in enumerate(self.topo):
            node = self.nodes[name]
            xs = [acts[inp] for inp in node.inputs]
            if node.layer is not None:
                layer = node.layer
                lstate = net_state.get(name, {})
                # the node's name and the layer's kind on its device
                # operations (metadata only)
                with jax.named_scope(name), layer.kind_scope():
                    if isinstance(layer, OutputLayer) and name in out_names and stop_at_preoutput:
                        h = layer.maybe_dropout(xs[0], train=train, rng=rngs[i])
                        acts[name] = layer.pre_output(params[name], h)
                    elif hasattr(layer, "apply_with_carry"):
                        carry = (carries or {}).get(name)
                        y, lst, new_carry = layer.apply_with_carry(
                            params[name], lstate, xs[0], carry,
                            train=train, rng=rngs[i], mask=fmask,
                        )
                        new_carries[name] = new_carry
                        acts[name] = y
                    else:
                        from deeplearning4j_tpu.nn.layers.convolution import GlobalPoolingLayer

                        kw = {"mask": fmask} if isinstance(layer, GlobalPoolingLayer) else {}
                        y, lst = layer.apply(params[name], lstate, xs[0],
                                             train=train, rng=rngs[i], **kw)
                        if lst:
                            new_state[name] = lst
                        acts[name] = y
            else:
                if isinstance(node.vertex, LastTimeStepVertex):
                    acts[name] = node.vertex.apply(xs, mask=fmask)
                else:
                    acts[name] = node.vertex.apply(xs)
        return acts, new_state, new_carries

    def _loss_fn(self, params, net_state, inputs, labels, rng, fmask=None,
                 lmask=None, carries=None, train=True, collect_acts=False,
                 numerics_now=None):
        """inputs: dict name->array (or single array for 1-input graphs);
        labels: dict output-name->array or single array."""
        inputs = self._as_input_dict(inputs)
        labels = self._as_label_dict(labels)
        acts, new_state, new_carries = self._forward(
            params, net_state, inputs, train=train, rng=rng, fmask=fmask,
            carries=carries)
        total = jnp.zeros(())
        for node in self.output_nodes:
            layer = node.layer
            lm = lmask.get(node.name) if isinstance(lmask, dict) else lmask
            pre = acts[node.name]
            with jax.named_scope("loss"):
                if self.conf.compute_dtype is not None:
                    pre = pre.astype(jnp.float32)  # loss in full precision
                total = total + losses_mod.score(
                    layer.loss, labels[node.name], pre, layer.activation, lm
                )
        for n in self.conf.nodes:
            if n.layer is not None and n.layer.has_params():
                total = total + n.layer.reg_score(params[n.name])
        if collect_acts:
            # introspection: per-layer-node activation summaries reduced
            # in-graph (same node order as IntrospectPlan.act_names)
            named = [(n.name, acts[n.name]) for n in self.conf.nodes
                     if n.layer is not None]
            policy = self.conf.introspection
            act_stats = {}
            if policy is not None:
                from deeplearning4j_tpu.observability import introspection

                act_stats = introspection.act_summary(
                    named, dead_eps=policy.dead_eps)
            npolicy = self.conf.numerics
            if npolicy is not None and npolicy.collect_activations:
                # precision ledger: activation dynamic-range blocks
                from deeplearning4j_tpu.observability import numerics

                act_stats.update(numerics.act_ranges(
                    named, policy=npolicy, now=numerics_now))
            return total, (new_state, new_carries, act_stats)
        return total, (new_state, new_carries)

    def _as_input_dict(self, inputs):
        if isinstance(inputs, dict):
            return inputs
        if len(self.conf.inputs) != 1:
            raise ValueError("Multi-input graph requires a dict of inputs")
        return {self.conf.inputs[0]: inputs}

    def _as_label_dict(self, labels):
        if isinstance(labels, dict):
            return labels
        if len(self.conf.outputs) != 1:
            raise ValueError("Multi-output graph requires a dict of labels")
        return {self.conf.outputs[0]: labels}

    # ---------------------------------------------------------- train step
    def _step_core(self):
        """The raw (un-jitted) SGD step shared by the per-batch train step
        and the scanned multi-step window (mirrors
        ``MultiLayerNetwork._step_core``)."""
        from deeplearning4j_tpu.observability import introspection, numerics
        from deeplearning4j_tpu.optimize import updaters as upd

        cfg = self.conf.updater
        lr_overrides = {
            n.name: n.layer.learning_rate
            for n in self.conf.nodes
            if n.layer is not None and n.layer.learning_rate is not None
        }

        policy = self.conf.stability
        plan = introspection.plan_for(self)
        nplan = numerics.plan_for(self)

        def step(params, upd_state, net_state, iteration, inputs, labels,
                 rng, fmask, lmask, carries):
            nstate = None
            if nplan is not None:
                nstate, upd_state = numerics.split_state(upd_state)
            if plan is not None:
                _, upd_state = introspection.split_state(upd_state)
            now = numerics.collect_now(nplan, iteration)
            kw = ({"collect_acts": True}
                  if numerics.wants_acts(plan, nplan) else {})
            if kw and now is not None:
                kw["numerics_now"] = now
            if policy is None:
                (loss, aux), grads = jax.value_and_grad(
                    self._loss_fn, has_aux=True
                )(params, net_state, inputs, labels, rng, fmask, lmask,
                  carries, **kw)
                new_ns, new_carries, act_stats = (
                    numerics.unpack_aux(plan, nplan, aux))
                grads = {k: v for k, v in grads.items() if v}
                updates, new_us = upd.update(cfg, grads, upd_state, iteration,
                                             lr_overrides, params=params)
                new_params = dict(params)
                for lname, u in updates.items():
                    new_params[lname] = upd.apply_updates(params[lname], u)
                introspection.attach(
                    new_us, plan, grads=grads, params=params,
                    new_params=new_params, iteration=iteration,
                    act_stats=act_stats)
                numerics.attach(
                    new_us, nplan, grads=grads, iteration=iteration,
                    act_stats=act_stats, prev=nstate, now=now)
                return new_params, new_us, new_ns, loss, new_carries
            # non-finite step guard + loss scaling: a poisoned step folds
            # into a device-side no-op (resilience/stability.py; same
            # structure as MultiLayerNetwork._step_core)
            from deeplearning4j_tpu.resilience import stability

            stab, inner = stability.split_state(upd_state)
            (_, (loss, aux)), grads = jax.value_and_grad(
                stability.scaled_loss(self._loss_fn, stab), has_aux=True
            )(params, net_state, inputs, labels, rng, fmask, lmask,
              carries, **kw)
            new_ns, new_carries, act_stats = (
                numerics.unpack_aux(plan, nplan, aux))
            new_params, new_us, new_ns, finite = (
                stability.apply_guarded_update(
                    policy, cfg, stab, inner, params, net_state,
                    loss, grads, new_ns, iteration, lr_overrides))
            introspection.attach(
                new_us, plan, grads=grads, params=params,
                new_params=new_params, iteration=iteration,
                act_stats=act_stats, grad_scale=1.0 / stab["loss_scale"])
            numerics.attach(
                new_us, nplan, grads=grads, iteration=iteration,
                act_stats=act_stats, grad_scale=1.0 / stab["loss_scale"],
                prev=nstate, now=now)
            if new_carries is not None and policy.skip_nonfinite:
                # poisoned TBPTT window: reset the recurrent stream state
                # rather than carrying NaN into the next window
                new_carries = stability.select(
                    finite, new_carries,
                    jax.tree_util.tree_map(jnp.zeros_like, new_carries))
            return new_params, new_us, new_ns, loss, new_carries

        return step

    def _get_train_step(self):
        if "train_step" not in self._jit_cache:
            self._jit_cache["train_step"] = instrument(
                jax.jit(self._step_core(), donate_argnums=(0, 1, 2)),
                "ComputationGraph.train_step",
                argnums=(3, 4, 5, 6, 7, 8, 9))
        return self._jit_cache["train_step"]

    def _make_scanned_step(self):
        """K weight updates in ONE dispatch — ``lax.scan`` over the step
        core, amortizing the host dispatch floor to 1/K for small graphs
        (same design as ``MultiLayerNetwork._make_scanned_step``)."""
        core = self._step_core()

        def multi(params, upd_state, net_state, it0, xs, ys, rngs):
            def body(carry, inp):
                params, upd_state, net_state, it = carry
                x, y, rng = inp
                params, upd_state, net_state, loss, _ = core(
                    params, upd_state, net_state, it, x, y, rng,
                    None, None, None)
                return (params, upd_state, net_state, it + 1.0), loss

            (params, upd_state, net_state, _), losses = jax.lax.scan(
                body, (params, upd_state, net_state, it0), (xs, ys, rngs))
            return params, upd_state, net_state, losses

        return instrument(jax.jit(multi, donate_argnums=(0, 1, 2)),
                          "ComputationGraph.scanned_step",
                          argnums=(3, 4, 5, 6))

    def fit_scanned(self, batches, scan_steps: int, epochs: int = 1):
        """Amortized training: consecutive same-shape batches stacked
        ``scan_steps`` at a time into one scanned XLA program — same
        per-batch updates and RNG stream as ``fit`` over the same batches
        (the CG SGD path runs each batch once, so no num_iterations
        divergence is possible); listeners fire once per window with
        ``score_value`` the window's last loss; a short tail (or a shape
        change) runs the regular per-batch step.  SGD only; no masks or
        TBPTT."""
        if scan_steps < 1:
            raise ValueError(f"scan_steps={scan_steps} must be >= 1")
        if self.conf.optimization_algo != "stochastic_gradient_descent":
            raise ValueError("fit_scanned requires SGD optimization")
        if self.conf.backprop_type == "truncated_bptt":
            raise ValueError("fit_scanned does not support TBPTT")
        if self.conf.introspection is not None:
            from deeplearning4j_tpu.observability import introspection

            introspection.ensure_state(self)
            self._introspect_live = None
        if self.conf.numerics is not None:
            from deeplearning4j_tpu.observability import numerics

            numerics.ensure_state(self)
            self._numerics_live = None
        scanned = self._jit_cache.setdefault(
            "scanned_step", self._make_scanned_step())
        for _ in range(epochs):
            window: list = []
            wshape = None
            for batch in batches:
                if hasattr(batch, "features_masks"):  # MultiDataSet
                    x, y, fm, lm = self._unpack_multi(batch)
                elif hasattr(batch, "features"):
                    x, y, fm, lm = (batch.features, batch.labels,
                                    batch.features_mask, batch.labels_mask)
                else:
                    x, y = batch[0], batch[1]
                    fm = batch[2] if len(batch) > 2 else None
                    lm = batch[3] if len(batch) > 3 else None
                if fm is not None or lm is not None:
                    raise ValueError("fit_scanned does not support masks")
                x = {k: np.asarray(v)
                     for k, v in self._as_input_dict(x).items()}
                y = {k: np.asarray(v)
                     for k, v in self._as_label_dict(y).items()}
                shape = ({k: v.shape for k, v in x.items()},
                         {k: v.shape for k, v in y.items()})
                if window and shape != wshape:
                    self._flush_window(window, scanned, scan_steps)
                    window = []
                wshape = shape
                window.append((x, y))
                if len(window) == scan_steps:
                    self._flush_window(window, scanned, scan_steps)
                    window = []
            if window:
                self._flush_window(window, scanned, scan_steps)
        return self

    def _flush_window(self, window, scanned, scan_steps):
        if len(window) == scan_steps:
            tel = fit_telemetry("ComputationGraph")
            batch = len(next(iter(window[0][0].values())))
            t0 = time.perf_counter()
            with step_guard("fit_window", model="ComputationGraph",
                            iteration=self.iteration, steps=len(window)):
                with tel.span(self.iteration):
                    xs = {k: jnp.asarray(np.stack([b[0][k] for b in window]))
                          for k in window[0][0]}
                    ys = {k: jnp.asarray(np.stack([b[1][k] for b in window]))
                          for k in window[0][1]}
                    rngs = jnp.stack([self._keys.next() for _ in window])
                    it0 = jnp.asarray(self.iteration, jnp.float32)
                    (self.params, self.updater_state, self.net_state,
                     losses) = scanned(self.params, self.updater_state,
                                       self.net_state, it0, xs, ys, rngs)
            self.score_value = losses[-1]
            self.iteration += len(window)
            tel.record_step(time.perf_counter() - t0, batch, losses[-1],
                            steps=len(window), model=self)
            # listeners fire once per window, so they get the WINDOW's
            # sample count — samples/sec = samples / (window wall time)
            notify_listeners(self, batch * len(window))
        else:  # short tail: regular per-batch step keeps semantics exact
            for x, y in window:
                self._one_step(x, y, None, None, carries=None)

    def fit(self, data, labels=None, *, fmask=None, lmask=None,
            checkpoint_manager=None, retry_policy=None):
        """fit(inputs, labels) or fit(iterable of DataSet / MultiDataSet /
        tuples).  MultiDataSet features/labels map positionally onto
        ``conf.inputs`` / ``conf.outputs`` (reference
        ``ComputationGraph.fit(MultiDataSetIterator)`` :599-747).

        ``checkpoint_manager=`` / ``retry_policy=`` wire the resilience
        layer exactly as in ``MultiLayerNetwork.fit``: auto-resume with
        batch skipping, boundary saves, clean preemption stop, transient
        step retry (docs/resilience.md)."""
        from deeplearning4j_tpu.observability import profiling, shardstats

        prof = profiling.active_profiler()
        if prof is not None:
            # memory attribution: flight/watchdog dumps show this model's
            # per-leaf param/updater byte breakdown (weakly held)
            prof.track_model(self, "ComputationGraph")
        # sharding ledger (per-tree bytes/replication; metadata walk only,
        # once per fit call) — flight dumps and GET /memory read it
        shardstats.record_model_ledger(self, "ComputationGraph")
        res = None
        if checkpoint_manager is not None or retry_policy is not None:
            from deeplearning4j_tpu.resilience import FitResilience

            res = FitResilience("ComputationGraph", checkpoint_manager,
                                retry_policy, net=self)
        if self.conf.stability is not None:
            from deeplearning4j_tpu.resilience import stability

            stability.ensure_state(self)
            created = self._stab_rt is None
            if created:
                self._stab_rt = stability.StabilityRuntime(
                    "ComputationGraph", self.conf.stability)
            if created or (res is not None and res.resumed_from is not None):
                # a restored nonfinite_total is history, not fresh evidence
                self._stab_rt.baseline_from(
                    self.updater_state.get(stability.STATE_KEY))
        if self.conf.introspection is not None:
            from deeplearning4j_tpu.observability import introspection

            introspection.ensure_state(self)
            # facade updater_state is authoritative during a solo fit
            self._introspect_live = None
        if self.conf.numerics is not None:
            from deeplearning4j_tpu.observability import numerics

            numerics.ensure_state(self)
            self._numerics_live = None
        from deeplearning4j_tpu.resilience import preemption_requested

        try:
            if labels is not None:
                # the single-pair path is one "batch": same skip /
                # preemption / boundary-save duties as the iterable loop
                # (user-driven loops call fit(x, y) repeatedly)
                if res is not None and res.skip_window(self._batch_adv(data)):
                    return self
                if preemption_requested():
                    if res is not None:
                        res.on_preempt(self)
                    return self
                self._fit_one(data, labels, fmask, lmask, res)
                if res is not None:
                    res.after_step(self)
                if self._stab_rt is not None:
                    self._stab_rt.poll_net(self, res)
                return self
            for batch in data:
                if hasattr(batch, "features_masks"):  # MultiDataSet
                    x, y, fm, lm = self._unpack_multi(batch)
                elif hasattr(batch, "features"):
                    x, y, fm, lm = (batch.features, batch.labels,
                                    batch.features_mask, batch.labels_mask)
                else:
                    x, y = batch[0], batch[1]
                    fm = batch[2] if len(batch) > 2 else None
                    lm = batch[3] if len(batch) > 3 else None
                if res is not None and res.skip_window(self._batch_adv(x)):
                    continue   # auto-resume: batch covered by the ckpt
                if preemption_requested():
                    if res is not None:
                        res.on_preempt(self)
                    break   # preemption: stop cleanly at a boundary
                self._fit_one(x, y, fm, lm, res)
                if res is not None:
                    res.after_step(self)
                if self._stab_rt is not None:
                    # sentinel boundary: no-op except every check_every-th
                    # batch (harvest + possible backoff/rewind escalation)
                    self._stab_rt.poll_net(self, res)
        except Exception as e:
            # fit-loop exception: leave the same flight-recorder report a
            # hang would (events + live spans + registry snapshot)
            crash_dump("fit_exception", model="ComputationGraph",
                       iteration=self.iteration, error=repr(e))
            raise
        finally:
            if self._stab_rt is not None:
                # final harvest: the tail past the last check boundary
                # still lands in the non-finite counter
                self._stab_rt.flush(self)
        return self

    def _unpack_multi(self, mds):
        """Positional MultiDataSet -> named input/label dicts."""
        if len(mds.features) != len(self.conf.inputs):
            raise ValueError(
                f"MultiDataSet has {len(mds.features)} feature arrays, graph "
                f"declares {len(self.conf.inputs)} inputs")
        if len(mds.labels) != len(self.conf.outputs):
            raise ValueError(
                f"MultiDataSet has {len(mds.labels)} label arrays, graph "
                f"declares {len(self.conf.outputs)} outputs")
        x = dict(zip(self.conf.inputs, mds.features))
        y = dict(zip(self.conf.outputs, mds.labels))
        fm = None
        if mds.features_masks is not None:
            present = [m for m in mds.features_masks if m is not None]
            if len(present) > 1:
                raise ValueError("at most one features mask is supported")
            fm = present[0] if present else None
        lm = None
        if mds.labels_masks is not None:
            lm = {name: m for name, m in zip(self.conf.outputs, mds.labels_masks)
                  if m is not None} or None
        return x, y, fm, lm

    def _batch_adv(self, x) -> int:
        """How many ITERATIONS one batch advances — the resume-skip unit.
        1 everywhere except SGD TBPTT, where one batch runs one iteration
        per fwd-length window (the solver path also advances by exactly 1,
        after the solve)."""
        if (self.conf.optimization_algo == "stochastic_gradient_descent"
                and self.conf.backprop_type == "truncated_bptt"):
            temporal = [np.shape(a)[1]
                        for a in self._as_input_dict(x).values()
                        if np.ndim(a) >= 3]
            if temporal:
                return -(-max(temporal) // self.conf.tbptt_fwd_length)
        return 1

    def _fit_one(self, x, y, fm, lm, res=None):
        """One batch; the resilience retry scope is per ITERATION — the
        single SGD step, each TBPTT window, or the whole solver solve
        (which only writes params/iteration after it finishes)."""
        if self.conf.optimization_algo != "stochastic_gradient_descent":
            if res is not None:
                return res.step(lambda: self._fit_solver(x, y, fm, lm),
                                self.iteration, net=self)
            return self._fit_solver(x, y, fm, lm)
        if self.conf.backprop_type == "truncated_bptt":
            return self._fit_tbptt(x, y, fm, lm, res)
        if res is not None:
            res.step(lambda: self._one_step(x, y, fm, lm, carries=None),
                     self.iteration, net=self)
        else:
            self._one_step(x, y, fm, lm, carries=None)

    def _one_step(self, x, y, fm, lm, carries):
        from deeplearning4j_tpu.resilience import get_fault_injector

        inj = get_fault_injector()
        if inj is not None and inj.has_poison():
            # deterministic chaos: single-device fit loops poison under
            # worker id "0" (docs/resilience.md "Stability")
            x, y = inj.poison_batch("0", self.iteration, x, y)
        step = self._get_train_step()
        x = jax.tree_util.tree_map(jnp.asarray, self._as_input_dict(x))
        y = jax.tree_util.tree_map(jnp.asarray, self._as_label_dict(y))
        batch = int(next(iter(x.values())).shape[0]) if x else None
        tel = fit_telemetry("ComputationGraph")
        t0 = time.perf_counter()
        with step_guard("fit_step", model="ComputationGraph",
                        iteration=self.iteration):
            with tel.span(self.iteration):
                (self.params, self.updater_state, self.net_state, loss,
                 new_carries) = step(
                    self.params, self.updater_state, self.net_state,
                    jnp.asarray(float(self.iteration)), x, y,
                    self._keys.next(),
                    None if fm is None else jax.tree_util.tree_map(
                        jnp.asarray, fm),
                    None if lm is None else jax.tree_util.tree_map(
                        jnp.asarray, lm),
                    carries,
                )
        self.score_value = loss  # device scalar; fetched lazily on read
        self.iteration += 1
        tel.record_step(time.perf_counter() - t0, batch, loss, model=self)
        notify_listeners(self, batch)
        return new_carries

    def _fit_tbptt(self, x, y, fm, lm, res=None):
        """Truncated BPTT over the DAG: slice the time axis of every input/
        label/mask into fwd-length windows, carrying recurrent-node state
        (detached) across windows (reference ``ComputationGraph``
        ``doTruncatedBPTT`` :1549).  Retry scope is per WINDOW — each
        window is one committed iteration."""
        x = self._as_input_dict(x)
        y = self._as_label_dict(y)
        temporal = [a.shape[1] for a in x.values() if np.ndim(a) >= 3]
        if not temporal:
            raise ValueError(
                "TBPTT requires at least one rank-3 [batch, time, features] "
                "input; use backprop_type='standard' for feed-forward graphs")
        T = max(temporal)
        L = self.conf.tbptt_fwd_length
        carries = None
        for t0 in range(0, T, L):
            sl = slice(t0, min(t0 + L, T))

            def one_window(c=carries, sl=sl):
                return self._one_step(
                    self._tbptt_slice_data(x, sl),
                    self._tbptt_slice_data(y, sl),
                    self._tbptt_slice_mask(fm, sl),
                    self._tbptt_slice_mask(lm, sl),
                    c,
                )

            if res is not None:
                carries = res.step(one_window, self.iteration, net=self)
            else:
                carries = one_window()
            carries = jax.lax.stop_gradient(carries)

    @staticmethod
    def _tbptt_slice_data(tree, sl):
        """Time-slice rank-3 sequences; rank-2 arrays are static
        feed-forward features / one-hot labels, passed whole."""
        if tree is None:
            return None
        return jax.tree_util.tree_map(
            lambda a: a[:, sl] if np.ndim(a) >= 3 else a, tree)

    @staticmethod
    def _tbptt_slice_mask(tree, sl):
        """Masks are [batch, time] — rank-2 IS temporal here."""
        if tree is None:
            return None
        return jax.tree_util.tree_map(
            lambda a: a[:, sl] if np.ndim(a) >= 2 else a, tree)

    def _fit_solver(self, x, y, fm, lm):
        """Full-batch solver path (CG/LBFGS/line-search GD); see
        ``MultiLayerNetwork._fit_solver``. Reference ``Solver.java:47-74``."""
        from deeplearning4j_tpu.optimize import solvers as solvers_mod

        args = (
            self.net_state,
            jax.tree_util.tree_map(jnp.asarray, self._as_input_dict(x)),
            jax.tree_util.tree_map(jnp.asarray, self._as_label_dict(y)),
            self._keys.next(),
            None if fm is None else jnp.asarray(fm),
            None if lm is None else jnp.asarray(lm),
        )

        def loss_fn(params, net_state, x, y, rng, fm, lm):
            return self._loss_fn(params, net_state, x, y, rng, fm, lm)

        solvers_mod.fit_model_with_solver(
            self, loss_fn, args, self.conf.optimization_algo,
            self.conf.num_iterations,
        )

    # ------------------------------------------------------------ inference
    def output(self, inputs, fmask=None):
        if "output" not in self._jit_cache:

            def out(params, net_state, inputs, fmask):
                from deeplearning4j_tpu.nn import activations

                acts, _, _ = self._forward(params, net_state, inputs,
                                           train=False, rng=None, fmask=fmask)
                outs = []
                for node in self.output_nodes:
                    pre = acts[node.name]
                    if self.conf.compute_dtype is not None:
                        pre = pre.astype(jnp.float32)  # fp32 API boundary
                    outs.append(activations.get(node.layer.activation)(pre))
                return outs

            self._jit_cache["output"] = jax.jit(out)
        inputs = jax.tree_util.tree_map(jnp.asarray, self._as_input_dict(inputs))
        outs = self._jit_cache["output"](
            self.params, self.net_state, inputs,
            None if fmask is None else jnp.asarray(fmask),
        )
        return outs[0] if len(outs) == 1 else outs

    def evaluate(self, iterator, evaluation=None):
        """Classification metrics over a DataSet/MultiDataSet iterator
        (reference ``ComputationGraph.doEvaluation`` — single-output graphs)."""
        from deeplearning4j_tpu.evaluation import Evaluation

        if len(self.conf.outputs) != 1:
            raise ValueError("evaluate() supports single-output graphs; use "
                             "output() + per-head Evaluation for multi-output")
        ev = evaluation or Evaluation()
        for batch in iterator:
            if hasattr(batch, "features_masks"):  # MultiDataSet
                x, y, fm, lm = self._unpack_multi(batch)
                lm = None if lm is None else next(iter(lm.values()))
                y = y[self.conf.outputs[0]]
            else:
                x, y = batch.features, batch.labels
                fm, lm = batch.features_mask, batch.labels_mask
            ev.eval(y, self.output(x, fmask=fm), mask=lm)
        return ev

    def feed_forward(self, inputs, train: bool = False, fmask=None):
        """All vertex activations as a name->array dict (reference
        ``ComputationGraph.feedForward()`` :1012-1036; output vertices carry
        their post-activation values)."""
        from deeplearning4j_tpu.nn import activations

        inputs = jax.tree_util.tree_map(jnp.asarray, self._as_input_dict(inputs))
        rng = self._keys.next() if train else None
        acts, _, _ = self._forward(self.params, self.net_state, inputs,
                                   train=train, rng=rng, fmask=fmask)
        out = {}
        out_names = set(self.conf.outputs)
        for name, a in acts.items():
            if name in out_names:
                a = activations.get(self.nodes[name].layer.activation)(
                    a.astype(jnp.float32) if self.conf.compute_dtype else a)
            elif self.conf.compute_dtype is not None and hasattr(a, "dtype") \
                    and jnp.issubdtype(a.dtype, jnp.floating):
                a = a.astype(jnp.float32)  # fp32 API boundary
            out[name] = a
        return out

    def score(self, inputs=None, labels=None, dataset=None, fmask=None,
              lmask=None) -> float:
        if dataset is not None:
            if hasattr(dataset, "features"):
                inputs, labels = dataset.features, dataset.labels
                fmask = fmask if fmask is not None else getattr(dataset, "features_mask", None)
                lmask = lmask if lmask is not None else getattr(dataset, "labels_mask", None)
            else:
                inputs, labels = dataset[0], dataset[1]
        inputs = jax.tree_util.tree_map(jnp.asarray, self._as_input_dict(inputs))
        labels = jax.tree_util.tree_map(jnp.asarray, self._as_label_dict(labels))
        loss, _ = self._loss_fn(self.params, self.net_state, inputs, labels,
                                None, fmask=fmask, lmask=lmask, train=False)
        return float(loss)

    # ------------------------------------------------- streaming rnnTimeStep
    def rnn_clear_previous_state(self):
        """Reference ``ComputationGraph.rnnClearPreviousState`` :1686."""
        self._rnn_state = {}
        self._stream_pos = 0

    def _id_consumer(self, input_name: str):
        """The EmbeddingLayer consuming this graph input, if any — its
        inputs are integer token ids, not feature vectors.  The map is
        static for the life of the graph; memoized because this sits in
        the per-token streaming loop."""
        cache = getattr(self, "_id_consumer_map", None)
        if cache is None:
            from deeplearning4j_tpu.nn.layers.dense import EmbeddingLayer

            cache = {}
            for node in self.nodes.values():
                if node.layer is not None and isinstance(node.layer,
                                                         EmbeddingLayer):
                    for inp in node.inputs:
                        cache[inp] = node.layer
            self._id_consumer_map = cache
        return cache.get(input_name)

    def rnn_time_step(self, inputs, fmask=None):
        """Stateful streaming inference (reference
        ``ComputationGraph.rnnTimeStep`` :1674): feed one (or a few)
        timesteps; recurrent-node carries persist across calls."""
        from deeplearning4j_tpu.models.common import (
            check_cache_capacity, seed_stream_caches,
        )

        inputs = self._as_input_dict(inputs)
        inputs = {k: jnp.asarray(v) for k, v in inputs.items()}
        # per-input expansion: id inputs (feeding an EmbeddingLayer) follow
        # the MLN id rules; feature inputs treat rank-2 as one timestep
        squeeze = False
        expanded = {}
        for name, v in inputs.items():
            emb = self._id_consumer(name)
            if emb is not None:
                sq = v.ndim == 1 or (
                    emb.collapse_column and v.ndim == 2 and v.shape[1] == 1)
                if v.ndim == 1:
                    v = v[:, None]
                if v.ndim == 2 and emb.collapse_column:
                    v = v[..., None]
            else:
                sq = v.ndim == 2
                if sq:
                    v = v[:, None, :]
            squeeze = squeeze or sq
            expanded[name] = v
        inputs = expanded
        first = next(iter(inputs.values()))
        if not self._rnn_state:
            self._stream_pos = 0
        carries = seed_stream_caches(
            ((n, self.nodes[n].layer) for n in self.topo
             if self.nodes[n].layer is not None),
            self._rnn_state, first.shape[0], self.conf.compute_dtype)
        # the longest time axis across inputs bounds what any attention
        # cache may be asked to append this call
        t_all = {int(v.shape[1]) for v in inputs.values() if v.ndim >= 2}
        t_new = max(t_all, default=1)
        # host-side position counter: no device->host sync per streamed
        # chunk.  Valid only while every input streams the same number of
        # timesteps per call (caches fed by a shorter input would advance
        # less than the counter) — unequal chunks poison the counter and
        # the check falls back to syncing each cache's device position.
        if len(t_all) > 1:
            self._stream_pos = None
        pos = self._stream_pos if isinstance(self._stream_pos, int) else None
        check_cache_capacity(carries, t_new, pos=pos)
        carries = carries or None
        acts, _, new_carries = self._forward(
            self.params, self.net_state, inputs, train=False, rng=None,
            fmask=fmask, carries=carries,
        )
        self._rnn_state = new_carries
        if isinstance(self._stream_pos, int):
            self._stream_pos += t_new
        from deeplearning4j_tpu.nn import activations

        outs = []
        for node in self.output_nodes:
            pre = acts[node.name]
            if self.conf.compute_dtype is not None:
                pre = pre.astype(jnp.float32)
            o = activations.get(node.layer.activation)(pre)
            outs.append(o[:, -1] if squeeze and o.ndim == 3 else o)
        return outs[0] if len(outs) == 1 else outs

    # -------------------------------------------------------------- pretrain
    def pretrain(self, batches, epochs: int = 1):
        """Layerwise unsupervised pretraining of AutoEncoder/RBM layer
        vertices, in topological order (reference ``ComputationGraph.pretrain``
        :478: trains each pretrainable vertex on the DAG activations feeding
        it)."""
        from deeplearning4j_tpu.nn.layers.autoencoder import AutoEncoder, RBM

        batches = list(batches) if not isinstance(batches, list) else batches
        for name in self.topo:
            node = self.nodes[name]
            if node.layer is None or not isinstance(node.layer, (AutoEncoder, RBM)):
                continue
            layer = node.layer

            def ploss(lparams, x, rng, _layer=layer):
                return _layer.pretrain_loss(lparams, x, rng)

            grad_fn = jax.jit(jax.value_and_grad(ploss))
            lr = layer.learning_rate or self.conf.updater.learning_rate
            for _ in range(epochs):
                for batch in batches:
                    if hasattr(batch, "features_masks"):
                        x, _, _, _ = self._unpack_multi(batch)
                    elif hasattr(batch, "features"):
                        x = batch.features
                    else:
                        x = batch[0] if isinstance(batch, (tuple, list)) else batch
                    x = jax.tree_util.tree_map(jnp.asarray, self._as_input_dict(x))
                    # DAG activations feeding this node (test mode, current params)
                    acts, _, _ = self._forward(self.params, self.net_state, x,
                                               train=False, rng=None,
                                               stop_at_preoutput=True)
                    h = acts[node.inputs[0]]  # _forward seeds acts with inputs
                    loss, g = grad_fn(self.params[name], h, self._keys.next())
                    self.params[name] = jax.tree_util.tree_map(
                        lambda p, gg: p - lr * gg, self.params[name], g
                    )
        return self

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def clone(self) -> "ComputationGraph":
        net = ComputationGraph(self.conf)
        net.params = jax.tree_util.tree_map(lambda a: a, self.params)
        net.net_state = jax.tree_util.tree_map(lambda a: a, self.net_state)
        net.updater_state = jax.tree_util.tree_map(lambda a: a, self.updater_state)
        net.iteration = self.iteration
        return net

    def save(self, path, save_updater: bool = True):
        from deeplearning4j_tpu.models import serialization

        serialization.write_model(self, path, save_updater=save_updater)

    @staticmethod
    def load(path) -> "ComputationGraph":
        from deeplearning4j_tpu.models import serialization

        return serialization.restore_computation_graph(path)
