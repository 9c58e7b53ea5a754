"""Layer base abstraction — the functional re-design of the reference's
``nn/api/Layer.java`` + one-config-class-per-layer (``nn/conf/layers/*.java``).

A layer here is a *frozen config dataclass* exposing:
  - ``setup(input_type)``  -> completed copy (n_in inferred) — replaces the
    reference's ``ConvolutionLayerSetup``/``InputTypeUtil`` auto-wiring
  - ``output_type(input_type)`` -> static shape inference
  - ``init(key, dtype)``   -> parameter pytree (dict name->array) — replaces
    ``ParamInitializer`` (``nn/params/*.java``)
  - ``init_state()``       -> non-trainable state pytree (e.g. BN running stats)
  - ``apply(params, state, x, *, train, rng)`` -> (y, new_state) — replaces
    ``Layer.activate``; backprop is ``jax.grad`` through apply, replacing the
    reference's hand-written ``backpropGradient`` chains.

There is no mutable layer object holding params: params live in the model's
pytree, so the whole train step jits to one XLA program and shards with pjit.

Serialization: each class registers under its reference-style type name;
``to_dict``/``layer_from_dict`` give the Jackson-subtype-registry equivalent
(custom layers register the same way — ``register_layer``).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Dict, Optional, Tuple, Type

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.inputs import InputType

_LAYER_REGISTRY: Dict[str, Type["Layer"]] = {}

# What a layer IS, whatever it is called: the closed vocabulary of
# ``Layer.kind``.  The walkers (``MultiLayerNetwork._forward``, the composite
# blocks' sublayer chain, ``ComputationGraph._forward``) put it on the layer's
# device operations as a ``jax.named_scope`` beside the layer's name, which is
# how ``observability.recompile.program_scopes`` and a trace reader tell an
# attention matmul from an FFN's (docs/observability.md, "Device time by
# layer").  The ``mhc_mix`` of the two ends of a hyper-connection stack is the
# scope their blocks already use.
KINDS = ("embed", "norm", "attention", "ffn", "experts", "head", "conv",
         "recurrent", "mhc_mix")


@dataclasses.dataclass(frozen=True)
class ServingCall:
    """One compute program of the generation engine as its layers see it,
    built once a program (``generation.programs.GenerationPrograms``):
    ``batch`` rows of ``t`` positions (the slots and 1 in the decode step,
    1 and the bucket in a prefill), whether every row starts at position 0
    (``from_zero``: a prompt with nothing shared), pages of ``page_size``,
    ``pages`` columns of a row's global block table and ``ring`` of its
    ring table (0 without window layers), ``slots`` state slots, and the
    ``dtype`` the layers are traced in."""

    batch: int
    t: int
    from_zero: bool
    page_size: int
    pages: int
    ring: int
    slots: int
    dtype: Any


def register_layer(cls: Type["Layer"]) -> Type["Layer"]:
    """Class decorator: register a layer type for JSON round-trip
    (the Jackson ``@JsonSubTypes`` equivalent; custom layers use this too,
    mirroring the reference custom-layer tests ``nn/layers/custom/``)."""
    _LAYER_REGISTRY[cls.__name__] = cls
    return cls


def layer_from_dict(d: Dict[str, Any]) -> "Layer":
    d = dict(d)
    type_name = d.pop("type")
    cls = _LAYER_REGISTRY.get(type_name)
    if cls is None:
        raise ValueError(f"Unknown layer type '{type_name}'; registered: {sorted(_LAYER_REGISTRY)}")
    return cls.from_dict(d)


@dataclasses.dataclass(frozen=True)
class Layer:
    """Base layer config. Fields every layer shares (reference
    ``nn/conf/layers/Layer.java`` base: activation, weightInit, dropOut,
    l1/l2, learning-rate overrides)."""

    name: Optional[str] = None
    activation: str = "sigmoid"
    weight_init: str = "xavier"
    dist: Optional[dict] = None        # distribution spec when weight_init="distribution"
    dropout: float = 0.0               # input dropout probability (reference dropOut)
    drop_connect: bool = False         # dropOut masks WEIGHTS instead of inputs
    _SUPPORTS_DROP_CONNECT = False     # overridden by layers that mask W
    # one of ``KINDS``, declared once a class; None for a composite block
    # (its sublayers carry theirs) and for a custom layer that declares none
    kind = None
    l1: float = 0.0
    l2: float = 0.0
    learning_rate: Optional[float] = None   # per-layer lr override
    bias_init: float = 0.0

    # ---- validation -----------------------------------------------------
    def validate(self) -> None:
        """Fail fast at build time on unknown activation / weight-init names
        (otherwise the error would surface mid-trace at first fit/output)."""
        from deeplearning4j_tpu.nn import activations, initializers

        activations.get(self.activation)
        initializers.check(self.weight_init)
        if self.drop_connect and not self._SUPPORTS_DROP_CONNECT:
            # fail fast: with drop_connect set, input dropout is disabled,
            # so a layer that never masks W would silently lose ALL dropout
            raise ValueError(
                f"{type(self).__name__} does not support drop_connect "
                "(weight masking is implemented for Dense/Output layers); "
                "use plain dropout here")

    # ---- shape plumbing -------------------------------------------------
    def setup(self, input_type: InputType) -> "Layer":
        """Return a completed copy with sizes inferred from input_type."""
        return self

    def output_type(self, input_type: InputType) -> InputType:
        raise NotImplementedError

    # ---- params ---------------------------------------------------------
    def init(self, key: jax.Array, dtype=jnp.float32) -> Dict[str, jax.Array]:
        raise NotImplementedError

    def init_state(self) -> Dict[str, jax.Array]:
        return {}

    def has_params(self) -> bool:
        return True

    # ---- forward --------------------------------------------------------
    def kind_scope(self):
        """The ``jax.named_scope`` of the layer's kind, for the walker that
        applies it (metadata on the device operations; nothing is added)."""
        return (jax.named_scope(self.kind) if self.kind
                else contextlib.nullcontext())

    def apply(
        self,
        params: Dict[str, jax.Array],
        state: Dict[str, jax.Array],
        x: jax.Array,
        *,
        train: bool = False,
        rng: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        raise NotImplementedError

    def maybe_dropout(self, x, *, train, rng):
        """Input dropout (reference ``util/Dropout.java`` applyDropout:
        inverted dropout scaling at train time).  With ``drop_connect`` the
        dropOut probability applies to weights instead (reference
        ``useDropConnect``), so input dropout is a no-op here."""
        if not train or self.dropout <= 0.0 or self.drop_connect:
            return x
        if rng is None:
            raise ValueError(f"Layer {self.name}: dropout requires an rng key at train time")
        keep = 1.0 - self.dropout
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0)

    def maybe_drop_connect(self, W, *, train, rng):
        """DropConnect: bernoulli-mask the weight matrix at train time
        (reference ``util/Dropout.java:24-36`` applyDropConnect, with
        inverted scaling so inference needs no rescale)."""
        if not train or not self.drop_connect or self.dropout <= 0.0:
            return W
        if rng is None:
            raise ValueError(
                f"Layer {self.name}: drop_connect requires an rng key at train time")
        keep = 1.0 - self.dropout
        mask = jax.random.bernoulli(rng, keep, W.shape)
        return jnp.where(mask, W / keep, 0.0)

    # ---- serving --------------------------------------------------------
    def serving_path(self, call: "ServingCall") -> Optional[str]:
        """The path this layer takes in the generation program ``call``
        describes — the same rule its traced branch follows, asked on the
        host; None for a layer with one way through (the engine counts
        ``dl4j_layer_path_steps_total{stage, kind, path}`` by it)."""
        return None

    def describe_serving(self, call: "ServingCall") -> Optional[str]:
        """One warm-up log line on how this layer's kernel tiles in
        ``call``'s program, None where there is nothing to say."""
        return None

    # ---- regularization -------------------------------------------------
    def reg_score(self, params: Dict[str, jax.Array]) -> jax.Array:
        """L1/L2 penalty contribution (reference calcL1/calcL2 on weights only)."""
        if (self.l1 == 0.0 and self.l2 == 0.0) or not params:
            return jnp.zeros(())
        total = jnp.zeros(())
        for pname, p in params.items():
            if pname in ("b", "beta", "gamma", "mean", "var"):
                continue
            if self.l1:
                total = total + self.l1 * jnp.sum(jnp.abs(p))
            if self.l2:
                total = total + 0.5 * self.l2 * jnp.sum(p * p)
        return total

    # ---- serde ----------------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["type"] = type(self).__name__
        return d

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Layer":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})

    def with_name(self, name: str) -> "Layer":
        return dataclasses.replace(self, name=name)
