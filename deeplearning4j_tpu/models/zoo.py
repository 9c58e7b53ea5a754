"""Model zoo — the reference's target configurations (BASELINE.json) built
on the DSL.

- LeNet-5 / MNIST  (reference baseline config 1: MultiLayerNetwork)
- ResNet-50        (reference baseline config 2: ComputationGraph; residual
  adds via ElementWiseVertex)
- GravesLSTM char-LM (reference baseline config 3)

All TPU-first: NHWC, bf16-ready, static shapes.
"""

from __future__ import annotations

from typing import Optional, Sequence

from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers import (
    RBM,
    BatchNormalization,
    ConvolutionLayer,
    DenseLayer,
    GlobalPoolingLayer,
    GravesLSTM,
    OutputLayer,
    RnnOutputLayer,
    SubsamplingLayer,
)
from deeplearning4j_tpu.models.graph import ComputationGraph, GraphConfiguration
from deeplearning4j_tpu.models.sequential import MultiLayerNetwork
from deeplearning4j_tpu.models.vertices import ElementWiseVertex, MergeVertex


def lenet(seed: int = 12345, updater: str = "nesterovs", lr: float = 0.01,
          n_classes: int = 10) -> MultiLayerNetwork:
    """LeNet-5 on 28x28x1 (the classic DL4J MNIST example config)."""
    conf = (
        NeuralNetConfiguration.builder()
        .seed(seed)
        .updater(updater, learning_rate=lr)
        .regularization(True)
        .l2(5e-4)
        .list()
        .layer(ConvolutionLayer(n_out=20, kernel_size=(5, 5), stride=(1, 1),
                                activation="identity", weight_init="xavier"))
        .layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2), stride=(2, 2)))
        .layer(ConvolutionLayer(n_out=50, kernel_size=(5, 5), stride=(1, 1),
                                activation="identity"))
        .layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2), stride=(2, 2)))
        .layer(DenseLayer(n_out=500, activation="relu"))
        .layer(OutputLayer(n_out=n_classes, loss="mcxent", activation="softmax"))
        .set_input_type(InputType.convolutional_flat(28, 28, 1))
        .build()
    )
    return MultiLayerNetwork(conf).init()


def _bottleneck(g, name: str, in_name: str, channels: int, stride: int,
                project: bool):
    """ResNet-v1 bottleneck: 1x1 -> 3x3 -> 1x1(4c) + shortcut, post-add relu."""
    mid = channels
    out_ch = channels * 4
    g.add_layer(f"{name}_c1", ConvolutionLayer(
        n_out=mid, kernel_size=(1, 1), stride=(stride, stride),
        activation="identity", weight_init="relu"), in_name)
    g.add_layer(f"{name}_bn1", BatchNormalization(activation="relu"), f"{name}_c1")
    g.add_layer(f"{name}_c2", ConvolutionLayer(
        n_out=mid, kernel_size=(3, 3), stride=(1, 1), padding=(1, 1),
        activation="identity", weight_init="relu"), f"{name}_bn1")
    g.add_layer(f"{name}_bn2", BatchNormalization(activation="relu"), f"{name}_c2")
    g.add_layer(f"{name}_c3", ConvolutionLayer(
        n_out=out_ch, kernel_size=(1, 1), stride=(1, 1),
        activation="identity", weight_init="relu"), f"{name}_bn2")
    g.add_layer(f"{name}_bn3", BatchNormalization(activation="identity"), f"{name}_c3")
    shortcut = in_name
    if project:
        g.add_layer(f"{name}_proj", ConvolutionLayer(
            n_out=out_ch, kernel_size=(1, 1), stride=(stride, stride),
            activation="identity", weight_init="relu"), in_name)
        g.add_layer(f"{name}_projbn", BatchNormalization(activation="identity"),
                    f"{name}_proj")
        shortcut = f"{name}_projbn"
    g.add_vertex(f"{name}_add", ElementWiseVertex(op="add"), f"{name}_bn3", shortcut)
    from deeplearning4j_tpu.nn.layers import ActivationLayer

    g.add_layer(f"{name}_relu", ActivationLayer(activation="relu"), f"{name}_add")
    return f"{name}_relu"


def resnet50(height: int = 224, width: int = 224, channels: int = 3,
             n_classes: int = 1000, seed: int = 12345,
             updater: str = "nesterovs", lr: float = 0.1,
             blocks: Sequence[int] = (3, 4, 6, 3),
             stem_stride: int = 2, init_channels: int = 64,
             compute_dtype: Optional[str] = None) -> ComputationGraph:
    """ResNet-50 as a ComputationGraph (residual adds = ElementWiseVertex,
    the reference's DAG capability exercised at benchmark scale).

    For CIFAR-scale inputs pass height=width=32, stem_stride=1."""
    b = (
        NeuralNetConfiguration.builder()
        .seed(seed)
        .updater(updater, learning_rate=lr)
        .graph()
        .add_inputs("input")
        .set_input_types(input=InputType.convolutional(height, width, channels))
    )
    if compute_dtype:
        b.compute_dtype(compute_dtype)
    stem_kernel = (7, 7) if stem_stride == 2 else (3, 3)
    stem_pad = (3, 3) if stem_stride == 2 else (1, 1)
    b.add_layer("stem", ConvolutionLayer(
        n_out=init_channels, kernel_size=stem_kernel,
        stride=(stem_stride, stem_stride), padding=stem_pad,
        activation="identity", weight_init="relu"), "input")
    b.add_layer("stem_bn", BatchNormalization(activation="relu"), "stem")
    prev = "stem_bn"
    if stem_stride == 2:
        b.add_layer("stem_pool", SubsamplingLayer(
            pooling_type="max", kernel_size=(3, 3), stride=(2, 2), padding=(1, 1)),
            "stem_bn")
        prev = "stem_pool"
    ch = init_channels
    for stage, n_blocks in enumerate(blocks):
        for i in range(n_blocks):
            stride = 2 if (stage > 0 and i == 0) else 1
            project = i == 0
            prev = _bottleneck(b, f"s{stage}b{i}", prev, ch, stride, project)
        ch *= 2
    b.add_layer("gap", GlobalPoolingLayer(pooling_type="avg"), prev)
    b.add_layer("fc", OutputLayer(n_out=n_classes, loss="mcxent",
                                  activation="softmax", weight_init="xavier"), "gap")
    conf = b.set_outputs("fc").build()
    return ComputationGraph(conf).init()


def alexnet(height: int = 224, width: int = 224, channels: int = 3,
            n_classes: int = 1000, seed: int = 12345,
            updater: str = "nesterovs", lr: float = 0.01,
            compute_dtype: Optional[str] = None) -> MultiLayerNetwork:
    """AlexNet (the classic DL4J model-zoo config: 5 conv + LRN + 3 fc with
    dropout).  Exercises LRN (the Pallas helper path) at benchmark scale."""
    from deeplearning4j_tpu.nn.layers import LocalResponseNormalization

    b = (NeuralNetConfiguration.builder().seed(seed)
         .updater(updater, learning_rate=lr)
         .regularization(True).l2(5e-4).list())
    if compute_dtype:
        b.compute_dtype(compute_dtype)
    (b.layer(ConvolutionLayer(n_out=96, kernel_size=(11, 11), stride=(4, 4),
                              activation="relu", weight_init="relu"))
      .layer(LocalResponseNormalization())
      .layer(SubsamplingLayer(pooling_type="max", kernel_size=(3, 3), stride=(2, 2)))
      .layer(ConvolutionLayer(n_out=256, kernel_size=(5, 5), stride=(1, 1),
                              padding=(2, 2), activation="relu"))
      .layer(LocalResponseNormalization())
      .layer(SubsamplingLayer(pooling_type="max", kernel_size=(3, 3), stride=(2, 2)))
      .layer(ConvolutionLayer(n_out=384, kernel_size=(3, 3), stride=(1, 1),
                              padding=(1, 1), activation="relu"))
      .layer(ConvolutionLayer(n_out=384, kernel_size=(3, 3), stride=(1, 1),
                              padding=(1, 1), activation="relu"))
      .layer(ConvolutionLayer(n_out=256, kernel_size=(3, 3), stride=(1, 1),
                              padding=(1, 1), activation="relu"))
      .layer(SubsamplingLayer(pooling_type="max", kernel_size=(3, 3), stride=(2, 2)))
      .layer(DenseLayer(n_out=4096, activation="relu", dropout=0.5))
      .layer(DenseLayer(n_out=4096, activation="relu", dropout=0.5))
      .layer(OutputLayer(n_out=n_classes, loss="mcxent", activation="softmax"))
      .set_input_type(InputType.convolutional(height, width, channels)))
    return MultiLayerNetwork(b.build()).init()


def vgg16(height: int = 224, width: int = 224, channels: int = 3,
          n_classes: int = 1000, seed: int = 12345,
          updater: str = "nesterovs", lr: float = 0.01,
          compute_dtype: Optional[str] = None) -> MultiLayerNetwork:
    """VGG-16 (13 conv 3x3 + 3 fc; DL4J model-zoo config)."""
    b = (NeuralNetConfiguration.builder().seed(seed)
         .updater(updater, learning_rate=lr)
         .regularization(True).l2(5e-4).list())
    if compute_dtype:
        b.compute_dtype(compute_dtype)
    for block, (n_convs, ch) in enumerate([(2, 64), (2, 128), (3, 256),
                                           (3, 512), (3, 512)]):
        for _ in range(n_convs):
            b.layer(ConvolutionLayer(n_out=ch, kernel_size=(3, 3),
                                     stride=(1, 1), padding=(1, 1),
                                     activation="relu"))
        b.layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2),
                                 stride=(2, 2)))
    (b.layer(DenseLayer(n_out=4096, activation="relu", dropout=0.5))
      .layer(DenseLayer(n_out=4096, activation="relu", dropout=0.5))
      .layer(OutputLayer(n_out=n_classes, loss="mcxent", activation="softmax"))
      .set_input_type(InputType.convolutional(height, width, channels)))
    return MultiLayerNetwork(b.build()).init()


def _inception(g, name: str, in_name: str, c1: int, c3r: int, c3: int,
               c5r: int, c5: int, cp: int) -> str:
    """GoogLeNet inception module: four parallel branches (1x1 | 1x1->3x3 |
    1x1->5x5 | maxpool->1x1) channel-concatenated via MergeVertex."""
    g.add_layer(f"{name}_b1", ConvolutionLayer(
        n_out=c1, kernel_size=(1, 1), activation="relu", weight_init="relu"),
        in_name)
    g.add_layer(f"{name}_b2r", ConvolutionLayer(
        n_out=c3r, kernel_size=(1, 1), activation="relu", weight_init="relu"),
        in_name)
    g.add_layer(f"{name}_b2", ConvolutionLayer(
        n_out=c3, kernel_size=(3, 3), padding=(1, 1), activation="relu",
        weight_init="relu"), f"{name}_b2r")
    g.add_layer(f"{name}_b3r", ConvolutionLayer(
        n_out=c5r, kernel_size=(1, 1), activation="relu", weight_init="relu"),
        in_name)
    g.add_layer(f"{name}_b3", ConvolutionLayer(
        n_out=c5, kernel_size=(5, 5), padding=(2, 2), activation="relu",
        weight_init="relu"), f"{name}_b3r")
    g.add_layer(f"{name}_b4p", SubsamplingLayer(
        pooling_type="max", kernel_size=(3, 3), stride=(1, 1), padding=(1, 1)),
        in_name)
    g.add_layer(f"{name}_b4", ConvolutionLayer(
        n_out=cp, kernel_size=(1, 1), activation="relu", weight_init="relu"),
        f"{name}_b4p")
    g.add_vertex(f"{name}_cat", MergeVertex(), f"{name}_b1", f"{name}_b2",
                 f"{name}_b3", f"{name}_b4")
    return f"{name}_cat"


def googlenet(height: int = 224, width: int = 224, channels: int = 3,
              n_classes: int = 1000, seed: int = 12345,
              updater: str = "nesterovs", lr: float = 0.01,
              compute_dtype: Optional[str] = None) -> ComputationGraph:
    """GoogLeNet / Inception-v1 as a ComputationGraph — the era model whose
    parallel-branch modules exercise MergeVertex channel concatenation at
    benchmark scale (the reference's DAG merge capability,
    ``nn/graph/vertex/impl/MergeVertex.java``)."""
    b = (
        NeuralNetConfiguration.builder()
        .seed(seed)
        .updater(updater, learning_rate=lr)
        .regularization(True)
        .l2(2e-4)
        .graph()
        .add_inputs("input")
        .set_input_types(input=InputType.convolutional(height, width, channels))
    )
    if compute_dtype:
        b.compute_dtype(compute_dtype)
    b.add_layer("stem1", ConvolutionLayer(
        n_out=64, kernel_size=(7, 7), stride=(2, 2), padding=(3, 3),
        activation="relu", weight_init="relu"), "input")
    b.add_layer("pool1", SubsamplingLayer(
        pooling_type="max", kernel_size=(3, 3), stride=(2, 2), padding=(1, 1)),
        "stem1")
    b.add_layer("stem2r", ConvolutionLayer(
        n_out=64, kernel_size=(1, 1), activation="relu", weight_init="relu"),
        "pool1")
    b.add_layer("stem2", ConvolutionLayer(
        n_out=192, kernel_size=(3, 3), padding=(1, 1), activation="relu",
        weight_init="relu"), "stem2r")
    b.add_layer("pool2", SubsamplingLayer(
        pooling_type="max", kernel_size=(3, 3), stride=(2, 2), padding=(1, 1)),
        "stem2")
    # (c1, c3r, c3, c5r, c5, cp) per module — the published v1 table
    prev = _inception(b, "i3a", "pool2", 64, 96, 128, 16, 32, 32)
    prev = _inception(b, "i3b", prev, 128, 128, 192, 32, 96, 64)
    b.add_layer("pool3", SubsamplingLayer(
        pooling_type="max", kernel_size=(3, 3), stride=(2, 2), padding=(1, 1)),
        prev)
    prev = _inception(b, "i4a", "pool3", 192, 96, 208, 16, 48, 64)
    prev = _inception(b, "i4b", prev, 160, 112, 224, 24, 64, 64)
    prev = _inception(b, "i4c", prev, 128, 128, 256, 24, 64, 64)
    prev = _inception(b, "i4d", prev, 112, 144, 288, 32, 64, 64)
    prev = _inception(b, "i4e", prev, 256, 160, 320, 32, 128, 128)
    b.add_layer("pool4", SubsamplingLayer(
        pooling_type="max", kernel_size=(3, 3), stride=(2, 2), padding=(1, 1)),
        prev)
    prev = _inception(b, "i5a", "pool4", 256, 160, 320, 32, 128, 128)
    prev = _inception(b, "i5b", prev, 384, 192, 384, 48, 128, 128)
    b.add_layer("gap", GlobalPoolingLayer(pooling_type="avg"), prev)
    b.add_layer("fc", OutputLayer(n_out=n_classes, loss="mcxent",
                                  activation="softmax", weight_init="xavier",
                                  dropout=0.4), "gap")
    conf = b.set_outputs("fc").build()
    return ComputationGraph(conf).init()


def dbn(n_in: int = 784, hidden: Sequence[int] = (500, 250, 100),
        n_classes: int = 10, seed: int = 12345, updater: str = "nesterovs",
        lr: float = 0.1, k: int = 1) -> MultiLayerNetwork:
    """Deep Belief Network — stacked RBMs + softmax output, trained by
    layerwise CD-k ``pretrain`` then supervised ``fit`` (the reference's
    historical flagship workflow: RBM contrastive divergence
    ``nn/layers/feedforward/rbm/RBM.java:66,99`` under
    ``MultiLayerNetwork.pretrain`` ``MultiLayerNetwork.java:164``)."""
    b = (
        NeuralNetConfiguration.builder()
        .seed(seed)
        .updater(updater, learning_rate=lr)
        .list()
    )
    prev = n_in
    for li, h in enumerate(hidden):
        # first RBM sees real-valued inputs (gaussian visible); deeper ones
        # see sigmoid activations in [0,1] (binary visible)
        b.layer(RBM(n_in=prev, n_out=h, hidden_unit="binary",
                    visible_unit="gaussian" if li == 0 else "binary", k=k))
        prev = h
    b.layer(OutputLayer(n_in=prev, n_out=n_classes, loss="mcxent",
                        activation="softmax"))
    return MultiLayerNetwork(b.build()).init()


def graves_lstm_char_lm(vocab_size: int = 77, hidden: int = 200,
                        seq_len: int = 64, layers: int = 2,
                        seed: int = 12345, updater: str = "rmsprop",
                        lr: float = 0.1, tbptt: int = 50) -> MultiLayerNetwork:
    """GravesLSTM character language model (the classic DL4J char-RNN
    example shape; reference recurrent benchmark config)."""
    b = (
        NeuralNetConfiguration.builder()
        .seed(seed)
        .updater(updater, learning_rate=lr)
        .list()
    )
    n_in = vocab_size
    for i in range(layers):
        b.layer(GravesLSTM(n_in=n_in, n_out=hidden, activation="tanh"))
        n_in = hidden
    b.layer(RnnOutputLayer(n_in=hidden, n_out=vocab_size, loss="mcxent",
                           activation="softmax"))
    conf = b.backprop_type("truncated_bptt", fwd_length=tbptt, back_length=tbptt).build()
    return MultiLayerNetwork(conf).init()


def transformer_char_lm(vocab_size: int = 77, d_model: int = 128,
                        n_heads: int = 4, layers: int = 2,
                        ff_mult: int = 4, seed: int = 12345,
                        updater: str = "adam", lr: float = 1e-3,
                        seq_axis: Optional[str] = None,
                        remat: bool = False,
                        compute_dtype: Optional[str] = None,
                        rope: bool = True,
                        n_kv_heads: Optional[int] = None,
                        window: Optional[int] = None,
                        max_cache: int = 1024,
                        stability=None,
                        introspection=None,
                        numerics=None) -> MultiLayerNetwork:
    """Causal transformer char-LM — the long-context flagship (no reference
    analog: the reference is pre-transformer, SURVEY.md §5).  With
    ``seq_axis='seq'`` every attention layer runs ring attention over the
    mesh sequence axis (see ``parallel.sequence_parallel``): train
    sequences sharded over chips without materializing full K/V.  With
    ``remat=True`` each block rematerializes its activations in the
    backward pass (jax.checkpoint) — the other half of the long-context
    memory budget.

    ``rope=True`` (default since 2026-07-30) adds rotary position
    embeddings on q/k — parameter-free, so checkpoints are shape-
    compatible either way, but logits differ: models SAVED with the
    earlier position-free config reload exactly (the zip carries
    ``rope`` in the layer config, absent -> False); only params-only
    reloads through this builder must pass ``rope=False`` explicitly."""
    from deeplearning4j_tpu.nn.layers import (
        EmbeddingLayer, LayerNorm, ResidualBlock, SelfAttentionLayer,
    )

    nb = (
        NeuralNetConfiguration.builder()
        .seed(seed)
        .updater(updater, learning_rate=lr)
    )
    if stability is not None:
        # training-stability engine (nn.conf.TrainingStability): the
        # non-finite guard + loss scaling the production loops run with
        nb.training_stability(stability)
    if introspection is not None:
        # training-introspection engine (nn.conf.TrainingIntrospection):
        # per-layer gradient/update/activation stats inside the step
        nb.training_introspection(introspection)
    if numerics is not None:
        # precision-ledger engine (nn.conf.TrainingNumerics): per-layer
        # dynamic-range / format-safety stats inside the step
        nb.training_numerics(numerics)
    b = nb.list()
    if compute_dtype:
        b.compute_dtype(compute_dtype)
    # collapse_column off: ids are [B, T] sequences; a length-1 prompt must
    # keep its time axis (see EmbeddingLayer.collapse_column)
    b.layer(EmbeddingLayer(n_in=vocab_size, n_out=d_model,
                           collapse_column=False))
    for i in range(layers):
        b.layer(ResidualBlock(remat=remat, layers=(
            LayerNorm(n_in=d_model),
            SelfAttentionLayer(n_in=d_model, n_out=d_model,
                               n_heads=n_heads, causal=True,
                               seq_axis=seq_axis, rope=rope,
                               n_kv_heads=n_kv_heads, window=window,
                               max_cache=max_cache),
        )))
        b.layer(ResidualBlock(remat=remat, layers=(
            LayerNorm(n_in=d_model),
            DenseLayer(n_in=d_model, n_out=d_model * ff_mult, activation="relu"),
            DenseLayer(n_in=d_model * ff_mult, n_out=d_model, activation="identity"),
        )))
    b.layer(LayerNorm(n_in=d_model))
    b.layer(RnnOutputLayer(n_in=d_model, n_out=vocab_size, loss="mcxent",
                           activation="softmax"))
    return MultiLayerNetwork(b.build()).init()
