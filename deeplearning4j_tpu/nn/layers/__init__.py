from deeplearning4j_tpu.nn.layers.base import Layer, register_layer, layer_from_dict
from deeplearning4j_tpu.nn.layers.dense import (
    DenseLayer,
    OutputLayer,
    ActivationLayer,
    DropoutLayer,
    EmbeddingLayer,
    GatedMLP,
)
from deeplearning4j_tpu.nn.layers.convolution import (
    ConvolutionLayer,
    SubsamplingLayer,
    GlobalPoolingLayer,
)
from deeplearning4j_tpu.nn.layers.normalization import (
    BatchNormalization,
    LayerNorm,
    LocalResponseNormalization,
    RMSNorm,
)
from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
from deeplearning4j_tpu.nn.layers.latent_attention import LatentAttentionLayer
from deeplearning4j_tpu.nn.layers.composite import (
    HyperConnectionBlock,
    HyperStreamExpand,
    HyperStreamReduce,
    ResidualBlock,
)
from deeplearning4j_tpu.nn.layers.recurrent import (
    GravesLSTM,
    GravesBidirectionalLSTM,
    LSTM,
    RnnOutputLayer,
)
from deeplearning4j_tpu.nn.layers.state_space import MambaLayer
from deeplearning4j_tpu.nn.layers.delta_net import (
    GatedDeltaNetLayer, KimiDeltaAttentionLayer,
)
from deeplearning4j_tpu.nn.layers.autoencoder import AutoEncoder, RBM
from deeplearning4j_tpu.nn.layers.moe import MoELayer, RoutedMoELayer
