"""Neural network layers (reference python/mxnet/gluon/nn/)."""
from .basic_layers import (
    Sequential, HybridSequential, Dense, Dropout, Flatten, Lambda,
    HybridLambda, Embedding, Activation, LeakyReLU, PReLU, ELU, SELU, GELU,
    Swish, SiLU, BatchNorm, BatchNormReLU, LayerNorm, GroupNorm, InstanceNorm, Identity,
)
from .conv_layers import (
    Conv1D, Conv2D, Conv3D, Conv1DTranspose, Conv2DTranspose, Conv3DTranspose,
    MaxPool1D, MaxPool2D, MaxPool3D, AvgPool1D, AvgPool2D, AvgPool3D,
    GlobalMaxPool1D, GlobalMaxPool2D, GlobalMaxPool3D,
    GlobalAvgPool1D, GlobalAvgPool2D, GlobalAvgPool3D, ReflectionPad2D,
)
from .transformer_layers import (RMSNorm, SwiGLU, ReLU2MLP, RoutedFFN,
                                 Mamba2Mixer, GroupedQueryAttention)
