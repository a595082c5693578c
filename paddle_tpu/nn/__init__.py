"""paddle.nn-equivalent namespace (reference: python/paddle/nn/__init__.py,
137 exported layer symbols)."""
from . import functional  # noqa: F401
from . import layout  # noqa: F401  (channels-last trunk annotation helpers)
from . import initializer  # noqa: F401
from .layer import (  # noqa: F401
    Layer, Sequential, LayerList, LayerDict, ParameterList, Identity, ParamAttr,
)
from .layers.common import (  # noqa: F401
    Linear, Embedding, Dropout, Dropout2D, Dropout3D, AlphaDropout, Flatten,
    Pad1D, Pad2D, Pad3D, Upsample, UpsamplingBilinear2D, UpsamplingNearest2D,
    PixelShuffle, Unfold, Bilinear,
)
from .layers.conv import Conv1D, Conv2D, Conv3D, Conv2DTranspose  # noqa: F401
from .layers.norm import (  # noqa: F401
    LayerNorm, RMSNorm, BatchNorm, BatchNorm1D, BatchNorm2D, BatchNorm3D,
    SyncBatchNorm, GroupNorm, InstanceNorm1D, InstanceNorm2D, InstanceNorm3D,
    LocalResponseNorm,
)
from .layers.activation import (  # noqa: F401
    ReLU, ReLU6, GELU, SiLU, Swish, ELU, SELU, CELU, LeakyReLU, PReLU, Sigmoid,
    Tanh, Softmax, LogSoftmax, Hardtanh, Hardsigmoid, Hardswish, Hardshrink,
    Softshrink, Tanhshrink, Mish, Softplus, Softsign, GLU, ThresholdedReLU, Maxout,
    Softmax2D,
)
from .layers.pooling import (  # noqa: F401
    MaxPool1D, MaxPool2D, AvgPool1D, AvgPool2D, AdaptiveAvgPool1D,
    AdaptiveAvgPool2D, AdaptiveMaxPool2D,
)
from .layers.loss import (  # noqa: F401
    CrossEntropyLoss, MSELoss, L1Loss, NLLLoss, BCELoss, BCEWithLogitsLoss,
    KLDivLoss, SmoothL1Loss, MarginRankingLoss, CTCLoss, CosineSimilarity,
    CosineEmbeddingLoss, TripletMarginLoss, HingeEmbeddingLoss,
    MultiMarginLoss, SoftMarginLoss, MultiLabelSoftMarginLoss, RNNTLoss,
    HSigmoidLoss,
)
from .layers.transformer import (  # noqa: F401
    MultiHeadAttention, TransformerEncoderLayer, TransformerEncoder,
    TransformerDecoderLayer, TransformerDecoder, Transformer,
)
from .layers.rnn import (  # noqa: F401
    SimpleRNN, LSTM, GRU, RNN, SimpleRNNCell, LSTMCell, GRUCell,
)
from .layers.decode import BeamSearchDecoder, dynamic_decode  # noqa: F401
from .layers.experts import HeldExperts  # noqa: F401

from ..core.tensor import Parameter  # noqa: F401


class ClipGradByNorm:
    """Reference: paddle.nn.ClipGradByNorm (fluid/clip.py)."""

    def __init__(self, clip_norm):
        self.clip_norm = clip_norm


class ClipGradByGlobalNorm:
    """Reference: paddle.nn.ClipGradByGlobalNorm (fluid/clip.py:449)."""

    def __init__(self, clip_norm, group_name="default_group"):
        self.clip_norm = clip_norm


class ClipGradByValue:
    def __init__(self, max, min=None):  # noqa: A002
        self.max = max
        self.min = -max if min is None else min

from .layers.extras import (  # noqa: E402,F401
    MaxPool3D, AvgPool3D, AdaptiveMaxPool1D, AdaptiveMaxPool3D,
    AdaptiveAvgPool3D, Conv1DTranspose, Conv3DTranspose, SpectralNorm,
    RReLU, LogSigmoid, Silu, RNNCellBase, BiRNN, HuberLoss, SoftMarginLoss,
    MultiLabelSoftMarginLoss, PoissonNLLLoss, GaussianNLLLoss,
    PairwiseDistance, TripletMarginWithDistanceLoss, ZeroPad2D,
    PixelUnshuffle, ChannelShuffle, Fold, Unflatten, MaxUnPool1D,
    MaxUnPool2D, MaxUnPool3D,
)
