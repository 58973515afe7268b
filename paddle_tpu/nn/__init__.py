"""paddle.nn parity surface."""
from . import functional  # noqa: F401
from . import initializer  # noqa: F401
from .layer_base import Layer, Parameter, ParamAttr  # noqa: F401
from .layout import channels_last, is_channels_last  # noqa: F401
from .meta import abstract_init, is_abstract_init  # noqa: F401
from .functional_call import functional_call, module_fn, state_values  # noqa: F401
from .clip import ClipGradByValue, ClipGradByNorm, ClipGradByGlobalNorm  # noqa: F401
from .clip import clip_grad_norm_  # noqa: F401

from .layer.common import (  # noqa: F401
    Linear, Identity, Dropout, Dropout2D, Dropout3D, AlphaDropout, Embedding,
    Flatten, Unflatten, Upsample, UpsamplingBilinear2D, UpsamplingNearest2D,
    PixelShuffle, PixelUnshuffle, ChannelShuffle, CosineSimilarity, Bilinear,
    Pad1D, Pad2D, Pad3D, ZeroPad2D,
)
from .layer.activation import (  # noqa: F401
    ReLU, ReLU6, ELU, SELU, CELU, GELU, Sigmoid, LogSigmoid, Hardsigmoid,
    Hardswish, Hardtanh, Hardshrink, Softshrink, Tanhshrink, LeakyReLU,
    Softplus, Softsign, Silu, Swish, Mish, Tanh, Softmax, LogSoftmax, Maxout,
    GLU, RReLU, PReLU,
)
from .layer.conv import (  # noqa: F401
    Conv1D, Conv2D, Conv3D, Conv1DTranspose, Conv2DTranspose, Conv3DTranspose,
)
from .layer.pooling import (  # noqa: F401
    MaxPool1D, MaxPool2D, MaxPool3D, AvgPool1D, AvgPool2D, AvgPool3D,
    AdaptiveAvgPool1D, AdaptiveAvgPool2D, AdaptiveAvgPool3D,
    AdaptiveMaxPool1D, AdaptiveMaxPool2D, AdaptiveMaxPool3D,
)
from .layer.norm import (  # noqa: F401
    BatchNorm, BatchNorm1D, BatchNorm2D, BatchNorm3D, SyncBatchNorm, LayerNorm, RMSNorm,
    GroupNorm, InstanceNorm1D, InstanceNorm2D, InstanceNorm3D,
    LocalResponseNorm, SpectralNorm,
)
from .layer.container import Sequential, LayerList, ParameterList, LayerDict  # noqa: F401
from .layer.loss import (  # noqa: F401
    CrossEntropyLoss, MSELoss, L1Loss, NLLLoss, BCELoss, BCEWithLogitsLoss,
    KLDivLoss, SmoothL1Loss, MarginRankingLoss, HingeEmbeddingLoss,
    CosineEmbeddingLoss, TripletMarginLoss, CTCLoss,
)
from .layer.rnn import (  # noqa: F401
    RNNCellBase, SimpleRNNCell, LSTMCell, GRUCell, RNN, BiRNN, SimpleRNN, LSTM,
    GRU,
)
from .layer.transformer import (  # noqa: F401
    MultiHeadAttention, TransformerEncoderLayer, TransformerEncoder,
    TransformerDecoderLayer, TransformerDecoder, Transformer,
)
from .layer.extra import (  # noqa: F401
    BeamSearchDecoder, Fold, HSigmoidLoss, MaxUnPool1D, MaxUnPool2D,
    MaxUnPool3D, MultiLabelSoftMarginLoss, PairwiseDistance, SoftMarginLoss,
    Softmax2D, ThresholdedReLU, TripletMarginWithDistanceLoss,
    dynamic_decode, Unfold,
)
from . import utils  # noqa: F401
from .utils import spectral_norm  # noqa: F401
from .layer import loss  # noqa: F401  (reference exports nn.loss)
from . import quant  # noqa: F401
