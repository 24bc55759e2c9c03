"""bigdl_tpu.nn — layer + criterion library (reference: ``bigdl/nn``)."""

from bigdl_tpu.nn.module import Module, Criterion, spec_of  # noqa: F401
from bigdl_tpu.nn.init_methods import (  # noqa: F401
    InitializationMethod, Zeros, Ones, ConstInitMethod, RandomUniform,
    RandomNormal, Xavier, MsraFiller, BilinearFiller)
from bigdl_tpu.nn.linear import (  # noqa: F401
    Linear, Cosine, Euclidean, Bilinear)
from bigdl_tpu.nn.activation import (  # noqa: F401
    ReLU, ReLU6, Sigmoid, Tanh, HardTanh, HardSigmoid, SoftMax, SoftMin,
    LogSoftMax, LogSigmoid, SoftPlus, SoftSign, ELU, GELU, Threshold, PReLU,
    RReLU, SReLU, HardShrink, SoftShrink, TanhShrink, Power, Square, Sqrt,
    Abs, Clamp, Exp, Log, Negative, Identity, Maxout)
from bigdl_tpu.nn.conv import (  # noqa: F401
    SpatialConvolution, SpatialDilatedConvolution, SpatialFullConvolution,
    SpatialSeparableConvolution, TemporalConvolution, VolumetricConvolution,
    SpatialShareConvolution, VolumetricFullConvolution)
from bigdl_tpu.nn.pooling import (  # noqa: F401
    SpatialMaxPooling, SpatialAveragePooling, TemporalMaxPooling,
    VolumetricMaxPooling, VolumetricAveragePooling)
from bigdl_tpu.nn.normalization import (  # noqa: F401
    BatchNormalization, SpatialBatchNormalization,
    VolumetricBatchNormalization, LayerNormalization, RMSNorm,
    SpatialCrossMapLRN,
    SpatialWithinChannelLRN, Normalize, NormalizeScale)
from bigdl_tpu.nn.basic import (  # noqa: F401
    Reshape, View, Flatten, Transpose, Squeeze, Unsqueeze, Select, Narrow,
    Index, Replicate, Tile, Reverse, Contiguous, Padding, SpatialZeroPadding,
    Dropout, SpatialDropout2D, GaussianNoise, GaussianDropout, Mean, Sum,
    Max, Min, AddConstant, MulConstant, Add, Mul, CMul, CAdd, Scale, Masking,
    Pack, Echo)
from bigdl_tpu.nn.containers import (  # noqa: F401
    Container, Sequential, Concat, ConcatTable, ParallelTable, MapTable,
    Bottle)
from bigdl_tpu.nn.table_ops import (  # noqa: F401
    CAddTable, CSubTable, CMulTable, CDivTable, CMaxTable, CMinTable,
    CAveTable, JoinTable, SplitTable, SelectTable, FlattenTable, MixtureTable,
    DotProduct, CosineDistance, MM, MV)
from bigdl_tpu.nn.graph import Graph, Node, Input  # noqa: F401
from bigdl_tpu.nn.recurrent import (  # noqa: F401
    Cell, RnnCell, LSTM, LSTMPeephole, GRU, ConvLSTMPeephole,
    ConvLSTMPeephole3D, MultiRNNCell,
    Recurrent, RecurrentDecoder, BiRecurrent, TimeDistributed)
from bigdl_tpu.nn.embedding import LookupTable, LookupTableSparse  # noqa: F401
from bigdl_tpu.nn.locally_connected import (  # noqa: F401
    LocallyConnected1D, LocallyConnected2D)
from bigdl_tpu.nn.quantized import (  # noqa: F401
    QuantizedLinear, QuantizedSpatialConvolution,
    QuantizedSpatialDilatedConvolution, Quantizer)
from bigdl_tpu.nn.tree_lstm import (  # noqa: F401
    BinaryTreeLSTM, TreeGather, TreeLSTM)
from bigdl_tpu.nn.sparse import (  # noqa: F401
    SparseTensor, SparseLinear, SparseJoinTable, DenseToSparse,
    dense_to_sparse)
from bigdl_tpu.nn.criterion import (  # noqa: F401
    ClassNLLCriterion, CrossEntropyCriterion, MSECriterion, AbsCriterion,
    BCECriterion, BCECriterionWithLogits, SmoothL1Criterion, MarginCriterion,
    MarginRankingCriterion, CosineEmbeddingCriterion, HingeEmbeddingCriterion,
    SoftMarginCriterion, MultiMarginCriterion, MultiLabelMarginCriterion,
    MultiLabelSoftMarginCriterion, DistKLDivCriterion, KLDCriterion,
    GaussianCriterion, L1Cost, DiceCoefficientCriterion, PGCriterion,
    MultiCriterion, ParallelCriterion, TimeDistributedCriterion,
    TransformerCriterion, SoftmaxWithCriterion, ClassSimplexCriterion,
    L1HingeEmbeddingCriterion, CosineDistanceCriterion,
    CosineProximityCriterion, DotProductCriterion, PoissonCriterion,
    KullbackLeiblerDivergenceCriterion, MeanAbsolutePercentageCriterion,
    MeanSquaredLogarithmicCriterion, CategoricalCrossEntropy,
    SmoothL1CriterionWithWeights, NegativeEntropyPenalty,
    TimeDistributedMaskCriterion)
from bigdl_tpu.nn.detection import (  # noqa: F401
    Anchor, Nms, PriorBox, Proposal, RoiPooling, DetectionOutputSSD,
    DetectionOutputFrcnn, iou_matrix, nms_keep, bbox_transform_inv,
    clip_boxes, decode_boxes)
from bigdl_tpu.nn.misc import (  # noqa: F401
    InferReshape, MaskedSelect,
    BinaryThreshold, BifurcateSplitTable, NarrowTable, CrossProduct,
    PairwiseDistance, GradientReversal, L1Penalty, ActivityRegularization,
    GaussianSampler, Cropping3D, UpSampling3D, SpatialDropout3D,
    SpatialSubtractiveNormalization, SpatialDivisiveNormalization,
    SpatialContrastiveNormalization, SpatialConvolutionMap,
    LeakyReLU, Cropping2D, UpSampling1D, UpSampling2D, SpatialDropout1D,
    Highway, ResizeBilinear)
from bigdl_tpu.nn.conv import (  # noqa: F401
    SpatialSeperableConvolution)
from bigdl_tpu.nn.moe import (  # noqa: F401
    MoE, RoutedExperts, SharedAndRoutedExperts)
from bigdl_tpu.nn.latent import (  # noqa: F401
    LatentAttention, SelectedLatentAttention, WindowLatentAttention)
from bigdl_tpu.nn.gated import GatedMLP, GatedShortConv  # noqa: F401
from bigdl_tpu.nn.ssm import Mamba2Mixer  # noqa: F401
