"""Layer & criterion library (reference: dl/.../bigdl/nn/, 138 files)."""

from bigdl_tpu.nn.module import Module, Container, Criterion, Identity, Echo
from bigdl_tpu.nn.containers import (Sequential, Concat, ConcatTable,
                                     ParallelTable, MapTable, Bottle, Remat)
from bigdl_tpu.nn.linear import (Linear, GatedFFN, Bilinear, LookupTable,
                                 Cosine,
                                 Euclidean, Add, CAdd, CMul, Mul, MM, MV)
from bigdl_tpu.nn.activations import (
    ReLU, ReLU6, PReLU, RReLU, LeakyReLU, ELU, Tanh, TanhShrink, Sigmoid,
    LogSigmoid, SoftMax, SoftMin, LogSoftMax, SoftPlus, SoftSign, HardTanh,
    HardShrink, SoftShrink, Threshold, Clamp, Power, Sqrt, Square, Abs, Log,
    Exp, GradientReversal, Scale, MulConstant, AddConstant)
from bigdl_tpu.nn.conv import (SpatialConvolution, SpatialShareConvolution,
                               SpatialFullConvolution,
                               SpatialDilatedConvolution,
                               SpatialConvolutionMap)
from bigdl_tpu.nn.pooling import (SpatialMaxPooling, SpatialAveragePooling,
                                  RoiPooling)
from bigdl_tpu.nn.normalization import (
    BatchNormalization, SpatialBatchNormalization, SpatialCrossMapLRN,
    ReLUCrossMapLRN, Normalize, SpatialDivisiveNormalization,
    SpatialSubtractiveNormalization, SpatialContrastiveNormalization,
    LayerNorm, RMSNorm)
from bigdl_tpu.nn.dropout import Dropout, L1Penalty
from bigdl_tpu.nn.structural import (
    Reshape, InferReshape, View, Transpose, Squeeze, Unsqueeze, Select,
    SelectTable, Narrow, NarrowTable, Index, JoinTable, SplitTable,
    FlattenTable, Replicate, Padding, SpatialZeroPadding, Copy, Contiguous,
    Sum, Mean, Max, Min)
from bigdl_tpu.nn.table_ops import (CAddTable, CSubTable, CMulTable,
                                    CDivTable, CMaxTable, CMinTable,
                                    DotProduct, PairwiseDistance,
                                    CosineDistance, MixtureTable,
                                    MaskedSelect)
from bigdl_tpu.nn.recurrent import (Cell, RnnCell, RNN, LSTM, GRU, Recurrent,
                                    BiRecurrent, TimeDistributed)
from bigdl_tpu.nn.attention import (MultiHeadAttention, EvaAttention,
                                    SparseSelectAttention, LatentAttention)
from bigdl_tpu.nn.criterion import (
    ClassNLLCriterion, MSECriterion, BCECriterion, CrossEntropyCriterion,
    ClassSimplexCriterion, AbsCriterion, CosineEmbeddingCriterion,
    DistKLDivCriterion, HingeEmbeddingCriterion, L1Cost,
    L1HingeEmbeddingCriterion, MarginCriterion, MarginRankingCriterion,
    MultiCriterion, MultiLabelMarginCriterion, MultiLabelSoftMarginCriterion,
    MultiMarginCriterion, SmoothL1Criterion, SmoothL1CriterionWithWeights,
    SoftMarginCriterion, SoftmaxWithCriterion, ParallelCriterion,
    TimeDistributedCriterion, CriterionTable, MaskedCriterion,
    MultiBytePredictionCriterion)
from bigdl_tpu.nn.detection import Nms, nms
from bigdl_tpu.nn import init  # noqa: F401
