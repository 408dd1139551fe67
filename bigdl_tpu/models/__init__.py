"""Model zoo (reference dl/.../bigdl/models/, SURVEY §2.9)."""

from bigdl_tpu.models.lenet.model import LeNet5
from bigdl_tpu.models.alexnet import AlexNet, AlexNet_OWT
from bigdl_tpu.models.autoencoder.model import Autoencoder
from bigdl_tpu.models.inception.model import (Inception_Layer_v1, Inception_v1,
                                        Inception_v1_NoAuxClassifier,
                                        Inception_Layer_v2, Inception_v2,
                                        Inception_v2_NoAuxClassifier)
from bigdl_tpu.models.vgg.model import VggForCifar10, Vgg_16, Vgg_19
from bigdl_tpu.models.resnet.model import (ResNet, ShortcutType, DatasetType,
                                     model_init)
from bigdl_tpu.models.rnn.model import SimpleRNN, BatchedSimpleRNN
from bigdl_tpu.models.transformer.model import (EvaByteLM, KeyeLM, KimiLM,
                                                NemotronHLM, SmallThinkerLM,
                                                TransformerBlock,
                                                TransformerLM)

__all__ = [
    "LeNet5", "AlexNet", "AlexNet_OWT", "Autoencoder",
    "Inception_Layer_v1", "Inception_v1", "Inception_v1_NoAuxClassifier",
    "Inception_Layer_v2", "Inception_v2", "Inception_v2_NoAuxClassifier",
    "VggForCifar10", "Vgg_16", "Vgg_19",
    "ResNet", "ShortcutType", "DatasetType", "model_init",
    "SimpleRNN", "BatchedSimpleRNN",
    "TransformerLM", "TransformerBlock", "EvaByteLM", "KeyeLM", "KimiLM",
    "NemotronHLM", "SmallThinkerLM",
]
