"""bigdl_tpu.models — model zoo (reference: ``bigdl/models``)."""

from bigdl_tpu.models.lenet import LeNet5, lenet_graph  # noqa: F401
from bigdl_tpu.models.resnet import ResNet  # noqa: F401
from bigdl_tpu.models.vgg import VggForCifar10, Vgg_16, Vgg_19  # noqa: F401
from bigdl_tpu.models.inception import (  # noqa: F401
    Inception_v1, Inception_v1_NoAuxClassifier, Inception_v2)
from bigdl_tpu.models.rnn import SimpleRNN, PTBModel  # noqa: F401
from bigdl_tpu.models.autoencoder import Autoencoder  # noqa: F401
from bigdl_tpu.models.alexnet import AlexNet, AlexNet_OWT  # noqa: F401
from bigdl_tpu.models.transformer import (  # noqa: F401
    BERT, BertForMLM, TransformerEncoderLayer, bert_base)
from bigdl_tpu.models.gpt import (  # noqa: F401
    GPT, GPTForCausalLM, TransformerDecoderBlock, gpt2_small)
