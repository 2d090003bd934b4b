"""Model zoo.

Parity targets: the reference's book-model fixtures and distributed test
models (/root/reference/python/paddle/fluid/tests/book/,
tests/unittests/dist_mnist.py, dist_se_resnext.py, dist_transformer.py,
dist_ctr.py) plus the reference's headline configs (MNIST-LeNet, ResNet-50,
BERT-base, fused-attention transformer, Wide&Deep sparse).

All models are `nn.Layer`s; use `nn.layers.functional_call` /
`make_train_step` to obtain pure jittable/shardable train steps.
"""

from .lenet import LeNet
from .mlp import MLP
from .resnet import ResNet, resnet18, resnet34, resnet50, SEResNeXt
from .bert import BertConfig, BertModel, BertForPretraining, bert_base_config
from .gpt import GPTConfig, GPT
from .wide_deep import WideDeep
from .word2vec import Word2Vec
from .train import make_train_step, make_eval_step, TrainState

__all__ = [
    "LeNet", "MLP",
    "ResNet", "resnet18", "resnet34", "resnet50", "SEResNeXt",
    "BertConfig", "BertModel", "BertForPretraining", "bert_base_config",
    "GPTConfig", "GPT", "WideDeep", "Word2Vec",
    "make_train_step", "make_eval_step", "TrainState",
]
