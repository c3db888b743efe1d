"""Model zoo — MLP, CNN, LSTM, encoder-decoder Transformer, hybrid
linear/softmax-attention language model with sparse experts.

One library replacing the reference's copy-pasted per-script model classes
(C2/C5/C8 duplicated across sequential/distributed scripts, SURVEY.md §2.1)
and its ``transformer.py`` module library (C14-C23).
"""

from machine_learning_apache_spark_tpu.models.mlp import MLP
from machine_learning_apache_spark_tpu.models.cnn import TinyVGG, FashionMNISTModel
from machine_learning_apache_spark_tpu.models.lstm import LSTMClassifier
from machine_learning_apache_spark_tpu.models.transformer import (
    Transformer,
    beam_translate,
    greedy_translate,
    greedy_translate_cached,
    sample_translate,
    Encoder,
    Decoder,
    TransformerConfig,
)
from machine_learning_apache_spark_tpu.models.hybrid_lm import (
    HybridLM,
    HybridLMConfig,
)
from machine_learning_apache_spark_tpu.models.moe import DroplessMoE, MoEFeedForward

__all__ = [
    "HybridLM",
    "HybridLMConfig",
    "DroplessMoE",
    "MoEFeedForward",
    "MLP",
    "TinyVGG",
    "FashionMNISTModel",
    "LSTMClassifier",
    "Transformer",
    "beam_translate",
    "greedy_translate",
    "greedy_translate_cached",
    "sample_translate",
    "Encoder",
    "Decoder",
    "TransformerConfig",
]
