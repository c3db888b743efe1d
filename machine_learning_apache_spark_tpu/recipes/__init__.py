"""recipes — one function per reference entry-point script (SURVEY.md §0).

Each recipe owns a workload's data resolution, hypers, fit and eval; the
sequential/distributed split the reference maintains as separate scripts
collapses: the same recipe function runs single-device, multi-chip
(data-parallel mesh), or multi-process (under ``launcher.Distributor``).
"""

from machine_learning_apache_spark_tpu.recipes.mlp import MLPRecipe, train_mlp
from machine_learning_apache_spark_tpu.recipes.cnn import CNNRecipe, train_cnn
from machine_learning_apache_spark_tpu.recipes.lstm import LSTMRecipe, train_lstm
from machine_learning_apache_spark_tpu.recipes.translation import (
    TranslationRecipe,
    train_translator,
)
from machine_learning_apache_spark_tpu.recipes.language_model import (
    LMRecipe,
    make_lm_loss,
    train_lm,
)

__all__ = [
    "MLPRecipe",
    "train_mlp",
    "CNNRecipe",
    "train_cnn",
    "LSTMRecipe",
    "train_lstm",
    "TranslationRecipe",
    "train_translator",
    "LMRecipe",
    "make_lm_loss",
    "train_lm",
]
