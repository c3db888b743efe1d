"""The one place the benchmark builds the program's model from a
configuration's file (the cell kinds and the rate sweep share it)."""

from __future__ import annotations


def make_model(cfg: dict):
    import jax.numpy as jnp

    from machine_learning_apache_spark_tpu.models.transformer import (
        Transformer,
        TransformerConfig,
    )

    return Transformer(TransformerConfig(
        src_vocab_size=cfg["src_vocab_size"],
        trg_vocab_size=cfg["trg_vocab_size"],
        d_model=cfg["d_model"], ffn_hidden=cfg["ffn_hidden"],
        num_heads=cfg["num_heads"], num_layers=cfg["num_layers"],
        dropout=cfg["dropout"], max_len=cfg["max_len"], pad_id=cfg["pad_id"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
    ))
