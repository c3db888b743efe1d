"""ops — attention core, masks, positional encodings.

The reference's hot inner kernel is ``scaled_dot_product`` + the mask builders
(``transformer.py:12-25``, ``pytorch_machine_translator.py:102-104``) — see
SURVEY.md §3.3. Implemented here once, with *correct* semantics (quirks Q8/Q9
fixed: boolean masks select, they are never added; query/key lengths are
independent).

Mask convention (flax-style): boolean, ``True = attendable``. The reference's
look-ahead mask uses the opposite polarity (True = masked,
``pytorch_machine_translator.py:102-104``) and then *adds* it (Q9); converting
at the boundary keeps the framework internally consistent.
"""

from machine_learning_apache_spark_tpu.ops.masks import (
    make_causal_mask,
    make_padding_mask,
    make_attention_mask,
    make_segment_mask,
    combine_masks,
)
from machine_learning_apache_spark_tpu.ops.positional import (
    rotary_embedding,
    sinusoidal_encoding,
)
from machine_learning_apache_spark_tpu.ops.gated_delta import (
    gated_delta_recurrent,
    gated_delta_rule,
)
from machine_learning_apache_spark_tpu.ops.attention import (
    attention_impl,
    kernel_mesh,
    dot_product_attention,
    ragged_paged_attention,
    scaled_dot_product_attention,
    multi_head_attention_weights,
    sequence_parallel,
)

__all__ = [
    "attention_impl",
    "kernel_mesh",
    "dot_product_attention",
    "ragged_paged_attention",
    "make_causal_mask",
    "make_padding_mask",
    "make_attention_mask",
    "make_segment_mask",
    "combine_masks",
    "sinusoidal_encoding",
    "rotary_embedding",
    "gated_delta_rule",
    "gated_delta_recurrent",
    "scaled_dot_product_attention",
    "multi_head_attention_weights",
    "sequence_parallel",
]
