from machine_learning_apache_spark_tpu.utils.prng import KeySeq, key
from machine_learning_apache_spark_tpu.utils.logging import get_logger, rank_zero_print
from machine_learning_apache_spark_tpu.utils.timing import Timer, timed_span
from machine_learning_apache_spark_tpu.utils.profiling import (
    StepWindowTracer,
    annotate,
    device_trace,
)

__all__ = [
    "KeySeq",
    "key",
    "get_logger",
    "rank_zero_print",
    "Timer",
    "timed_span",
    "StepWindowTracer",
    "annotate",
    "device_trace",
]
