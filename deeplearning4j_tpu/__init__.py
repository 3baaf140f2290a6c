"""deeplearning4j_tpu — a TPU-native deep-learning framework.

A ground-up re-design of the capabilities of Eclipse Deeplearning4j
(reference: /root/reference, surveyed in SURVEY.md) on jax/XLA/Pallas:

- ``nn``       — layer catalog, config DSL, sequential + DAG networks
                 (reference: deeplearning4j-nn)
- ``ops``      — Pallas kernels + custom lowerings for the hot paths
                 (reference role: libnd4j / deeplearning4j-cuda helpers)
- ``parallel`` — mesh-based data/model parallelism over ICI/DCN
                 (reference role: ParallelWrapper + Spark TrainingMasters)
- ``datasets`` — dataset fetchers/iterators with async prefetch
                 (reference: deeplearning4j-core datasets + AsyncDataSetIterator)
- ``eval``     — evaluation suite (reference: org.deeplearning4j.eval)
- ``models``   — model zoo (reference: deeplearning4j-zoo)
- ``utils``    — dtype policy, serde registry, checkpointing
"""

import time as _time

#: ``perf_counter`` as the package's first line ran: the mark
#: ``program_entered`` of ``utils.compile_cache.startup_marks()`` (what a
#: process spent before it is the interpreter's, jax's and the caller's)
_PROGRAM_ENTERED = _time.perf_counter()

__version__ = "0.1.0"

from deeplearning4j_tpu.utils import dtypes  # noqa: F401
