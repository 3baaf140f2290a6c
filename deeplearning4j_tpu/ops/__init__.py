"""Custom device kernels (Pallas) — the framework's "cuDNN helper" tier.

Reference analog: deeplearning4j-cuda's reflectively-dispatched *Helper
classes (SURVEY.md §2.2). Here the dispatch seam is explicit, and each kernel's
module owns its choice: layers ask ``attention_pallas.resolve_attention(...)``
or ``lstm_pallas.enabled()`` / ``supported(...)``, which answer from shape,
dtype, mask and backend, and fall back to their pure-XLA path.
"""

from deeplearning4j_tpu.ops import attention_pallas, lstm_pallas  # noqa: F401
