"""The sequence mixers a ``TransformerBlock`` can hold beside
``MultiHeadAttention`` (nn/layers/attention.py), a module each. A mixer is
a ``ParamLayer`` over [B,T,F] whose ``apply`` takes ``mask=`` and whose
class states, as ``param_key``, where a block keeps its parameters."""
