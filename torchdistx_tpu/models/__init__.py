"""TPU-native model stack.

The reference framework ships no models of its own — its BASELINE workloads
instantiate torchvision / HF models through deferred init.  This framework
supports that torch-module path (:mod:`torchdistx_tpu.deferred_init`) *and*
ships JAX-native model families designed for the TPU training stack:

* :mod:`torchdistx_tpu.models.llama` — Llama-2-family decoder (flagship).
* :mod:`torchdistx_tpu.models.gpt2` — GPT-2 family.
* :mod:`torchdistx_tpu.models.jamba` — state-space (Mamba-1) layers with
  an attention layer a period.
* :mod:`torchdistx_tpu.models.afmoe` — window and full attention layers
  mixed, gated attention output, sandwich norms, routed experts beside a
  shared one (:mod:`torchdistx_tpu.models.afmoe_torch` is the published
  architecture as a torch module, for the deferred-init path).
* :mod:`torchdistx_tpu.models.smallthinker` — every layer an expert
  layer whose router reads the layer's input, before attention; ReGLU
  experts; full and window attention layers 1 to 3
  (:mod:`torchdistx_tpu.models.smallthinker_torch` for the deferred-init
  path).
* :mod:`torchdistx_tpu.models.moe`, :mod:`torchdistx_tpu.models.deepseek_v3`
  — routed-expert families (imported where used).
"""

from . import afmoe, gpt2, jamba, llama, smallthinker  # noqa: F401

__all__ = ["afmoe", "gpt2", "jamba", "llama", "smallthinker"]
