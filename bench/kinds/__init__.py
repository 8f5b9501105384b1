"""Kinds of model, one module each, named by each configuration's ``kind``
key: ``bench/kinds/<kind>.py``, with its plain reference beside it in
``bench/references/<kind>.py``. The harness names no architecture; what it
needs of a model it takes from the kind:

- ``dims(hf_config) -> dict``: the sizes the kind computes with, from the
  published keys. Every kind gives ``layers`` and ``vocab``, the only keys
  the harness reads itself; the values are hashable, because ``dims`` is a
  static argument of jitted functions. ``bench.configs.load`` adds
  ``kind``.
- ``model_config(conf)``: the program's ``ModelConfig`` for a configuration
  as run (the program is imported inside the function).
- ``program_params(seed, dims, dtype)``: the whole model in the program's
  layout, drawn from the seed on the device (``bench.weights``), and
  ``by_name(params)``: its layer stacks by published name.
- The counts the metric readers take through ``bench.work``:
  ``prefill_flops(dims, new_tokens, prefix_tokens)`` and
  ``decode_bytes(dims, prompt_tokens, steps)``, and the sizing helpers
  ``weight_bytes``, ``decode_step_bytes``, ``payload_bytes`` and
  ``charged_bytes_per_token``.

Adding a kind is adding those two files; nothing else changes.
"""
import importlib


def module(kind: str):
    """The module of kind ``kind``."""
    return importlib.import_module(f"{__name__}.{kind}")
