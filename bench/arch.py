"""The architecture of a model section, found by its ``"arch"`` key.

A configuration file's ``target`` and ``draft`` sections each name their
architecture; ``of(section)`` imports ``archs/<arch>.py`` (a namespace
package: any ``archs/`` directory on ``sys.path`` adds to it).  Everything
in the benchmark that depends on a model's layer layout goes through that
module, which gives:

    model_config(section, name, dtype)   the program's ``ModelConfig``
    make_params(section, acc, key, dtype)
                                         seeded weights with the acceptance
                                         construction (``weights.py``), in
                                         the program's parameter layout, in
                                         one jitted call
    token_flops(section)                 matrix FLOPs per token of one
                                         forward pass, attention excluded
    attention(section, ctx0, T, kv_bytes)
                                         (flops, bytes) of one row's cached
                                         attention: T queries at positions
                                         ctx0 .. ctx0 + T - 1, all layers
    ATTENTION_KERNEL                     the device operation that does the
                                         model's cached attention
"""

from __future__ import annotations

import importlib


def of(section: dict):
    """The architecture module of one model section of a config file."""
    return importlib.import_module(f"archs.{section['arch']}")
