"""The layer equations a configuration is judged and counted by.

A configuration file may name its module with the key ``"arch"``:
``"arch": "<name>"`` is ``chipbench/archs/<name>.py``; a file without the key
gets ``gqa``, the dense and MoE grouped-query stack of ``reference.py`` and
``flops.py``.  A module provides:

``published_pairs(m)``  the ``program`` keys checked against the published
                        names the configuration file also holds.
``CACHE_KEYS``          the engine's paged cache arrays the check compares,
                        each mapped to the name the module's reference gives
                        that tensor (``{"kv_k": "k", "kv_v": "v"}``).
``compare_served(m, seed, prompts, served, program_kv, program_logits, modes)``
                        the plain reference's readings (``reference.py``).
``reduced_program(m)``  the ``program`` block at CPU stand-in sizes.
``prompt_flops(m, n)``, ``token_flops(m, context, head)``
                        FLOPs the served work requires (``flops.py``).
``paged_attention_calls(m, lengths, page_size)``
                        one decode step's paged-attention kernel calls:
                        ``(calls, flops, bytes)``, the number of alike calls
                        and the FLOPs and bytes of one.

So a configuration with layer equations of its own adds its module here
and touches no other file of the harness.
"""

from __future__ import annotations

import importlib
from types import ModuleType
from typing import Dict

DEFAULT = "gqa"


def for_config(cf: Dict) -> ModuleType:
    """The arch module a configuration file names, or the default."""
    return importlib.import_module(f"chipbench.archs.{cf.get('arch', DEFAULT)}")
