"""The architecture of a configuration file: which program fields, plain
reference, work counts and leaf values the harness uses for it.

A configuration whose ``model_type`` has a module
``bench/archs/<model_type>.py`` beside this file takes what that module
defines; what the module leaves out, and every configuration without a
module (``qwen3``, ``starcoder2``), takes the dense code.  A module may
define any of:

- ``program_fields(cfg: dict) -> dict``: the program's ``ModelConfig``
  fields beyond the dense ones that ``system.model_config`` maps from the
  file (``family``, ``block_pattern``, ``moe``, ``ssm``, ``rglru``,
  ``attn_window``, ...), nested groups as plain dicts.  They override the
  dense mapping; ``system.py`` builds the nested config objects and refuses
  a field ``ModelConfig`` does not have.  Dense: no fields.
- ``Reference``: the float32 plain reference, with the interface of
  ``reference.Reference``: ``Reference(cfg, quant=None)``,
  ``hidden(params, tokens)`` and ``reduce(params, x, pos, tgt)``.  It
  imports nothing of the program, takes the weights from the tree the
  harness made, and works one layer at a time on one device
  (``reference.layer_on_one_device``), so that it never runs the
  program's sharding.
- ``Shape``: the work counts, with the interface of ``counts.Shape``:
  ``Shape.from_config(cfg)``, ``weight_bytes_per_call``,
  ``prefill_call(rows, plen)``, ``decode_call(contexts)`` and
  ``decode_rows(contexts)``.  Readers reach the counts only through it.
- ``init_leaf(path, sd, key)``: the value of a weight leaf (``path`` as
  ``jax.tree_util.keystr`` gives it, ``sd`` its ``ShapeDtypeStruct``), or
  None to leave the leaf to ``weights.py``'s rules.  It is asked first.

Nothing else of the harness names an architecture, so a configuration of
any architecture the program builds enters the benchmark by new files.
"""
from __future__ import annotations

import functools
import importlib.util
import os
from dataclasses import dataclass
from typing import Callable, Optional

import counts
import reference

ARCH_DIR = os.path.dirname(os.path.abspath(__file__))


def _no_fields(cfg: dict) -> dict:
    return {}


@dataclass(frozen=True)
class Arch:
    program_fields: Callable[[dict], dict]
    Reference: type
    Shape: type
    init_leaf: Optional[Callable]


@functools.lru_cache(maxsize=None)
def _load(path: str, model_type: str):
    spec = importlib.util.spec_from_file_location(f"bench_arch_{model_type}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def of(cfg: dict) -> Arch:
    """The architecture of configuration file ``cfg`` (a dict)."""
    model_type = cfg["model_type"]
    path = os.path.join(ARCH_DIR, f"{model_type}.py")
    mod = _load(path, model_type) if os.path.exists(path) else None
    return Arch(program_fields=getattr(mod, "program_fields", _no_fields),
                Reference=getattr(mod, "Reference", reference.Reference),
                Shape=getattr(mod, "Shape", counts.Shape),
                init_leaf=getattr(mod, "init_leaf", None))
