"""Configuration families, found by name.

A configuration file names its family (``"family": "dense"``); the family's
module, ``bench/families/<family>.py``, holds everything the benchmark knows
about that kind of architecture:

* ``arch_config(cfg)``  -- the program's ``ArchConfig`` for the file's keys;
* ``shapes(cfg)``       -- ``{path: shape}`` of every parameter, in the
  layout the program's model takes;
* ``init(path, shape)`` -- ``(kind, std)`` of a leaf's seeded values:
  ``"normal"`` (N(0, std^2)) or ``"norm"`` (1 + N(0, std^2));
* ``reference()``       -- the plain reference module it is checked
  against, with ``logits_at(cfg, params, tokens, positions, dtype)`` and
  ``batch_loss(cfg, params, rows, dtype)``;
* its costs -- ``matmul_params(cfg)``, ``train_flops_per_token(cfg, seq)``,
  ``decode_step_cost(cfg, active, kv_tokens)`` and
  ``kv_bytes_per_token(cfg)``.

A new family is a new file here (or in another directory put on
:data:`SEARCH`); no other file changes.
"""

from __future__ import annotations

import importlib.util
import os

#: the directories a family's module is looked for in, in order
SEARCH = [os.path.dirname(os.path.abspath(__file__))]

_LOADED: dict[str, object] = {}


def load(cfg: dict):
    """The family module that ``cfg`` names."""
    name = cfg.get("family")
    if not name:
        raise ValueError(f"configuration {cfg.get('name')!r} names no family")
    paths = [os.path.join(d, name + ".py") for d in SEARCH]
    path = next((p for p in paths if os.path.isfile(p)), None)
    if path is None:
        raise FileNotFoundError(f"configuration {cfg.get('name')!r} names "
                                f"family {name!r}, but there is no {paths[0]}")
    if path not in _LOADED:
        spec = importlib.util.spec_from_file_location(
            "bench_family_" + name.replace("-", "_").replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


def param_count(cfg: dict) -> int:
    """Elements of every parameter of ``cfg``."""
    n = 0
    for shape in load(cfg).shapes(cfg).values():
        size = 1
        for s in shape:
            size *= s
        n += size
    return n
