"""A configuration's architecture, found by name.

A configuration file names its architecture in its ``reference`` key
(``dense_decoder`` where it has none).  The name finds two modules:
the architecture module ``archs/<name>.py`` and the plain reference
``references/<name>.py``, each looked for beside the configuration
first (``<root>/archs/``, ``<root>/references/``, where ``<root>``
holds its ``configs/``) and then in the harness.  Everything the harness
knows about a model's shape is in the architecture module: `run.py`,
`serve.py`, `weights.py`, `flops.py` and `check.py` hold none of it, so
a configuration of a new architecture enters as new files only.

An architecture module provides:

* ``harness_keys(c)``: the configuration file `c` mapped onto the keys
  that the module and its reference read.  Every architecture gives
  ``name``, ``n_layers``, ``d_model``, ``vocab_size``, ``dtype`` and
  ``n_pages``; `run.load_config` keeps the file's other keys beside
  them.
* ``lm_config(cfg)``: the program's model configuration.
* ``layer_leaves(cfg, l)``: layer `l`'s leaves, each as (path in the
  layer's tree, shape, init std, leaf id); std 0 is a zero leaf, which
  draws nothing (its id is None).  `weights.py` draws a leaf from
  ``fold_in(fold_in(base, 1000 + l), leaf id)``.
* ``groups(cfg)``: how layers stack into the program's parameter tree,
  as (tree key, layer indices): each group is one stacked tree.
* ``prefill(cfg, n, n_recomputed, recomputed_ctx)`` and
  ``decode(cfg, position)``: the operations one request's prefill and
  one decoded token need (`flops.py` says what is counted).
"""
from __future__ import annotations

import functools
import importlib.util
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT = "dense_decoder"


@functools.lru_cache(maxsize=None)
def _load(path: str, kind: str, name: str):
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find(kind: str, name: str, root: str = HERE):
    """Module `name` of `kind` (``archs`` or ``references``): beside the
    configuration first, then in the harness."""
    for base in (root, HERE):
        path = os.path.abspath(os.path.join(base, kind, f"{name}.py"))
        if os.path.exists(path):
            return _load(path, kind, name)
    raise FileNotFoundError(f"no {kind}/{name}.py under {root} or {HERE}")


def of(cfg: dict):
    """The architecture module of a configuration (`run.load_config`)."""
    return find("archs", cfg.get("reference", DEFAULT), cfg.get("root", HERE))


def reference(cfg: dict):
    """The plain reference module of a configuration."""
    return find("references", cfg.get("reference", DEFAULT), cfg.get("root", HERE))
