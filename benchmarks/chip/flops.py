"""Operations the served algorithm needs, counted by the architecture.

RcLLM's prefill (Eq. 3 selective recompute) runs layer 0 over every
prompt token and layers 1..L-1 only over the rows it recomputes; the
rest reuse cached KV.  Per request that is:

* layer 0: every prompt row's projections and MLP, and causal attention
  of each row over the rows before it;
* layers 1..L-1: the recomputed rows' projections and MLP, and their
  attention over their whole causal context (cached keys included);
* the LM head on the last prompt row (the first token);
* each decoded token through every layer, attending to the whole
  sequence before it, and the LM head.

A multiply-add is two operations.  Norms, RoPE, softmax and the Eq. 3
scoring are left out: they are elementwise and small beside the above.

What a row costs in a layer is the architecture's: each architecture
module (`archs`) counts ``prefill(cfg, n, n_recomputed,
recomputed_ctx)`` and ``decode(cfg, position)`` from its own widths.
``flops.<count>(cfg, ...)`` is the configuration's architecture's
``<count>(cfg, ...)``.
"""
from __future__ import annotations

import archs


def __getattr__(name: str):
    if name.startswith("__"):
        raise AttributeError(name)

    def count(cfg: dict, *args) -> int:
        return getattr(archs.of(cfg), name)(cfg, *args)

    count.__name__ = name
    return count
