"""Typed public serving API — the one front door to the serving stack.

Every serving entry point (``launch/serve.py``, the asyncio session
server, the cluster engine, the benchmarks) historically re-listed the
same ~15 knobs as positional/keyword arguments threaded through three
layers (``serve.py -> ClusterEngine -> JaxEngineBackend ->
BatchEngine``), so adding one knob was a five-file diff and invalid
combinations surfaced as deep crashes.  This module replaces that relay
with one validated dataclass plus the frozen request/response types the
session server speaks:

* `ServeConfig` — every engine/scheduler/backend/kernel/reuse knob in
  one frozen dataclass, validated at construction (an invalid combo
  like ``decode_kernel="paged"`` with ``engine="sim"`` raises
  immediately with a message naming both knobs, instead of failing five
  layers down).  `ServeConfig.from_args` maps the legacy ``serve.py``
  flag namespace into the dataclass — the deprecation shim that keeps
  old invocations working.

* `SubmitRequest` / `StreamEvent` / `Completion` — the typed session
  protocol: a client submits a frozen request (prompt tokens, token
  budget, stop sequences, sampling params) and consumes an async
  iterator of `StreamEvent`s ending in exactly one ``finished`` event;
  `Completion` is the materialized terminal view.

* `SamplingParams` / `sample_token` — per-sequence sampling with an
  explicit PRNG seed.  ``temperature == 0`` is greedy argmax (the
  parity-test mode: every scheduler/backend/reuse combination decodes
  bitwise-identical tokens); ``temperature > 0`` draws from the
  (optionally top-k truncated) softmax using a per-request
  ``numpy`` Generator, so a (seed, prompt) pair replays exactly.

* `build_engine` / `build_backend` / `build_batcher` — the sliced
  views: each consumes exactly the `ServeConfig` fields its layer needs,
  so the per-knob keyword plumbing between layers is gone.
"""

from __future__ import annotations

import dataclasses
import warnings
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from repro.configs.base import ATTN_BACKENDS, DECODE_KERNELS

ENGINES = ("sim", "jax")
MODES = ("rcllm", "prefix", "full")
SCHEDS = ("wave", "chunked")
FINISH_REASONS = ("length", "stop", "cancelled", "rejected")


# --------------------------------------------------------------- config
@dataclass(frozen=True)
class MeshConfig:
    """The typed sharding surface: how the serving stack maps onto a real
    ``jax.sharding.Mesh``.

    ``tp`` shards attention heads / MLP hidden / the KV arena's kv-head
    axis over the mesh's ``model`` axis (Megatron-style tensor
    parallelism — GSPMD inserts the all-reduces); ``dp`` sizes the
    ``data`` axis (replica sets — serving arrays are replicated over it).
    ``mesh_shape=None`` derives the shape from ``tp``/``dp``; an explicit
    shape (``--config mesh.mesh_shape=2x4``) must agree with any
    explicitly-set ``tp``/``dp`` and fills them in otherwise.  The
    default ``MeshConfig()`` is *disabled*: the stack runs exactly as
    before, on the default device, with no mesh anywhere.  ``tp=1`` with
    ``mesh_shape=(1, 1)`` is the enabled-but-single-device mesh the
    bitwise parity tests pin (tokens identical to the unsharded path).
    """

    tp: int = 1
    dp: int = 1
    mesh_shape: Optional[Tuple[int, ...]] = None
    axis_names: Tuple[str, ...] = ("data", "model")

    def __post_init__(self):
        def bad(msg: str):
            raise ValueError(f"invalid MeshConfig: {msg}")

        if self.mesh_shape is not None:
            object.__setattr__(self, "mesh_shape", tuple(self.mesh_shape))
        object.__setattr__(self, "axis_names", tuple(self.axis_names))
        if self.tp < 1 or self.dp < 1:
            bad(f"tp={self.tp}/dp={self.dp} must be >= 1")
        names = self.axis_names
        if (
            not names
            or len(set(names)) != len(names)
            or not all(isinstance(a, str) and a for a in names)
        ):
            bad(f"axis_names={names!r} must be distinct non-empty strings")
        if "model" not in names:
            bad(
                f"axis_names={names!r} must include 'model' "
                "(the tensor-parallel axis every PartitionSpec names)"
            )
        if self.mesh_shape is None:
            if names != ("data", "model"):
                bad(
                    f"axis_names={names!r} needs an explicit mesh_shape "
                    "(only the default ('data', 'model') layout can be "
                    "derived from tp/dp)"
                )
            return
        shape = self.mesh_shape
        if len(shape) != len(names):
            bad(
                f"mesh_shape={shape} has {len(shape)} dims but "
                f"axis_names={names!r} has {len(names)}"
            )
        if any(int(s) < 1 for s in shape):
            bad(f"mesh_shape={shape} dims must be >= 1")
        shape = tuple(int(s) for s in shape)
        object.__setattr__(self, "mesh_shape", shape)
        derived_tp = shape[names.index("model")]
        derived_dp = 1
        for name, size in zip(names, shape):
            if name != "model":
                derived_dp *= size
        if self.tp not in (1, derived_tp):
            bad(
                f"mesh_shape={shape} puts {derived_tp} devices on the "
                f"model axis but tp={self.tp}: drop one of the two knobs "
                "or make them agree"
            )
        if self.dp not in (1, derived_dp):
            bad(
                f"mesh_shape={shape} puts {derived_dp} devices on the "
                f"data axes but dp={self.dp}: drop one of the two knobs "
                "or make them agree"
            )
        object.__setattr__(self, "tp", derived_tp)
        object.__setattr__(self, "dp", derived_dp)

    @property
    def enabled(self) -> bool:
        """Does this config ask for a mesh at all?  The default
        ``MeshConfig()`` is disabled — everything runs unsharded on the
        default device, byte-identical to the pre-mesh stack."""
        return self.mesh_shape is not None or self.tp > 1 or self.dp > 1

    @property
    def resolved_shape(self) -> Tuple[int, ...]:
        if self.mesh_shape is not None:
            return self.mesh_shape
        return (self.dp, self.tp)

    @property
    def n_devices(self) -> int:
        n = 1
        for s in self.resolved_shape:
            n *= s
        return n

    def build(self):
        """The real ``jax.sharding.Mesh``, or None when disabled.

        Raises the `launch.mesh` explicit-shape error when the host has
        fewer devices than the shape needs (on CPU, export
        ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before
        the first jax import to force host devices)."""
        if not self.enabled:
            return None
        from repro.launch.mesh import make_production_mesh

        return make_production_mesh(
            shape=self.resolved_shape, axis_names=self.axis_names
        )


@dataclass(frozen=True)
class DisaggConfig:
    """The typed disaggregation surface: how many cluster workers serve
    each role, and how migration routing trades affinity against bytes.

    ``prefill_workers`` / ``decode_workers`` split the cluster's ``k``
    workers into role-typed halves: prefill workers admit and prefill
    (sampling each request's first token), then hand the finished — or
    chunk-partial — KV to a decode worker over the block-store
    transport; decode workers never admit.  The default
    ``DisaggConfig()`` is *disabled*: every worker is ``unified`` and
    the stack runs byte-for-byte as before.  ``mig_gamma`` weights the
    migration-byte term added to the Eq. 2 affinity score when choosing
    the decode worker (a candidate already holding the request's store
    blocks by digest moves fewer bytes and scores higher).
    """

    prefill_workers: int = 0
    decode_workers: int = 0
    mig_gamma: float = 0.25

    def __post_init__(self):
        def bad(msg: str):
            raise ValueError(f"invalid DisaggConfig: {msg}")

        if self.prefill_workers < 0 or self.decode_workers < 0:
            bad(
                f"prefill_workers={self.prefill_workers}/"
                f"decode_workers={self.decode_workers} must be >= 0"
            )
        if (self.prefill_workers > 0) != (self.decode_workers > 0):
            bad(
                f"prefill_workers={self.prefill_workers} and "
                f"decode_workers={self.decode_workers}: both roles need "
                "at least one worker (0/0 disables disaggregation)"
            )
        if self.mig_gamma < 0:
            bad(f"mig_gamma={self.mig_gamma} must be >= 0")

    @property
    def enabled(self) -> bool:
        """Does this config split roles at all?  The default
        ``DisaggConfig()`` is disabled — every worker is unified and
        every existing flow is preserved byte-for-byte."""
        return self.prefill_workers > 0

    @property
    def n_workers(self) -> int:
        return self.prefill_workers + self.decode_workers

    def role_of(self, wid: int) -> str:
        """Worker role by cluster index: the first ``prefill_workers``
        ids prefill, the rest decode; 'unified' when disabled."""
        if not self.enabled:
            return "unified"
        return "prefill" if wid < self.prefill_workers else "decode"


@dataclass(frozen=True)
class StoreConfig:
    """The typed tiered-store surface: how the shared block store holds
    its payload bytes and what happens to evicted blocks.

    ``kv_store_dtype='int8'`` quantizes user/item block payloads to
    symmetric per-(row, kv-head)-scaled int8 (~4x more catalog blocks
    per host byte; dequantized on assembly, accuracy-gated).
    ``spill_mb`` bounds a host-RAM spill tier that device-tier evictions
    demote to instead of dropping; 0 keeps the legacy drop-on-evict.
    ``prefetch_pages_per_tick`` budgets background promotion of
    router-hinted spill blocks back to device pages, per chunked tick
    (0 disables prefetch — spill hits then promote at insert time).
    The default ``StoreConfig()`` is *disabled*: fp32 payloads,
    drop-on-evict, no prefetch — byte-for-byte the pre-tier store.
    """

    kv_store_dtype: str = "fp32"
    spill_mb: int = 0
    prefetch_pages_per_tick: int = 0

    def __post_init__(self):
        def bad(msg: str):
            raise ValueError(f"invalid StoreConfig: {msg}")

        if self.kv_store_dtype not in ("fp32", "int8"):
            bad(
                f"kv_store_dtype={self.kv_store_dtype!r} not in "
                "('fp32', 'int8')"
            )
        if self.spill_mb < 0:
            bad(f"spill_mb={self.spill_mb} must be >= 0")
        if self.prefetch_pages_per_tick < 0:
            bad(
                f"prefetch_pages_per_tick={self.prefetch_pages_per_tick} "
                "must be >= 0"
            )
        if self.prefetch_pages_per_tick > 0 and self.spill_mb == 0:
            bad(
                f"prefetch_pages_per_tick={self.prefetch_pages_per_tick} "
                "needs spill_mb > 0 (there is no spill tier to prefetch "
                "from)"
            )

    @property
    def enabled(self) -> bool:
        """Does this config change the store at all?  The default
        ``StoreConfig()`` is disabled — fp32 payloads and drop-on-evict,
        preserving every existing bitwise invariant."""
        return self.kv_store_dtype != "fp32" or self.spill_mb > 0


@dataclass(frozen=True)
class ServeConfig:
    """Every serving knob, validated once, threaded everywhere.

    The fields mirror the historical ``launch/serve.py`` flags; see
    `from_args` for the exact mapping.  ``step_tokens=None`` resolves to
    ``max(4 * chunk_tokens, 512)`` (the chunked scheduler's default
    budget) via `resolved_step_tokens`.
    """

    engine: str = "jax"
    k: int = 1
    mode: str = "rcllm"
    policy: str = "affinity"
    sched: str = "wave"
    attn_backend: str = "jnp"
    decode_kernel: str = "auto"
    kv_reuse: bool = False
    chunk_tokens: int = 128
    step_tokens: Optional[int] = None
    max_batch_tokens: int = 4096
    max_decode_batch: int = 64
    page_size: int = 16
    n_pages: int = 512
    decode_steps: int = 4
    r_item: float = 0.3
    r_rev: float = 0.3
    mesh: MeshConfig = field(default_factory=MeshConfig)
    disagg: DisaggConfig = field(default_factory=DisaggConfig)
    store: StoreConfig = field(default_factory=StoreConfig)

    def __post_init__(self):
        def bad(msg: str):
            raise ValueError(f"invalid ServeConfig: {msg}")

        for name, val, choices in (
            ("engine", self.engine, ENGINES),
            ("mode", self.mode, MODES),
            ("sched", self.sched, SCHEDS),
            ("attn_backend", self.attn_backend, ATTN_BACKENDS),
            ("decode_kernel", self.decode_kernel, DECODE_KERNELS),
        ):
            if val not in choices:
                bad(f"{name}={val!r} not in {choices}")
        if self.engine == "sim":
            # the analytic simulator has no attention, no pool and no
            # chunk-resumable prefill: any real-engine knob is a
            # configuration error, caught here rather than five layers in
            if self.decode_kernel != "auto":
                bad(
                    f"decode_kernel={self.decode_kernel!r} needs engine='jax' "
                    "(the simulator has no decode kernel)"
                )
            if self.attn_backend != "jnp":
                bad(
                    f"attn_backend={self.attn_backend!r} needs engine='jax' "
                    "(the simulator runs no attention)"
                )
            if self.kv_reuse:
                bad("kv_reuse=True needs engine='jax' (no pool to share)")
            if self.sched == "chunked":
                bad("sched='chunked' needs engine='jax' (the simulator is wave-only)")
        else:
            if self.mode == "prefix":
                bad(
                    "mode='prefix' is a simulator-only baseline; "
                    "engine='jax' supports mode in ('rcllm', 'full')"
                )
        if self.kv_reuse and self.mode != "rcllm":
            bad(
                f"kv_reuse=True needs mode='rcllm' (the shared block store "
                f"holds beyond-prefix blocks), got mode={self.mode!r}"
            )
        if self.sched == "chunked" and self.mode != "rcllm":
            bad(
                "sched='chunked' drives the beyond-prefix selective prefill; "
                f"mode={self.mode!r} has no chunk-resumable path"
            )
        if self.k < 1:
            bad(f"k={self.k} must be >= 1")
        if self.chunk_tokens < 1:
            bad(f"chunk_tokens={self.chunk_tokens} must be >= 1")
        if self.step_tokens is not None and self.step_tokens < 1:
            bad(f"step_tokens={self.step_tokens} must be >= 1 (or None)")
        if self.page_size < 1 or self.n_pages < 2:
            bad(
                f"page_size={self.page_size} must be >= 1 and "
                f"n_pages={self.n_pages} >= 2 (page 0 is the scratch page)"
            )
        if self.decode_steps < 1:
            bad(f"decode_steps={self.decode_steps} must be >= 1")
        if not (0.0 <= self.r_item <= 1.0 and 0.0 <= self.r_rev <= 1.0):
            bad(f"r_item={self.r_item}/r_rev={self.r_rev} must be in [0, 1]")
        if not isinstance(self.mesh, MeshConfig):
            bad(f"mesh must be a MeshConfig, got {type(self.mesh).__name__}")
        if self.mesh.enabled and self.engine != "jax":
            bad(
                f"mesh.tp={self.mesh.tp}/mesh.dp={self.mesh.dp} needs "
                f"engine='jax' (engine={self.engine!r} runs no devices)"
            )
        if not isinstance(self.disagg, DisaggConfig):
            bad(
                f"disagg must be a DisaggConfig, got "
                f"{type(self.disagg).__name__}"
            )
        if self.disagg.enabled:
            if self.engine != "jax":
                bad(
                    f"disagg.prefill_workers={self.disagg.prefill_workers} "
                    f"needs engine='jax' (engine={self.engine!r} has no KV "
                    "to migrate)"
                )
            if self.k != self.disagg.n_workers:
                bad(
                    f"k={self.k} must equal disagg.prefill_workers + "
                    f"disagg.decode_workers = {self.disagg.n_workers} "
                    "(every cluster worker gets exactly one role)"
                )
        if not isinstance(self.store, StoreConfig):
            bad(
                f"store must be a StoreConfig, got "
                f"{type(self.store).__name__}"
            )
        if self.store.enabled:
            if self.engine != "jax":
                bad(
                    f"store.kv_store_dtype={self.store.kv_store_dtype!r}/"
                    f"store.spill_mb={self.store.spill_mb} needs "
                    f"engine='jax' (engine={self.engine!r} has no block "
                    "store)"
                )
            if not self.kv_reuse:
                bad(
                    "store tiering configures the shared block store: "
                    "set kv_reuse=on (the default store config is a "
                    "no-op without it)"
                )
        if self.mesh.tp > 1:
            # the Mosaic/Pallas kernels are single-device programs: under
            # tensor parallelism GSPMD partitions the jnp reference paths
            # instead (decode_kernel='auto' resolves to the gather oracle,
            # see `apply_to`) until sharded kernels land
            if self.attn_backend == "pallas":
                bad(
                    f"attn_backend='pallas' with mesh.tp={self.mesh.tp}: "
                    "the Pallas kernels are single-device; tensor "
                    "parallelism needs attn_backend='jnp'"
                )
            if self.decode_kernel == "paged":
                bad(
                    f"decode_kernel='paged' with mesh.tp={self.mesh.tp}: "
                    "the fused paged kernel is single-device; use "
                    "decode_kernel='auto' (resolves to the jnp gather "
                    "oracle under tp>1)"
                )

    @property
    def resolved_step_tokens(self) -> int:
        if self.step_tokens is not None:
            return self.step_tokens
        return max(4 * self.chunk_tokens, 512)

    def replace(self, **kw) -> "ServeConfig":
        """A modified copy, re-validated."""
        return dataclasses.replace(self, **kw)

    def apply_to(self, lm_cfg):
        """Slice the model-execution knobs onto an `LMConfig`.

        Under ``mesh.tp > 1`` a ``decode_kernel='auto'`` resolves to the
        jnp gather oracle explicitly (the paged Pallas kernel is
        single-device), so the engine never has to re-derive the routing
        from the mesh."""
        decode_kernel = self.decode_kernel
        if self.mesh.tp > 1 and decode_kernel == "auto":
            decode_kernel = "gather"
        return dataclasses.replace(
            lm_cfg,
            attn_backend=self.attn_backend,
            decode_kernel=decode_kernel,
        )

    # ------------------------- legacy flag shim -------------------------
    #: ``argparse`` attribute -> ServeConfig field for the historical
    #: per-knob ``launch/serve.py`` flags (`--pages` became ``n_pages``;
    #: ``--kv-reuse off|on`` becomes the bool).
    LEGACY_FLAGS = {
        "engine": "engine",
        "k": "k",
        "mode": "mode",
        "policy": "policy",
        "sched": "sched",
        "attn_backend": "attn_backend",
        "decode_kernel": "decode_kernel",
        "kv_reuse": "kv_reuse",
        "chunk_tokens": "chunk_tokens",
        "step_tokens": "step_tokens",
        "max_batch_tokens": "max_batch_tokens",
        "page_size": "page_size",
        "pages": "n_pages",
        "decode_steps": "decode_steps",
        "r_item": "r_item",
        "r_rev": "r_rev",
    }

    @classmethod
    def from_args(
        cls, args, base: Optional["ServeConfig"] = None, warn: bool = True
    ) -> "ServeConfig":
        """Map a legacy ``serve.py`` argparse namespace into a config.

        Only attributes that are present *and not None* override — the
        launcher declares every legacy flag with ``default=None`` so a
        flag the user never typed falls through to `base` (or the
        dataclass default).  When any legacy flag was typed, one
        `DeprecationWarning` names them all (a single warning path, not
        one per flag).
        """
        overrides: Dict[str, object] = {}
        used = []
        for attr, fld in cls.LEGACY_FLAGS.items():
            val = getattr(args, attr, None)
            if val is None:
                continue
            if fld == "kv_reuse" and isinstance(val, str):
                val = val == "on"
            overrides[fld] = val
            used.append(f"--{attr.replace('_', '-')} -> {fld}={render_value(val)}")
        if used and warn:
            warnings.warn(
                f"per-knob serve flags are deprecated; pass --config "
                f"{','.join(f'{f}={render_value(v)}' for f, v in overrides.items())}"
                f" instead ({'; '.join(used)})",
                DeprecationWarning,
                stacklevel=2,
            )
        base = base if base is not None else cls()
        return base.replace(**overrides) if overrides else base

    @classmethod
    def parse(cls, spec: str, base: Optional["ServeConfig"] = None) -> "ServeConfig":
        """Build a config from a compact ``key=value,key=value`` string —
        the launcher's new-style ``--config`` flag.  Values are coerced
        by the field's declared type; booleans accept on/off/true/false.
        Sub-config fields nest with a dot (``mesh.tp=4``,
        ``mesh.mesh_shape=2x4``, ``mesh.axis_names=data+model``,
        ``disagg.prefill_workers=2``, ``store.spill_mb=64``); the
        grammar is total — `render` emits a string this method parses
        back to an equal config.
        """
        base = base if base is not None else cls()
        if not spec.strip():
            return base
        fields = {f.name: f for f in dataclasses.fields(cls)}
        subs = {"mesh": MeshConfig, "disagg": DisaggConfig, "store": StoreConfig}
        sub_fields = {
            name: {f.name: f for f in dataclasses.fields(t)}
            for name, t in subs.items()
        }
        overrides: Dict[str, object] = {}
        sub_overrides: Dict[str, Dict[str, object]] = {n: {} for n in subs}
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            if "=" not in part:
                raise ValueError(f"--config entry {part!r} is not key=value")
            key, val = part.split("=", 1)
            key = key.strip()
            prefix = key.split(".", 1)[0]
            if "." in key and prefix in subs:
                sub = key[len(prefix) + 1 :]
                flds = sub_fields[prefix]
                if sub not in flds:
                    raise ValueError(
                        f"--config key {key!r} is not a "
                        f"{subs[prefix].__name__} field (choose from "
                        f"{sorted(prefix + '.' + f for f in flds)})"
                    )
                sub_overrides[prefix][sub] = _coerce(flds[sub], val.strip())
                continue
            if key in subs:
                examples = {
                    "mesh": "mesh.tp=4, mesh.dp=2, mesh.mesh_shape=2x4, "
                    "mesh.axis_names=data+model",
                    "disagg": "disagg.prefill_workers=2, "
                    "disagg.decode_workers=2, disagg.mig_gamma=0.25",
                    "store": "store.kv_store_dtype=int8, store.spill_mb=64, "
                    "store.prefetch_pages_per_tick=8",
                }
                raise ValueError(
                    f"--config {key} is a sub-config: set its fields as "
                    f"{examples[key]}"
                )
            if key not in fields:
                raise ValueError(
                    f"--config key {key!r} is not a ServeConfig field "
                    f"(choose from {sorted(fields)})"
                )
            overrides[key] = _coerce(fields[key], val.strip())
        for name, ov in sub_overrides.items():
            if ov:
                overrides[name] = dataclasses.replace(
                    getattr(base, name), **ov
                )
        return base.replace(**overrides) if overrides else base

    def render(self) -> str:
        """The ``--config`` string reproducing this config exactly:
        ``ServeConfig.parse(cfg.render()) == cfg`` for every valid
        config (the round-trip the grammar tests pin)."""
        parts = []
        subs = {"mesh": MeshConfig, "disagg": DisaggConfig, "store": StoreConfig}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if f.name in subs:
                for mf in dataclasses.fields(subs[f.name]):
                    parts.append(
                        f"{f.name}.{mf.name}={render_value(getattr(v, mf.name))}"
                    )
            else:
                parts.append(f"{f.name}={render_value(v)}")
        return ",".join(parts)


def render_value(v) -> str:
    """One value in the ``--config`` grammar (`_coerce`'s inverse):
    booleans as on/off, None as none, int tuples ``x``-joined (mesh
    shapes, ``2x4``), string tuples ``+``-joined (axis names,
    ``data+model``)."""
    if isinstance(v, bool):
        return "on" if v else "off"
    if v is None:
        return "none"
    if isinstance(v, tuple):
        if all(isinstance(x, int) for x in v):
            return "x".join(str(x) for x in v)
        return "+".join(str(x) for x in v)
    return str(v)


def _coerce(fld: dataclasses.Field, val: str):
    t = fld.type
    if "Tuple" in t:
        if val.lower() == "none" and "Optional" in t:
            return None
        if "int" in t:
            try:
                return tuple(int(x) for x in val.split("x"))
            except ValueError:
                raise ValueError(
                    f"--config {fld.name}={val!r}: expected an "
                    "'x'-separated int tuple like 2x4"
                ) from None
        return tuple(s for s in val.split("+") if s)
    if "bool" in t:
        low = val.lower()
        if low in ("on", "true", "1", "yes"):
            return True
        if low in ("off", "false", "0", "no"):
            return False
        raise ValueError(f"--config {fld.name}={val!r}: expected on/off")
    if val.lower() == "none":
        return None
    if "int" in t:
        return int(val)
    if "float" in t:
        return float(val)
    return val


# ------------------------------------------------------------- sampling
@dataclass(frozen=True)
class SamplingParams:
    """Per-sequence sampling.  ``temperature == 0`` is greedy argmax —
    the default, and the mode every bitwise parity test pins.  With
    ``temperature > 0`` the token is drawn from the softmax of
    ``logits / temperature`` (optionally truncated to the ``top_k``
    highest logits) using a per-request PRNG seeded with ``seed``, so
    one (seed, prompt) pair replays the exact same stream."""

    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError(f"temperature={self.temperature} must be >= 0")
        if self.top_k < 0:
            raise ValueError(f"top_k={self.top_k} must be >= 0")

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


GREEDY = SamplingParams()


def sample_token(
    logits: np.ndarray,
    params: SamplingParams = GREEDY,
    rng: Optional[np.random.Generator] = None,
) -> int:
    """One token from one row of logits under `params`."""
    logits = np.asarray(logits, np.float64)
    if params.greedy or rng is None:
        return int(np.argmax(logits))
    z = logits / params.temperature
    if params.top_k and params.top_k < len(z):
        kth = np.partition(z, -params.top_k)[-params.top_k]
        z = np.where(z >= kth, z, -np.inf)
    z = z - np.max(z)
    p = np.exp(z)
    p /= p.sum()
    return int(rng.choice(len(p), p=p))


def match_stop(generated: Sequence[int], stops: Sequence[Tuple[int, ...]]) -> bool:
    """Does the generated stream end with any stop sequence?"""
    for s in stops:
        n = len(s)
        if n and len(generated) >= n and tuple(generated[-n:]) == tuple(s):
            return True
    return False


# ------------------------------------------------------ session protocol
@dataclass(frozen=True)
class SubmitRequest:
    """One client request to the session server.

    ``tokens`` is the prompt (int32 ids).  ``max_tokens`` bounds the
    generated stream (prefill's first token included); ``stop`` is a
    tuple of token-id sequences — generation ends the moment the stream
    *ends with* one of them (the matching tokens are kept, vLLM-style
    inclusive semantics for token-id stops).  ``context`` carries the
    rcllm assembly payload — ``(plan, cached_k, cached_v, have)`` — and
    ``reuse`` the cross-request block metadata; both are None for
    mode='full' prompts.
    """

    rid: int
    tokens: np.ndarray
    max_tokens: int = 4
    stop: Tuple[Tuple[int, ...], ...] = ()
    sampling: SamplingParams = GREEDY
    context: Optional[tuple] = field(default=None, repr=False)
    reuse: Optional[object] = field(default=None, repr=False)

    def __post_init__(self):
        if self.max_tokens < 1:
            raise ValueError(f"max_tokens={self.max_tokens} must be >= 1")
        if any(len(s) == 0 for s in self.stop):
            raise ValueError("empty stop sequence")


@dataclass(frozen=True)
class StreamEvent:
    """One element of a session's event stream.  Exactly one event per
    stream has ``finished=True`` (its ``token`` may still carry the
    final sampled id); ``reason`` is then one of `FINISH_REASONS`."""

    rid: int
    index: int  # 0-based position in the generated stream
    token: Optional[int]
    t_s: float  # server wall clock (seconds since server start)
    finished: bool = False
    reason: Optional[str] = None


@dataclass(frozen=True)
class Completion:
    """Terminal view of one session: every generated token plus the
    latency split the closed-loop runner reports."""

    rid: int
    tokens: Tuple[int, ...]
    reason: str
    submitted_s: float
    first_token_s: Optional[float]
    done_s: float

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_s is None:
            return None
        return self.first_token_s - self.submitted_s


# ------------------------------------------------------- sliced builders
def build_engine(params, lm_cfg, config: ServeConfig, pool=None, sel=None,
                 device=None):
    """`BatchEngine` from the config's engine/pool/reuse/mesh slice.  The
    returned engine's `cfg` carries the attention backend and decode
    kernel; `pool`/`sel` override only when a caller needs a bespoke
    pool (tests) or selective budget.  `device` (without a mesh) puts the
    engine's params, KV arena and block store on that one device — a
    cluster worker's own chip; None leaves them on the default device.

    With ``config.mesh`` enabled this is the one place the mesh becomes
    physical: the param tree is placed by the `sharding.specs`
    PartitionSpec trees and the paged KV arena is sharded over the
    mesh's model axis — the jitted prefill/decode steps are unchanged
    (GSPMD propagates the shardings and inserts the collectives)."""
    from repro.core import engine as ENG
    from repro.serving.batch_engine import BatchEngine
    from repro.serving.block_store import SharedBlockStore
    from repro.serving.kv_pool import pool_for

    cfg = config.apply_to(lm_cfg)
    mesh = config.mesh.build()
    if mesh is not None:
        if device is not None:
            raise ValueError("build_engine: pass a mesh or a device, not both")
        from repro.sharding.specs import shard_lm_params

        params = shard_lm_params(params, cfg, mesh)
    elif device is not None:
        import jax

        params = jax.device_put(params, device)
    if pool is None:
        pool = pool_for(
            cfg,
            page_size=config.page_size,
            n_pages=config.n_pages,
            mesh=mesh,
            device=device,
        )
    if sel is None:
        sel = ENG.SelectiveConfig(r_item=config.r_item, r_rev=config.r_rev)
    return BatchEngine(
        params,
        cfg,
        pool=pool,
        sel=sel,
        store=SharedBlockStore(
            pool,
            kv_store_dtype=config.store.kv_store_dtype,
            spill_mb=config.store.spill_mb,
            prefetch_pages_per_tick=config.store.prefetch_pages_per_tick,
        )
        if config.kv_reuse
        else None,
        chunk_tokens=config.chunk_tokens,
        mesh=mesh,
    )


def build_backend(engine, config: ServeConfig, plans=None, reuse=None):
    """`JaxEngineBackend` over a built engine (mode slice)."""
    from repro.serving.batching import JaxEngineBackend

    return JaxEngineBackend(engine, mode=config.mode, plans=plans, reuse=reuse)


def build_batcher(backend, config: ServeConfig):
    """`ContinuousBatcher` over a backend (scheduler slice)."""
    from repro.serving.batching import ContinuousBatcher

    return ContinuousBatcher(
        backend=backend,
        max_batch_tokens=config.max_batch_tokens,
        max_decode_batch=config.max_decode_batch,
        sched=config.sched,
        chunk_tokens=config.chunk_tokens,
        step_tokens=config.step_tokens,
    )
