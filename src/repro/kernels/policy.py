"""KernelPolicy: ONE frozen object selecting how every kernel lowers.

Before this module, backend selection was scattered: per-call
``backend="pallas"`` strings on every ``kernels.ops`` entry point plus
module-level environment sniffing (``_on_tpu()`` / ``_interpret()``)
deciding interpret mode behind the caller's back. A ``KernelPolicy``
replaces all of that with a single hashable value that rides inside
``workloads.FrameProblem`` (itself the compile-cache key of the scan
engines), so "which lowering" is part of the SAME identity that keys
jitted pipelines:

* ``backend`` -- the lowering ladder rung:
    ``jnp``    the pure-jnp oracles in ``ref.py``, fused by XLA (the
               default: the lowering that compiles for the TPU);
    ``pallas`` the Pallas kernel bodies (interpret mode off the TPU;
               on the TPU most do not lower yet at the paper's region
               sides -- ROADMAP speed item 2);
    ``tuned``  per-kernel measured selection: consult the autotune
               cache (``kernels.autotune``) for the winning
               (impl, block, unroll) at this call's static signature,
               falling back to the jnp heuristics when cold.
* ``interpret`` -- tri-state: ``None`` auto-resolves per call site
  (interpret whenever the default JAX platform is not TPU -- the old
  sniffing, now explicit and overridable), ``True``/``False`` pins it.
* ``overrides`` -- per-kernel parameter overrides (block shapes,
  unroll factors) applied LAST, over whatever the backend/tuner chose.
  Accepts a mapping ``{kernel_name: {param: value}}`` and canonicalises
  it to sorted tuples so the policy stays hashable.
* ``tuning_cache`` -- path of the JSON tuning cache the ``tuned``
  backend consults (``None``: heuristics only).

Old-style ``backend="..."`` kwargs keep working through
``resolve_policy`` (a thin shim that wraps the string and emits a
``DeprecationWarning``); new code passes ``policy=``.
"""

from __future__ import annotations

import dataclasses
import enum
import warnings
from typing import Mapping, Optional, Tuple, Union

import jax

__all__ = ["Backend", "KernelPolicy", "resolve_policy", "default_interpret",
           "DEFAULT_POLICY", "JNP_POLICY", "PALLAS_POLICY", "TUNED_POLICY",
           "KERNEL_NAMES"]

# the kernels a policy can carry overrides for (ops.py entry points)
KERNEL_NAMES = ("dwell", "perimeter_query", "region_fill", "region_dwell",
                "region_fill_pooled", "region_dwell_pooled",
                "olt_compact", "batched_ranks")


class Backend(enum.Enum):
    """The lowering ladder: jnp oracle < Pallas body < tuned selection."""

    JNP = "jnp"
    PALLAS = "pallas"
    TUNED = "tuned"

    def __str__(self) -> str:  # str(pol.backend) == the legacy string
        return self.value


def _coerce_backend(backend: Union[Backend, str]) -> Backend:
    if isinstance(backend, Backend):
        return backend
    try:
        return Backend(str(backend))
    except ValueError:
        raise ValueError(
            f"unknown backend {backend!r}; expected one of "
            f"{[b.value for b in Backend]}") from None


def _freeze_value(v):
    """Hashable canonical form of one override value (lists -> tuples)."""
    if isinstance(v, (list, tuple)):
        return tuple(_freeze_value(x) for x in v)
    return v


def _freeze_overrides(overrides) -> Tuple[Tuple[str, Tuple], ...]:
    """{kernel: {param: value}} -> sorted nested tuples (hashable)."""
    if not overrides:
        return ()
    if isinstance(overrides, tuple):  # may already be canonical; re-freeze
        overrides = {k: dict(v) for k, v in overrides}
    if not isinstance(overrides, Mapping):
        raise TypeError(
            f"overrides must be a mapping kernel -> params, got "
            f"{type(overrides).__name__}")
    out = []
    for kernel in sorted(overrides):
        if kernel not in KERNEL_NAMES:
            raise ValueError(
                f"unknown kernel {kernel!r} in overrides; known kernels: "
                f"{KERNEL_NAMES}")
        params = overrides[kernel]
        if not isinstance(params, Mapping):
            raise TypeError(
                f"overrides[{kernel!r}] must be a mapping param -> value")
        out.append((kernel, tuple(sorted(
            (str(k), _freeze_value(v)) for k, v in params.items()))))
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class KernelPolicy:
    """Frozen, hashable kernel-lowering policy (see module docstring).

    Hashability is load-bearing: the policy is a field of
    ``workloads.FrameProblem``, the compile-cache key of
    ``core.ask._PIPELINE_CACHE`` -- two problems differing only in
    policy compile (and cache) separately, which is exactly right
    because they lower differently.
    """

    backend: Backend = Backend.JNP
    interpret: Optional[bool] = None  # None: auto (not-on-TPU)
    overrides: Tuple[Tuple[str, Tuple], ...] = ()
    tuning_cache: Optional[str] = None

    def __post_init__(self):
        object.__setattr__(self, "backend", _coerce_backend(self.backend))
        if self.interpret is not None:
            object.__setattr__(self, "interpret", bool(self.interpret))
        object.__setattr__(self, "overrides",
                           _freeze_overrides(self.overrides))
        if self.tuning_cache is not None:
            object.__setattr__(self, "tuning_cache", str(self.tuning_cache))

    @classmethod
    def coerce(cls, value: Union["KernelPolicy", Backend, str]) -> "KernelPolicy":
        """A policy from a policy (pass-through) or a backend name."""
        if isinstance(value, cls):
            return value
        return cls(backend=value)

    # -- resolution helpers (all trace-time / static) -----------------------

    def resolve_interpret(self) -> bool:
        """Whether Pallas calls run in interpret mode: the explicit flag,
        else interpret everywhere but TPU (the old module-level sniff,
        now a per-policy decision)."""
        if self.interpret is not None:
            return self.interpret
        return jax.default_backend() != "tpu"

    def override_for(self, kernel: str) -> dict:
        """This policy's parameter overrides for one kernel (may be {})."""
        for name, params in self.overrides:
            if name == kernel:
                return dict(params)
        return {}

    def with_backend(self, backend: Union[Backend, str]) -> "KernelPolicy":
        """Same policy, different ladder rung."""
        return dataclasses.replace(self, backend=_coerce_backend(backend))


DEFAULT_POLICY = KernelPolicy()
JNP_POLICY = KernelPolicy(backend=Backend.JNP)
PALLAS_POLICY = KernelPolicy(backend=Backend.PALLAS)
TUNED_POLICY = KernelPolicy(backend=Backend.TUNED)


def default_interpret() -> bool:
    """Interpret-mode resolution for kernel entry points called WITHOUT a
    policy in scope (``interpret=None`` defaults on the raw kernel
    modules): the default policy's decision -- interpret everywhere but
    TPU. Kept as one function so the raw kernels and ``ops.py`` can never
    drift apart on what "auto" means."""
    return DEFAULT_POLICY.resolve_interpret()


def resolve_policy(backend=None, policy=None, *,
                   default: KernelPolicy = DEFAULT_POLICY,
                   stacklevel: int = 3) -> KernelPolicy:
    """The deprecation shim every ``kernels.ops`` entry point routes
    through: ``policy=`` wins, a legacy ``backend=`` string is wrapped
    (with a ``DeprecationWarning``), neither yields ``default``.

    Passing both is an error -- silently preferring one would make the
    migration ambiguous at exactly the call sites it matters.

    ``stacklevel`` positions the ``DeprecationWarning`` at the frame
    that actually wrote ``backend=``: the default (3) is right for the
    direct ``ops`` entry points (1 = here, 2 = the ops function, 3 = the
    caller); wrappers that add a frame between the user and the ops call
    (e.g. ``workloads.exhaustive``) resolve once themselves with a
    larger value and pass the resolved policy down, so the user sees the
    warning at THEIR ``backend=`` and it fires exactly once.
    """
    if policy is not None:
        if backend is not None:
            raise ValueError(
                "pass policy= OR the legacy backend=, not both")
        return KernelPolicy.coerce(policy)
    if backend is None:
        return default
    warnings.warn(
        "backend= strings on kernels.ops entry points are deprecated; "
        "pass policy=KernelPolicy(backend=...) (or a backend name via "
        "KernelPolicy.coerce) instead",
        DeprecationWarning, stacklevel=stacklevel)
    return KernelPolicy(backend=backend)
