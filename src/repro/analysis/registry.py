"""Unified kernel registry: one trace counter, one catalogue of kernels.

Before this module existed, `core/batch.py`, `kernels/aig_sim.py`, and
`launch/system.py` each hand-rolled the same idiom — a module-level
``TRACE_COUNTS`` Counter incremented inside every jitted function body
(the increment runs only while jax *traces*, never on cached dispatch)
plus a ``trace_counts()`` snapshot helper.  The registry replaces the
three copies:

  * `TRACE_COUNTS` — the single process-wide Counter.  The kernel
    modules re-export it, so ``batch.TRACE_COUNTS["fused_suite"]`` and
    friends keep working and all counters share one namespace.
  * `register_counter(name, module)` — declares which module owns a
    counter key.  `trace_counts(module=...)` filters the snapshot to one
    module's kernels, which is exactly what the old per-module
    ``trace_counts()`` returned — the re-exported aliases keep their
    historical scope, so tests that compare whole snapshots are not
    perturbed by *other* modules' kernels tracing in between.
  * `register_kernel(name, module, build)` — additionally hands the
    static analyzer a lazy *representative-shape builder*: a zero-arg
    callable returning a `KernelExample` (a freshly made jit wrapper —
    fresh so its trace cache is empty and the counter increment provably
    runs — plus small-but-representative operands and the static
    arguments).  `repro.analysis.jaxpr_lint` abstract-traces every
    registered kernel through these builders and walks the jaxprs for
    discipline violations; no real device work happens.

The registry deliberately imports nothing from the kernel modules (they
import *it*), and `kernel_specs()` imports the default kernel modules
lazily so plain ``import repro.analysis`` stays cheap.
"""

from __future__ import annotations

import collections
import dataclasses
import importlib
import importlib.util
from typing import Any, Callable, Mapping, Sequence

#: The single per-process jit trace counter.  Kernel bodies bump
#: ``TRACE_COUNTS[<kernel name>]`` as their first traced-side statement;
#: because the Python body only runs while jax traces, the counter
#: counts *compiles*, not calls.
TRACE_COUNTS: "collections.Counter[str]" = collections.Counter()

#: counter key -> owning module (dotted name), filled by `register_counter`.
KERNEL_OWNERS: dict[str, str] = {}

#: Modules whose import registers the real kernels (each module calls
#: `register_counter` / `register_kernel` at import time).  This is also
#: the list `jaxpr_lint` walks by default.
DEFAULT_KERNEL_MODULES: tuple[str, ...] = (
    "repro.core.batch",
    "repro.kernels.aig_sim",
    "repro.kernels.cim_logic",
    "repro.launch.system",
)


def count_trace(kernel: str) -> None:
    """Bump ``kernel``'s trace counter — call this (or the equivalent
    ``TRACE_COUNTS[kernel] += 1``) as the first statement of every jitted
    function body."""
    TRACE_COUNTS[kernel] += 1


def trace_counts(module: str | None = None) -> dict[str, int]:
    """Snapshot of the jit trace counters.

    ``module=None`` returns the global view (every kernel of every
    module); a dotted module name restricts the snapshot to that module's
    registered counters — the scope the old per-module ``trace_counts``
    helpers had, preserved so whole-snapshot comparisons don't race
    against unrelated modules tracing.
    """
    if module is None:
        return dict(TRACE_COUNTS)
    return {
        k: v
        for k, v in TRACE_COUNTS.items()
        if KERNEL_OWNERS.get(k) == module
    }


@dataclasses.dataclass(frozen=True)
class KernelExample:
    """One abstract-traceable kernel instance: a callable (typically a
    *fresh* jit wrapper so tracing re-runs the Python body), positional
    example operands at representative shapes, the static (trace-time)
    keyword arguments, and any donated argument names the production
    wrapper would use."""

    fn: Callable[..., Any]
    args: tuple
    statics: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    donate_argnames: tuple[str, ...] = ()


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """A registered kernel: its counter key, owning module, and the lazy
    builder the jaxpr lint layer traces it through.

    ``x64``: trace under ``runtime.jax_env.x64()`` (the float64
    kernels' production context); integer-only kernels register with
    ``x64=False`` and are exempt from the dtype-drift rule (they carry
    no floats to drift).
    """

    name: str
    module: str
    build: Callable[[], KernelExample]
    x64: bool = True


_REGISTRY: "dict[str, KernelSpec]" = {}


def register_counter(name: str, module: str) -> None:
    """Declare ``module`` as the owner of counter key ``name`` (for the
    module-scoped `trace_counts` views).  Idempotent for the same owner;
    two modules claiming one key is a bug."""
    owner = KERNEL_OWNERS.get(name)
    if owner is not None and owner != module:
        raise ValueError(
            f"trace counter {name!r} already registered to {owner}"
        )
    KERNEL_OWNERS[name] = module


def register_kernel(
    name: str,
    module: str,
    build: Callable[[], KernelExample],
    x64: bool = True,
) -> None:
    """Register a kernel for abstract tracing (and declare its counter).

    ``build`` must be cheap to *store* (it is called only when the lint
    layer runs) and must return a `KernelExample` whose ``fn`` is a
    freshly constructed jit wrapper: a fresh wrapper has an empty trace
    cache, so tracing it provably re-runs the Python body and the
    counter-increment check cannot be satisfied by a stale cache entry.
    """
    register_counter(name, module)
    prev = _REGISTRY.get(name)
    if prev is not None and prev.module != module:
        raise ValueError(
            f"kernel {name!r} already registered by {prev.module}"
        )
    _REGISTRY[name] = KernelSpec(name=name, module=module, build=build, x64=x64)


def load_kernel_module(spec: str):
    """Import a kernel module by dotted name or by ``.py`` file path
    (file paths let the lint fixtures register seeded-violation kernels
    without living on the import path)."""
    if spec.endswith(".py"):
        mod_spec = importlib.util.spec_from_file_location(
            "_lint_fixture_" + spec.replace("/", "_").replace(".", "_"), spec
        )
        if mod_spec is None or mod_spec.loader is None:
            raise ImportError(f"cannot load kernel module from {spec}")
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod
    return importlib.import_module(spec)


def kernel_specs(
    modules: Sequence[str] | None = None,
) -> list[KernelSpec]:
    """The registered kernels of ``modules`` (default: the real kernel
    modules), importing each module first so its registrations run.

    File-path entries register under the module name they pass to
    `register_kernel`; the filter keys on that name, so a fixture file
    should use a unique module string and request it back verbatim.
    """
    mods = DEFAULT_KERNEL_MODULES if modules is None else tuple(modules)
    wanted: set[str] = set()
    for m in mods:
        before = dict(_REGISTRY)
        load_kernel_module(m)
        if m.endswith(".py"):
            # A file registers under whatever module string(s) it passes
            # to register_kernel; re-executing it replaces those entries
            # with fresh KernelSpec objects, so identity comparison
            # recovers the file's registrations on repeat loads too.
            wanted.update(
                s.module
                for k, s in _REGISTRY.items()
                if before.get(k) is not s
            )
        else:
            wanted.add(m)
    return sorted(
        (s for s in _REGISTRY.values() if s.module in wanted),
        key=lambda s: (s.module, s.name),
    )
