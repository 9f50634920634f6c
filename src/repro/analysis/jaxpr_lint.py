"""Layer 1: abstract-trace registered kernels and lint their jaxprs.

Every kernel registered through `repro.analysis.registry` carries a
representative-shape builder.  This layer calls the builder, abstract
traces the fresh jit wrapper with ``jax.make_jaxpr`` (no device work —
only the Python body runs, exactly as it would during a production
compile), and walks the resulting ClosedJaxpr recursively (into pjit /
scan / while / cond sub-jaxprs) checking the discipline contracts the
benches otherwise only catch at runtime:

``jaxpr-dtype-drift`` (error)
    A ``convert_element_type`` to float32/float16/bfloat16 inside an
    x64 kernel.  The engine's accuracy story is float64 end-to-end
    (``jax_env.x64``); a stray f32 literal or ``np.float32`` table column
    silently halves precision for the whole downstream dataflow.

``jaxpr-host-callback`` (error)
    A callback primitive (``pure_callback`` / ``io_callback`` /
    ``debug_callback``) inside the traced body.  Callbacks force a host
    round-trip per dispatch — the exact cost the one-trace discipline
    exists to avoid.

``jaxpr-baked-const`` (error)
    A constant captured by the jaxpr bigger than ``const_bytes``
    (default 64 KiB).  Large closed-over arrays are the recompile-hazard
    class PRs 3 and 8 removed by hand: they hash into the compile cache
    key, so every new table re-traces.  Pass them as operands instead.

``jaxpr-static-unhashable`` (error)
    A declared static argument whose example value is unhashable — jit
    would raise at call time; the registry catches it at lint time.

``jaxpr-donate-cpu`` (error)
    Donated buffers declared while the active backend is ``cpu``: XLA's
    CPU backend ignores donation and jax warns per call.  Production
    wrappers must gate donation on the backend (as ``_jit_fused`` does).

``jaxpr-counter-missing`` (error)
    Tracing the *fresh* wrapper did not bump the kernel's registered
    trace counter.  Because the builder returns a wrapper with an empty
    compile cache, tracing provably re-runs the Python body — so a
    missing bump means the body lost its ``TRACE_COUNTS[...] += 1`` /
    ``count_trace(...)`` first statement and the kernel is invisible to
    the one-compile-per-shape accounting.

``jaxpr-trace-error`` (error)
    The kernel failed to abstract-trace at its own representative
    shapes — whatever the cause, the example is broken and the kernel
    is unverifiable.
"""

from __future__ import annotations

import functools
from typing import Sequence

from .findings import Finding
from .registry import TRACE_COUNTS, KernelSpec, kernel_specs

#: Float dtypes that signal precision drift inside an x64 kernel.
_DRIFT_DTYPES = ("float32", "float16", "bfloat16")


def _finding(spec: KernelSpec, rule: str, detail: str, message: str) -> Finding:
    return Finding(
        rule=rule,
        severity="error",
        path=spec.module,
        line=0,
        message=f"kernel {spec.name!r}: {message}",
        context=f"{spec.name}: {detail}",
    )


def _walk_jaxprs(closed):
    """Yield ``closed`` and every sub-ClosedJaxpr reachable through eqn
    params (pjit bodies, scan/while carries, cond branches, ...)."""
    import jax.core  # noqa: F401  (ensures jax is importable here)

    seen: set[int] = set()
    stack = [closed]
    while stack:
        cj = stack.pop()
        if id(cj) in seen:
            continue
        seen.add(id(cj))
        yield cj
        jaxpr = getattr(cj, "jaxpr", cj)
        for eqn in jaxpr.eqns:
            for val in eqn.params.values():
                for sub in _iter_closed(val):
                    stack.append(sub)


def _iter_closed(val):
    if hasattr(val, "jaxpr") and hasattr(val, "consts"):
        yield val
    elif hasattr(val, "eqns"):  # open Jaxpr — wrap-free walk
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _iter_closed(v)


def _const_nbytes(const) -> int:
    nbytes = getattr(const, "nbytes", None)
    if nbytes is not None:
        return int(nbytes)
    size = getattr(const, "size", None)
    itemsize = getattr(getattr(const, "dtype", None), "itemsize", None)
    if size is not None and itemsize is not None:
        return int(size) * int(itemsize)
    return 0


def lint_kernel(
    spec: KernelSpec, const_bytes: int = 65536
) -> list[Finding]:
    import jax

    from repro.runtime import jax_env

    findings: list[Finding] = []

    try:
        example = spec.build()
    except Exception as e:  # registry builder itself broke
        return [
            _finding(
                spec,
                "jaxpr-trace-error",
                "build",
                f"representative-shape builder raised {type(e).__name__}: {e}",
            )
        ]

    # -- static hashability: jit would raise at dispatch; catch it here.
    for key, val in example.statics.items():
        try:
            hash(val)
        except TypeError:
            findings.append(
                _finding(
                    spec,
                    "jaxpr-static-unhashable",
                    f"static {key}",
                    f"static argument {key!r} has unhashable example "
                    f"value of type {type(val).__name__} — jit static "
                    f"arguments key the compile cache and must hash",
                )
            )

    # -- donation on a backend that ignores it.
    if example.donate_argnames and jax.default_backend() == "cpu":
        findings.append(
            _finding(
                spec,
                "jaxpr-donate-cpu",
                f"donate {','.join(example.donate_argnames)}",
                f"declares donated buffers "
                f"{example.donate_argnames} while the active backend "
                f"is cpu, which ignores donation (and jax warns per "
                f"call) — gate donation on the backend",
            )
        )

    if findings:
        # unhashable statics make the trace below raise confusingly;
        # report what we know and stop.
        if any(f.rule == "jaxpr-static-unhashable" for f in findings):
            return findings

    fn = example.fn
    if example.statics:
        fn = functools.partial(fn, **dict(example.statics))

    before = TRACE_COUNTS[spec.name]
    ctx = jax_env.x64() if spec.x64 else _null_ctx()
    try:
        with ctx:
            closed = jax.make_jaxpr(fn)(*example.args)
    except Exception as e:
        findings.append(
            _finding(
                spec,
                "jaxpr-trace-error",
                "trace",
                f"abstract trace failed with {type(e).__name__}: {e}",
            )
        )
        return findings

    if TRACE_COUNTS[spec.name] <= before:
        findings.append(
            _finding(
                spec,
                "jaxpr-counter-missing",
                "counter",
                "tracing a fresh wrapper did not bump "
                f"TRACE_COUNTS[{spec.name!r}] — the jitted body must "
                "increment its registered trace counter first",
            )
        )

    drift_seen: set[str] = set()
    callback_seen: set[str] = set()
    for cj in _walk_jaxprs(closed):
        jaxpr = getattr(cj, "jaxpr", cj)
        for const in getattr(cj, "consts", ()):
            nbytes = _const_nbytes(const)
            if nbytes > const_bytes:
                shape = getattr(const, "shape", ())
                dtype = getattr(const, "dtype", "?")
                detail = f"const {shape} {dtype}"
                findings.append(
                    _finding(
                        spec,
                        "jaxpr-baked-const",
                        detail,
                        f"bakes a {nbytes}-byte constant "
                        f"(shape {shape}, {dtype}) into the jaxpr — "
                        f"closed-over arrays key the compile cache and "
                        f"re-trace per table; pass as a traced operand",
                    )
                )
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if "callback" in prim and prim not in callback_seen:
                callback_seen.add(prim)
                findings.append(
                    _finding(
                        spec,
                        "jaxpr-host-callback",
                        prim,
                        f"contains host callback primitive {prim!r} — "
                        f"a host round-trip per dispatch defeats the "
                        f"one-trace pipeline",
                    )
                )
            if spec.x64 and prim == "convert_element_type":
                new_dtype = str(eqn.params.get("new_dtype", ""))
                if new_dtype in _DRIFT_DTYPES and new_dtype not in drift_seen:
                    drift_seen.add(new_dtype)
                    findings.append(
                        _finding(
                            spec,
                            "jaxpr-dtype-drift",
                            f"convert->{new_dtype}",
                            f"converts to {new_dtype} inside an x64 "
                            f"kernel — the engine is float64 end-to-end; "
                            f"a sub-f64 cast silently halves precision "
                            f"downstream",
                        )
                    )
    return findings


class _null_ctx:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def lint_kernels(
    modules: "Sequence[str] | None" = None, const_bytes: int = 65536
) -> list[Finding]:
    """Lint every kernel registered by ``modules`` (default: the real
    kernel modules)."""
    out: list[Finding] = []
    for spec in kernel_specs(modules):
        out.extend(lint_kernel(spec, const_bytes=const_bytes))
    return out
