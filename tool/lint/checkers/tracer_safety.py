"""JAX/tracer-safety checker (rule: tracer-safety, codes CFT0xx).

Inside a jit/pmap/pallas-traced function, Python scalar coercions and
host syncs either fail at trace time (ConcretizationTypeError) or —
worse — silently freeze a traced value into the compiled graph and
force a device round-trip on every call:

  CFT001  int()/float()/bool()/complex() applied to a traced value
  CFT002  .item() on a traced value (host sync + concretization)
  CFT003  np.asarray()/np.array() on a traced value (implicit host sync)
  CFT004  .block_until_ready() inside a traced function (host sync in
          the graph; belongs at the caller/benchmark boundary)
  CFT005  jitted function declares a static arg whose default is
          unhashable (list/dict/set) — every call that relies on the
          default dies in jit's static-argument hashing

A coercion is only flagged when its argument expression mentions a
non-static parameter of the traced function (values derived from
closure constants or static args are concrete and fine — see
ops/pallas_gf.py's `_apply_fn`, whose shapes are closure constants).

The family also covers the *distributed* tracer (`TraceClockChecker`):

  CFT006  naked time.time() in an instrumented hot-path module — span
          timing and the SLO sliding window ride the injectable clock
          (trace.set_clock / utils.retry.Clock) or time.perf_counter();
          wall-clock reads there make FakeClock-driven timing tests
          nondeterministic
"""

from __future__ import annotations

import ast

from ..core import Checker, Module, Violation

_COERCIONS = {"int", "float", "bool", "complex"}
_NUMPY_NAMES = {"np", "numpy", "onp"}
_JIT_NAMES = {"jit", "pmap", "pjit"}


def _dotted(node: ast.AST) -> str:
    """'jax.jit' for Attribute/Name chains, '' otherwise."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _jit_decoration(dec: ast.AST) -> ast.AST | None:
    """The jit-ish callable a decorator resolves to, or None.

    Matches `@jax.jit`, `@jit`, `@jax.jit(...)`, and
    `@[functools.]partial(jax.jit, ...)` — returns the Call node when
    arguments (static_argnames & co) are attached."""
    if isinstance(dec, ast.Call):
        head = _dotted(dec.func)
        if head.split(".")[-1] in _JIT_NAMES:
            return dec
        if head.split(".")[-1] == "partial" and dec.args:
            inner = _dotted(dec.args[0])
            if inner.split(".")[-1] in _JIT_NAMES:
                return dec
        return None
    if _dotted(dec).split(".")[-1] in _JIT_NAMES:
        return dec
    return None


def _static_params(fn: ast.FunctionDef, dec: ast.AST) -> set[str]:
    """Parameter names declared static via static_argnames/static_argnums."""
    statics: set[str] = set()
    if not isinstance(dec, ast.Call):
        return statics
    params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
    for kw in dec.keywords:
        if kw.arg == "static_argnames":
            for v in ast.walk(kw.value):
                if isinstance(v, ast.Constant) and isinstance(v.value, str):
                    statics.add(v.value)
        elif kw.arg == "static_argnums":
            for v in ast.walk(kw.value):
                if isinstance(v, ast.Constant) and isinstance(v.value, int):
                    if 0 <= v.value < len(params):
                        statics.add(params[v.value])
    return statics


def _param_names(fn: ast.FunctionDef) -> set[str]:
    a = fn.args
    return {p.arg for p in a.posonlyargs + a.args + a.kwonlyargs} | (
        {a.vararg.arg} if a.vararg else set()) | (
        {a.kwarg.arg} if a.kwarg else set())


def _mentions(node: ast.AST, names: set[str]) -> bool:
    return any(isinstance(n, ast.Name) and n.id in names
               for n in ast.walk(node))


_UNHASHABLE = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp,
               ast.SetComp)


class TracerSafetyChecker(Checker):
    rule = "tracer-safety"
    dirs = ("cubefs_tpu/ops/", "cubefs_tpu/codec/", "cubefs_tpu/parallel/")

    def check(self, mod: Module) -> list[Violation]:
        out: list[Violation] = []
        pallas_kernels = self._pallas_kernel_names(mod)
        for node in ast.walk(mod.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            dec = None
            for d in node.decorator_list:
                dec = _jit_decoration(d)
                if dec is not None:
                    break
            if dec is None and node.name not in pallas_kernels:
                continue
            statics = _static_params(node, dec) if dec is not None else set()
            traced = _param_names(node) - statics
            out.extend(self._check_traced_body(mod, node, traced))
            if dec is not None:
                out.extend(self._check_static_defaults(mod, node, statics))
        return out

    def _pallas_kernel_names(self, mod: Module) -> set[str]:
        """Function names passed (positionally) to pl.pallas_call: their
        bodies are traced exactly like a jitted function's."""
        names: set[str] = set()
        for node in ast.walk(mod.tree):
            if (isinstance(node, ast.Call)
                    and _dotted(node.func).split(".")[-1] == "pallas_call"
                    and node.args and isinstance(node.args[0], ast.Name)):
                names.add(node.args[0].id)
        return names

    def _check_traced_body(self, mod: Module, fn: ast.FunctionDef,
                           traced: set[str]) -> list[Violation]:
        out: list[Violation] = []
        # nested defs inherit the outer traced params (closures trace too)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if isinstance(func, ast.Name) and func.id in _COERCIONS:
                if node.args and _mentions(node.args[0], traced):
                    out.append(self.violation(
                        mod, "CFT001", node,
                        f"{func.id}() on a traced value inside "
                        f"`{fn.name}` concretizes the tracer"))
            elif isinstance(func, ast.Attribute):
                if (func.attr == "item" and not node.args
                        and _mentions(func.value, traced)):
                    out.append(self.violation(
                        mod, "CFT002", node,
                        f".item() on a traced value inside `{fn.name}` "
                        f"(host sync + concretization)"))
                elif (func.attr in ("asarray", "array")
                      and _dotted(func.value) in _NUMPY_NAMES
                      and node.args and _mentions(node.args[0], traced)):
                    out.append(self.violation(
                        mod, "CFT003", node,
                        f"np.{func.attr}() on a traced value inside "
                        f"`{fn.name}` forces a host sync; use jnp"))
                elif func.attr == "block_until_ready":
                    out.append(self.violation(
                        mod, "CFT004", node,
                        f".block_until_ready() inside traced `{fn.name}` "
                        f"(host sync belongs at the caller)"))
        return out

    def _check_static_defaults(self, mod: Module, fn: ast.FunctionDef,
                               statics: set[str]) -> list[Violation]:
        out: list[Violation] = []
        a = fn.args
        pos = a.posonlyargs + a.args
        defaults = dict(zip([p.arg for p in pos[len(pos) - len(a.defaults):]],
                            a.defaults))
        defaults.update({p.arg: d for p, d in zip(a.kwonlyargs, a.kw_defaults)
                         if d is not None})
        for name in statics:
            d = defaults.get(name)
            if d is not None and isinstance(d, _UNHASHABLE):
                out.append(self.violation(
                    mod, "CFT005", d,
                    f"static arg `{name}` of jitted `{fn.name}` has an "
                    f"unhashable default ({type(d).__name__.lower()}); "
                    f"jit's static-argument hashing will raise on every "
                    f"call that uses the default"))
        return out


class TraceClockChecker(Checker):
    """CFT006: no naked wall-clock reads in span-instrumented modules.

    These modules time spans, stages, and SLO windows; tests drive them
    with FakeClock (utils/retry.py) and seeded ids for byte-identical
    traces. A time.time() slipping in reintroduces wall-clock jitter —
    durations must come from the injected clock or time.perf_counter(),
    and wall timestamps (audit `ts` fields etc.) belong to the
    un-instrumented layers."""

    rule = "trace-clock"
    # exact instrumented hot-path modules, not whole dirs: fs/client.py
    # and fs/metanode.py legitimately stamp wall-clock mtime/ctime `ts`
    # fields, so the fence covers only the span/timing substrate and
    # the four hot paths' span-heavy modules
    dirs = (
        "cubefs_tpu/utils/trace.py",
        "cubefs_tpu/utils/slo.py",
        "cubefs_tpu/utils/metrics.py",
        "cubefs_tpu/codec/batcher.py",
        "cubefs_tpu/parallel/raft.py",
        "cubefs_tpu/blob/access.py",
        "cubefs_tpu/blob/worker.py",
    )

    def check(self, mod: Module) -> list[Violation]:
        out: list[Violation] = []
        # names resolving to the time module ("import time [as t]")
        time_mods = {alias for alias, full in mod.import_aliases.items()
                     if full == "time"}
        # names resolving to the function ("from time import time [as t]")
        bare = {name for name, full in mod.from_imports.items()
                if full == "time.time"}
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if ((isinstance(f, ast.Attribute) and f.attr == "time"
                 and isinstance(f.value, ast.Name)
                 and f.value.id in time_mods)
                    or (isinstance(f, ast.Name) and f.id in bare)):
                out.append(self.violation(
                    mod, "CFT006", node,
                    "naked time.time() in an instrumented hot path; use "
                    "the injectable clock (trace.set_clock / "
                    "utils.retry.Clock) or time.perf_counter() so "
                    "FakeClock timing tests stay deterministic"))
        return out
