"""Pallas kernel constraints: grid/index-map arity, traced control flow,
dtype/host-callback bans inside kernel bodies."""

from __future__ import annotations

import ast

from .core import Finding, ModuleCtx, Rule, call_name, register

_HOST_CALLS = {"print", "io_callback", "pure_callback", "host_callback",
               "debug_callback", "breakpoint"}


def _first_kernel_ref(call: ast.Call) -> str | None:
    """The kernel function a pallas_call launches: a bare Name, or the
    first argument of functools.partial(Name, ...)."""
    if not call.args:
        return None
    a = call.args[0]
    if isinstance(a, ast.Name):
        return a.id
    if isinstance(a, ast.Call) and call_name(a) == "partial" and a.args \
            and isinstance(a.args[0], ast.Name):
        return a.args[0].id
    return None


_GRID_SPECS = {"GridSpec", "PrefetchScalarGridSpec"}


def _grid_arity(call: ast.Call, local_tuples: dict[str, int]) -> int | None:
    """Index-map arity a pallas_call or grid spec demands: the grid's rank
    plus ``num_scalar_prefetch`` (Pallas appends one ref per prefetched
    scalar operand).  None when the grid or the prefetch count is not a
    literal the checker can read."""
    rank = prefetch = None
    for kw in call.keywords:
        if kw.arg == "grid":
            if isinstance(kw.value, ast.Tuple):
                rank = len(kw.value.elts)
            elif isinstance(kw.value, ast.Name):
                rank = local_tuples.get(kw.value.id)
        elif kw.arg == "num_scalar_prefetch":
            prefetch = (kw.value.value
                        if isinstance(kw.value, ast.Constant)
                        and isinstance(kw.value.value, int) else -1)
    if rank is None or prefetch == -1:
        return None
    return rank + (prefetch or 0)


def _iter_blockspecs(call: ast.Call):
    """Every BlockSpec(...) call reachable from in_specs/out_specs/
    out_shape keyword values."""
    for kw in call.keywords:
        if kw.arg not in ("in_specs", "out_specs"):
            continue
        for sub in ast.walk(kw.value):
            if isinstance(sub, ast.Call) and call_name(sub) == "BlockSpec":
                yield sub


def _index_map_lambda(spec: ast.Call) -> ast.Lambda | None:
    for kw in spec.keywords:
        if kw.arg == "index_map" and isinstance(kw.value, ast.Lambda):
            return kw.value
    for a in spec.args:
        if isinstance(a, ast.Lambda):
            return a
    return None


@register
class KernelConstraintsRule(Rule):
    name = "kernel-constraints"
    summary = ("Pallas kernels: index-map arity == grid rank, no Python "
               "control flow / float64 / host callbacks in kernel bodies")
    doc = """\
Invariant, three parts, applied to any module that defines `*_kernel`
functions or issues pl.pallas_call:

1. Every BlockSpec index_map lambda takes exactly len(grid) parameters,
   plus num_scalar_prefetch when the specs sit in a
   PrefetchScalarGridSpec.  Pallas hands the index map one program id per
   grid axis, then one ref per scalar-prefetch operand; an arity
   mismatch is a TypeError at trace time on TPU but can silently slip
   through on interpret-mode-only CI runs when the call path is not
   exercised.

2. Kernel bodies contain no Python `if`/`while`, and `for` only over
   range(...) with static bounds.  Kernel bodies run once at trace time:
   branching on a traced value raises ConcretizationTypeError at best;
   at worst a condition on a *static-looking* value bakes one branch into
   the compiled kernel.  Data-dependent selection uses @pl.when /
   jnp.where; static unrolling threads Python ints via functools.partial
   (how cp_count/mask_agg pass num-block counts).

3. No float64 and no host callbacks (print, io/pure/host/debug_callback)
   inside kernel bodies.  TPU Pallas has no f64 vector unit — jax silently
   downcasts under jax_enable_x64=False and *fails to lower* otherwise —
   and host callbacks stall the systolic pipeline (they are also
   unsupported inside Pallas kernels on TPU).  CHI count math is exact in
   int32; accumulate in float32.

Violation examples:

    pl.pallas_call(f, grid=(b, h // bh),
                   in_specs=[pl.BlockSpec((1, bh), lambda i: (i, 0))], ...)
    # index map takes 1 arg, grid has rank 2

    pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(b,),
        in_specs=[pl.BlockSpec((1, h), lambda i: (i, 0))])
    # index map takes 1 arg; grid rank 1 + 1 prefetch operand = 2

    def cp_count_kernel(chi_ref, out_ref):
        if chi_ref[0, 0] > 0:        # traced value in Python `if`
            ...

Fix: match lambda arity to the grid; replace `if` with @pl.when or
jnp.where; keep accumulation in f32/int32.  Reference kernels:
src/repro/kernels/cp_count.py, mask_agg.py, pair_count.py, chi_build.py.
"""

    def check_module(self, ctx: ModuleCtx) -> list[Finding]:
        tree = ctx.tree
        kernel_names = {n.name for n in ast.walk(tree)
                        if isinstance(n, ast.FunctionDef)
                        and n.name.endswith("_kernel")}
        pallas_calls = [n for n in ast.walk(tree)
                        if isinstance(n, ast.Call)
                        and call_name(n) == "pallas_call"]
        if not kernel_names and not pallas_calls:
            return []
        findings: list[Finding] = []

        # local `grid = (a, b)` style assignments, for grid=grid resolution
        local_tuples: dict[str, int] = {}
        for n in ast.walk(tree):
            if isinstance(n, ast.Assign) and isinstance(n.value, ast.Tuple):
                for t in n.targets:
                    if isinstance(t, ast.Name):
                        local_tuples[t.id] = len(n.value.elts)

        for call in pallas_calls:
            ref = _first_kernel_ref(call)
            if ref:
                kernel_names.add(ref)
        grid_specs = [n for n in ast.walk(tree)
                      if isinstance(n, ast.Call)
                      and call_name(n) in _GRID_SPECS]
        for call in pallas_calls + grid_specs:
            arity = _grid_arity(call, local_tuples)
            if arity is None:
                continue
            for spec in _iter_blockspecs(call):
                lam = _index_map_lambda(spec)
                if lam is not None and len(lam.args.args) != arity:
                    findings.append(ctx.finding(
                        self.name, lam,
                        f"BlockSpec index_map takes "
                        f"{len(lam.args.args)} argument(s) but the grid "
                        f"rank plus scalar-prefetch operands is {arity} "
                        f"— Pallas passes one program id per grid axis, "
                        f"then one ref per prefetched operand"))

        for fn in ast.walk(tree):
            if isinstance(fn, ast.FunctionDef) and fn.name in kernel_names:
                findings.extend(self._check_body(ctx, fn))
        return findings

    def _check_body(self, ctx: ModuleCtx, fn: ast.FunctionDef):
        findings: list[Finding] = []
        for node in ast.walk(fn):
            if isinstance(node, (ast.If, ast.While)):
                kind = "if" if isinstance(node, ast.If) else "while"
                findings.append(ctx.finding(
                    self.name, node,
                    f"Python `{kind}` inside kernel body {fn.name} — "
                    f"control flow on traced values must use @pl.when / "
                    f"jnp.where; static specialization goes through "
                    f"functools.partial"))
            elif isinstance(node, ast.For):
                it = node.iter
                if not (isinstance(it, ast.Call)
                        and isinstance(it.func, ast.Name)
                        and it.func.id == "range"):
                    findings.append(ctx.finding(
                        self.name, node,
                        f"`for` over a non-range iterable inside kernel "
                        f"body {fn.name} — only static range(...) "
                        f"unrolls are traceable"))
            elif isinstance(node, ast.Call) \
                    and call_name(node) in _HOST_CALLS:
                findings.append(ctx.finding(
                    self.name, node,
                    f"host callback {call_name(node)}(...) inside kernel "
                    f"body {fn.name} — unsupported in TPU Pallas and "
                    f"stalls the pipeline"))
            elif (isinstance(node, ast.Attribute)
                  and node.attr == "float64") \
                    or (isinstance(node, ast.Name)
                        and node.id == "float64") \
                    or (isinstance(node, ast.Constant)
                        and node.value == "float64"):
                findings.append(ctx.finding(
                    self.name, node,
                    f"float64 inside kernel body {fn.name} — TPU Pallas "
                    f"has no f64 path; CHI count math is exact in "
                    f"int32/float32"))
        return findings


_FLOAT_DTYPES = {"float64", "float32", "float16", "bfloat16", "float_",
                 "double", "half"}


@register
class PopcountNoFloatRule(Rule):
    name = "popcount-no-float"
    summary = ("bitpacked popcount kernel bodies must stay integer-only — "
               "no float dtypes or float literals")
    doc = """\
Invariant: a function named `*_popcount_kernel` (the bitpacked binary-mask
tier's Pallas kernel bodies, kernels/popcount.py) mentions no float dtype
(float16/32/64, bfloat16, ...) and no float literal anywhere in its body.

Why it holds: the packed tier's entire win is that verification streams
uint32 words at 1/32 the float bytes and answers counts with bitwise
AND/OR + popcount in int32.  A float dtype inside the kernel body means
someone unpacked words back into float lanes (re-paying the 32x traffic
the tier exists to avoid) or routed the CP range / threshold compare into
the kernel.  Value semantics are precomputed OUTSIDE the kernel: the
wrappers collapse `[lv, uv)` on binary values to two int32 flags
(`f1 = lv <= 1 < uv`, `f0 = lv <= 0 < uv`) and `value > t` to effective-
word flags, so the traced body is pure integer math by construction —
which is also what makes the packed path bit-identical to the float
kernels.

Violation example:

    def _cp_popcount_kernel(roi_ref, lv_ref, mask_ref, out_ref, *, ...):
        m = mask_ref[0].astype(jnp.float32)   # unpacked float load
        out_ref[0] += jnp.sum((m >= lv_ref[0]).astype(jnp.int32))

Fix: keep words uint32 end to end; compute range/threshold flags in the
wrapper (popcount.py `_range_flags` / `_thresh_flags`) and pass them in as
int32 operands; count with `_popcount32(word & span_mask)`.
"""

    def check_module(self, ctx: ModuleCtx) -> list[Finding]:
        findings: list[Finding] = []
        for fn in ast.walk(ctx.tree):
            if not (isinstance(fn, ast.FunctionDef)
                    and fn.name.endswith("_popcount_kernel")):
                continue
            for node in ast.walk(fn):
                dtype = None
                if isinstance(node, ast.Attribute) \
                        and node.attr in _FLOAT_DTYPES:
                    dtype = node.attr
                elif isinstance(node, ast.Name) and node.id in _FLOAT_DTYPES:
                    dtype = node.id
                elif isinstance(node, ast.Constant) \
                        and isinstance(node.value, str) \
                        and node.value in _FLOAT_DTYPES:
                    dtype = node.value
                if dtype is not None:
                    findings.append(ctx.finding(
                        self.name, node,
                        f"float dtype {dtype} inside popcount kernel body "
                        f"{fn.name} — packed verification is integer-only; "
                        f"unpacking to float lanes re-pays the 32x traffic "
                        f"the bitpacked tier removes"))
                elif isinstance(node, ast.Constant) \
                        and isinstance(node.value, float):
                    findings.append(ctx.finding(
                        self.name, node,
                        f"float literal {node.value!r} inside popcount "
                        f"kernel body {fn.name} — value-range semantics "
                        f"belong in the wrapper's int32 flags "
                        f"(_range_flags/_thresh_flags), not the traced "
                        f"body"))
        return findings
