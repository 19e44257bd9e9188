"""Jaxpr-traced GEMM/chain discovery (program capture, tentpole PR 5).

``jax.make_jaxpr`` turns any jax callable — a model ``apply`` fn, an
``Engine`` prefill/decode step — into a closed jaxpr that this module
walks to harvest every contraction the program executes:

  * every ``dot_general`` equation (einsums lower to dot_generals)
    becomes a canonical :class:`GemmSite` under the paper's GEMM
    abstraction ``P(x,y) = sum_z A(x,z) B(y,z)``: ``m`` is the product
    of the lhs non-contracting non-batch extents, ``n`` the rhs
    counterpart, ``k`` the contraction product, and *batch* extents
    (dims shared by both operands, incl. those introduced by ``vmap``)
    are flattened into the site's repeat weight — a batched GEMM is the
    same mapping instance executed ``prod(batch)`` times, exactly the
    ``w_g`` occurrence-count convention of eq. 35;
  * closed-over sub-jaxprs are walked recursively with multiplicative
    repeat weights: a ``scan`` multiplies by its static trip count
    (``length``), ``cond`` branches and ``while`` bodies are harvested
    once (conservative — ``while`` trip counts are not static), and
    call-like primitives (``pjit``, ``custom_jvp_call``, remat, ...)
    are transparent; ``pallas_call`` is deliberately opaque — its
    interior is an already-GOMA-planned kernel, not a workload;
  * fusable producer->consumer chains are detected per jaxpr body
    (:class:`ChainSite`): a ``dot_general`` whose A operand is produced
    from one or more same-shape ``dot_general`` outputs through
    *elementwise-only* ops is the ``core.fusion.GemmChain`` tie
    (producer-N feeds consumer-K), and the elementwise path is
    classified onto the fused kernel's combine vocabulary
    (``ELEMENTWISE_OPS``).  Shape-changing ops (reshape/transpose/
    reduce) break the path by construction, which is what keeps
    attention's per-head-slice ties out (DESIGN.md §Capture).

Everything here is shape-level: tracing never materializes arrays, so
capturing a 70B-parameter program costs milliseconds, and the harvest is
exact — it reads the program jax will actually execute rather than a
hand-maintained extraction table.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Iterator

import jax

from jax.extend.core import Literal

# Shape-preserving elementwise primitives a fused chain can stream
# through (plus comparisons/select so relu-style gates classify).
ELEMENTWISE_PRIMS = frozenset({
    "add", "sub", "mul", "div", "max", "min", "neg", "abs", "sign",
    "exp", "exp2", "log", "log1p", "expm1", "tanh", "logistic", "erf",
    "erfc", "rsqrt", "sqrt", "square", "cbrt", "integer_pow", "pow",
    "convert_element_type", "select_n", "stop_gradient",
    "optimization_barrier", "clamp", "floor", "ceil", "round",
    "is_finite", "sin", "cos", "copy", "real", "imag",
    "and", "or", "not", "xor", "gt", "lt", "ge", "le", "eq", "ne",
})

# Call-like primitives whose bodies are inlined for elementwise analysis.
_CALL_PRIMS = frozenset({
    "jit", "pjit", "closed_call", "core_call", "custom_jvp_call",
    "custom_vjp_call", "custom_vjp_call_jaxpr", "remat", "checkpoint",
    "remat2", "custom_lin",
})


@dataclasses.dataclass(frozen=True)
class GemmSite:
    """One harvested contraction site, canonicalized to the paper's GEMM."""

    dims: tuple[int, int, int]     # (m, n, k) with batch dims flattened
    dtype: str                     # output dtype of the site
    weight: int                    # repeat weight incl. batch product
    batch: int                     # flattened batch-dim product
    path: str                      # provenance (scope chain / eqn index)


@dataclasses.dataclass(frozen=True)
class ChainSite:
    """One detected fusable producer->consumer chain site."""

    producer_dims: tuple[int, int, int]
    consumer_dims: tuple[int, int, int]
    producer_count: int
    elementwise: str               # core.fusion.ELEMENTWISE_OPS member
    weight: int                    # repeat weight incl. batch product
    batch: int
    path: str


@dataclasses.dataclass
class CaptureResult:
    """Raw harvest of one traced program (pre-IR; see capture.program)."""

    name: str
    sites: list[GemmSite] = dataclasses.field(default_factory=list)
    chains: list[ChainSite] = dataclasses.field(default_factory=list)


def _prod(xs) -> int:
    return int(math.prod(xs)) if xs else 1


def _dot_dims(eqn) -> tuple[int, int, int, int] | None:
    """(m, n, k, batch) of one dot_general equation, or None when the
    site is degenerate (zero-extent, or a contraction-free broadcast
    multiply that einsum decomposition emits for combine weights)."""
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lsh = tuple(eqn.invars[0].aval.shape)
    rsh = tuple(eqn.invars[1].aval.shape)
    batch = _prod([lsh[i] for i in lb])
    k = _prod([lsh[i] for i in lc])
    m = _prod([lsh[i] for i in range(len(lsh))
               if i not in lc and i not in lb])
    n = _prod([rsh[i] for i in range(len(rsh))
               if i not in rc and i not in rb])
    if 0 in (m, n, k, batch):
        return None
    if k == 1 and min(m, n) == 1 and not lc:
        return None                # broadcast multiply, not a GEMM
    return m, n, k, batch


def _inner_jaxpr(obj):
    """The raw Jaxpr behind a ClosedJaxpr (or the Jaxpr itself)."""
    return obj.jaxpr if hasattr(obj, "jaxpr") else obj


def _jaxprs_in(value) -> Iterator[Any]:
    """Closed/raw jaxprs nested in one eqn param value."""
    if hasattr(value, "jaxpr") or hasattr(value, "eqns"):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _jaxprs_in(v)


def _sub_jaxprs(eqn) -> Iterator[tuple[Any, int, str]]:
    """(sub_jaxpr, weight multiplier, path tag) triples of one eqn."""
    prim = eqn.primitive.name
    if prim == "pallas_call":
        return                      # opaque: kernel interior, not workload
    if prim == "scan":
        length = int(eqn.params["length"])
        yield eqn.params["jaxpr"], length, f"scan[{length}]"
        return
    if prim == "while":
        # trip count is data-dependent: harvest one iteration and let the
        # caller scale by an external estimate if it has one
        yield eqn.params["body_jaxpr"], 1, "while"
        yield eqn.params["cond_jaxpr"], 1, "while_cond"
        return
    if prim == "cond":
        for i, br in enumerate(eqn.params["branches"]):
            yield br, 1, f"cond.br{i}"
        return
    for value in eqn.params.values():
        for sub in _jaxprs_in(value):
            yield sub, 1, prim


def _elementwise_body(closed) -> set[str] | None:
    """Primitive names of a call body iff it is elementwise-only."""
    names: set[str] = set()
    for eqn in _inner_jaxpr(closed).eqns:
        nm = eqn.primitive.name
        if nm in ELEMENTWISE_PRIMS or nm == "broadcast_in_dim":
            names.add(nm)
            continue
        if nm in _CALL_PRIMS:
            subs = [s for v in eqn.params.values() for s in _jaxprs_in(v)]
            if not subs:
                return None
            for sub in subs:
                inner = _elementwise_body(sub)
                if inner is None:
                    return None
                names |= inner
            fn_name = eqn.params.get("name")
            if fn_name:
                names.add(str(fn_name))
            continue
        return None
    return names


_LINEAR_OPS = frozenset({
    "mul", "add", "sub", "neg", "copy", "convert_element_type",
    "broadcast_in_dim", "stop_gradient", "optimization_barrier"})
# Wrappers a value passes through without changing combine structure.
_CAST_PRIMS = frozenset({"convert_element_type", "copy",
                         "stop_gradient", "optimization_barrier"})


def _classify_elementwise(ops: set[str]) -> str | None:
    """Map an elementwise-path op set onto the fused kernel's combine
    vocabulary (core.fusion.ELEMENTWISE_OPS); None = not realizable."""
    if "silu" in ops or "logistic" in ops:
        return "silu_mul"
    if "gelu" in ops or "erf" in ops or "tanh" in ops:
        return "gelu_mul"
    if ("relu" in ops or "max" in ops) and \
            ops & {"integer_pow", "square", "pow"}:
        return "sqrelu_mul"
    if ops <= _LINEAR_OPS:
        return "identity"
    return None


def _resolve_casts(v, produced):
    """Peel pure-cast wrappers; returns (var, producing eqn or None)."""
    while True:
        eqn = produced.get(v)
        if eqn is None or eqn.primitive.name not in _CAST_PRIMS:
            return v, eqn
        v = eqn.invars[0]


def _combine_is_kernel_shaped(var, produced, producers) -> bool:
    """Multi-producer combines must match the fused kernel's ``act(g) *
    u`` structure (kernels/goma_fused.ACTIVATIONS): the intermediate's
    top-level op is a ``mul`` with exactly two producers, at least one
    consumed bare (the un-activated u side; both bare = the identity
    combine ``g * u``).  An additive or otherwise non-multiplicative
    combine is analytically chainable but not in the kernel vocabulary,
    so it is rejected rather than mislabelled.  Single-producer chains
    (unary intermediate ``f(g)``) carry a descriptive label and skip
    this check — the chain objective never reads the combine."""
    if len(producers) == 1:
        return True
    if len(producers) != 2:
        return False
    top, top_eqn = _resolve_casts(var, produced)
    if top_eqn is None or top_eqn.primitive.name != "mul":
        return False
    producer_outs = {id(ov) for p in producers for ov in p.outvars}
    bare = sum(id(_resolve_casts(s, produced)[0]) in producer_outs
               for s in top_eqn.invars if not isinstance(s, Literal))
    return bare >= 1


def _trace_intermediate(var, produced, use_eqns, consumer_eqn):
    """Walk the consumer's A operand back through elementwise-only ops.

    Returns (producer dot_general eqns, op-name set) when (a) every
    array leaf of the path is a same-shape dot_general output and (b) no
    value computed on the path — producer outputs included — is consumed
    outside the path or returned from the body, so eliding the
    intermediate's DRAM round-trip is sound: nothing else needs it in
    memory.  Multiple uses *inside* the path (e.g. inlined gelu reading
    its argument three times) are fine — the value is re-read from the
    same resident tile.  Returns None otherwise.
    """
    target_shape = tuple(var.aval.shape)
    stack, seen = [var], set()
    producers: list = []
    ops: set[str] = set()
    path_eqns: set[int] = {id(consumer_eqn)}
    path_eqn_objs: list = []
    while stack:
        v = stack.pop()
        if isinstance(v, Literal):
            continue
        if v in seen:
            continue
        seen.add(v)
        eqn = produced.get(v)
        if eqn is None:
            if getattr(v.aval, "shape", None) == ():
                continue            # scalar input (eps, scale, ...)
            return None             # array input feeds the path directly
        nm = eqn.primitive.name
        if nm == "dot_general":
            if tuple(v.aval.shape) != target_shape:
                return None
            if eqn not in producers:
                producers.append(eqn)
            continue
        if nm == "broadcast_in_dim":
            if id(eqn) not in path_eqns:
                path_eqns.add(id(eqn))
                path_eqn_objs.append(eqn)
            stack.append(eqn.invars[0])
            continue
        if nm in _CALL_PRIMS:
            subs = [s for val in eqn.params.values()
                    for s in _jaxprs_in(val)]
            body_ops = None
            for sub in subs:
                body_ops = _elementwise_body(sub)
                if body_ops is None:
                    return None
                ops |= body_ops
            if body_ops is None:
                return None
            fn_name = eqn.params.get("name")
            if fn_name:
                ops.add(str(fn_name))
            if id(eqn) not in path_eqns:
                path_eqns.add(id(eqn))
                path_eqn_objs.append(eqn)
            stack.extend(eqn.invars)
            continue
        if nm in ELEMENTWISE_PRIMS:
            ops.add(nm)
            if id(eqn) not in path_eqns:
                path_eqns.add(id(eqn))
                path_eqn_objs.append(eqn)
            stack.extend(eqn.invars)
            continue
        return None
    if not producers:
        return None
    # escape check: every value the path computes — producer outputs and
    # *all* outputs of visited equations, incl. sibling outputs of
    # multi-output calls the backward walk never reached — is consumed
    # only by path equations (the consumer included), never elsewhere
    # and never as a body output
    for eqn in producers + path_eqn_objs:
        for ov in eqn.outvars:
            for user in use_eqns.get(ov, ()):
                if user == "output" or id(user) not in path_eqns:
                    return None
    return producers, ops


def _detect_chains(jaxpr, produced, use_eqns, weight, path,
                   out: CaptureResult) -> None:
    for i, eqn in enumerate(jaxpr.eqns):
        if eqn.primitive.name != "dot_general":
            continue
        lhs = eqn.invars[0]
        if isinstance(lhs, Literal) or lhs not in produced:
            continue
        cdims = _dot_dims(eqn)
        if cdims is None:
            continue
        hit = _trace_intermediate(lhs, produced, use_eqns, eqn)
        if hit is None:
            continue
        producers, ops = hit
        elem = _classify_elementwise(ops)
        if elem is None:
            continue
        if not _combine_is_kernel_shaped(lhs, produced, producers):
            continue
        pdims = {_dot_dims(p) for p in producers}
        if len(pdims) != 1 or None in pdims:
            continue                # producers must share one shape
        pm, pn, pk, pb = next(iter(pdims))
        cm, cn, ck, cb = cdims
        if pm != cm or pn != ck or pb != cb:
            continue                # the producer-N / consumer-K tie
        out.chains.append(ChainSite(
            producer_dims=(pm, pn, pk), consumer_dims=(cm, cn, ck),
            producer_count=len(producers), elementwise=elem,
            weight=weight * cb, batch=cb, path=f"{path}/chain#{i}"))


def _walk(jaxpr, weight: int, path: str, out: CaptureResult) -> None:
    produced: dict = {}
    use_eqns: dict = {}              # var -> [eqn | "output"]
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if not isinstance(v, Literal):
                use_eqns.setdefault(v, []).append(eqn)
        for v in eqn.outvars:
            produced[v] = eqn
    for v in jaxpr.outvars:
        if not isinstance(v, Literal):
            use_eqns.setdefault(v, []).append("output")

    for i, eqn in enumerate(jaxpr.eqns):
        if eqn.primitive.name == "dot_general":
            dims = _dot_dims(eqn)
            if dims is None:
                continue
            m, n, k, batch = dims
            out.sites.append(GemmSite(
                dims=(m, n, k),
                dtype=str(eqn.outvars[0].aval.dtype),
                weight=weight * batch, batch=batch,
                path=f"{path}/dot#{i}"))
            continue
        for sub, mult, tag in _sub_jaxprs(eqn):
            _walk(_inner_jaxpr(sub), weight * mult, f"{path}/{tag}", out)
    _detect_chains(jaxpr, produced, use_eqns, weight, path, out)


def harvest_jaxpr(closed_jaxpr, *, name: str = "program",
                  weight: int = 1) -> CaptureResult:
    """Walk a (closed) jaxpr into a raw :class:`CaptureResult`."""
    out = CaptureResult(name=name)
    _walk(_inner_jaxpr(closed_jaxpr), weight, name, out)
    return out


def capture(fn: Callable, *example_args, name: str = "program",
            weight: int = 1, **example_kwargs) -> CaptureResult:
    """Trace ``fn`` on example args (arrays or ShapeDtypeStructs — the
    trace is shape-level, nothing is materialized) and harvest every
    contraction site and fusable chain it executes."""
    from ..obs.registry import get_registry
    from ..obs.tracing import span as _span
    get_registry().inc("capture.traces")
    with _span("capture.trace", program=name) as sp:
        closed = jax.make_jaxpr(fn)(*example_args, **example_kwargs)
        result = harvest_jaxpr(closed, name=name, weight=weight)
        if sp:
            sp.attrs.update(sites=len(result.sites),
                            chains=len(result.chains))
        return result
