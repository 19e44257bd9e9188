"""GOMA -> TPU adaptation: plan Pallas GEMM tilings with the exact solver.

The TPU memory hierarchy instantiates GOMA's 5-level template (DESIGN.md
§4): HBM≙DRAM, VMEM≙SRAM, the 128x128 MXU≙PE-array with a *hard-wired*
spatial tile (fixed_spatial = (128,128,1)), accumulator VREGs≙regfile.
Bypass degenerates (Mosaic always stages through VMEM) — what survives is
tile-shape selection under the VMEM capacity constraint and walking-axis
selection, i.e. exactly the solver's remaining degrees of freedom.

Constraint added for Pallas realizability: a non-z outer walk with partial
reduction (L1_z < K) would imply partial-sum HBM round-trips, which a
single pallas_call cannot express (output blocks persist only across
consecutive grid steps).  We therefore solve twice if needed: free, then
restricted to alpha01 = z; GOMA's energy objective almost always picks the
z-walk on its own (partial-sum DRAM traffic is the most expensive term).
"""
from __future__ import annotations

import dataclasses
import functools
import os

from .fusion import GemmChain
from .geometry import Gemm, Mapping
from .hardware import TPUV5E_LIKE, AcceleratorSpec
from .solver import SolveResult, solve

MXU = 128
# VMEM of one v5e TensorCore as TPUV5E_LIKE describes it: the scoped limit
# every GOMA kernel declares to Mosaic.  The planner hands at most
# VMEM_BUDGET_BYTES of it to pipeline buffers and accumulators; the rest
# is Mosaic's own scratch.
VMEM_LIMIT_BYTES = 16 * 1024 * 1024
VMEM_BUDGET_BYTES = TPUV5E_LIKE.sram_words      # 8-bit words = bytes

# --- plan-store read-through ------------------------------------------------
# When a plan store is installed (explicitly via set_plan_store or through
# the GOMA_PLAN_DB env var), every tiling solve first consults the
# database; misses are solved once and written back, so a fleet of
# processes sharing one store converges to zero inline solves.
_PLAN_STORE = None
_PLAN_STORE_RESOLVED = False


def set_plan_store(store) -> None:
    """Install (or clear, with None) the process-wide plan store.

    Changing to a *different* store flushes the in-process plan cache so
    future lookups are served through (and recorded in) the new store;
    re-installing the current store keeps the warm cache."""
    global _PLAN_STORE, _PLAN_STORE_RESOLVED
    changed = store is not _PLAN_STORE
    _PLAN_STORE = store
    _PLAN_STORE_RESOLVED = True
    if changed:
        plan_gemm_tiling.cache_clear()
        _plan_fused_mlp.cache_clear()


def get_plan_store():
    """The installed store, lazily resolved from $GOMA_PLAN_DB once."""
    global _PLAN_STORE, _PLAN_STORE_RESOLVED
    if not _PLAN_STORE_RESOLVED:
        _PLAN_STORE_RESOLVED = True
        if os.environ.get("GOMA_PLAN_DB", "").strip():
            from ..planner.store import resolve_default_store
            _PLAN_STORE = resolve_default_store()
    return _PLAN_STORE


def _tpu_solve(gemm: Gemm, hw: AcceleratorSpec,
               allowed_walk01: tuple[str, ...] | None) -> SolveResult:
    store = get_plan_store()
    if store is not None:
        from ..planner.batch import cached_solve
        return cached_solve(gemm, hw, objective="energy",
                            allowed_walk01=allowed_walk01, store=store,
                            warm_start=True)
    return solve(gemm, hw, objective="energy",
                 allowed_walk01=allowed_walk01)


def _pad_to(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


@dataclasses.dataclass(frozen=True)
class TpuTilePlan:
    """A GOMA-solved Pallas tiling for C[M,N] = A[M,K] @ B[K,N]."""

    M: int
    N: int
    K: int
    padded: tuple[int, int, int]
    block: tuple[int, int, int]       # (bm, bn, bk) = VMEM (L1) tile
    grid_order: tuple[str, ...]       # outer -> inner pallas grid dims
    walk: str                         # GOMA's alpha_{0-1}
    objective: float                  # modeled pJ / MAC
    solve_time_s: float

    @property
    def grid(self) -> tuple[int, ...]:
        pm, pn, pk = self.padded
        bm, bn, bk = self.block
        sizes = {"m": pm // bm, "n": pn // bn, "k": pk // bk}
        return tuple(sizes[g] for g in self.grid_order)

    def vmem_bytes(self, dtype_bytes: int) -> int:
        """VMEM goma_gemm allocates for this plan: two pipeline buffers of
        each block, plus the f32 accumulator when k takes several steps."""
        bm, bn, bk = self.block
        acc = 4 * bm * bn if self.padded[2] > bk else 0
        return 2 * dtype_bytes * (bm * bk + bk * bn + bm * bn) + acc


def tpu_spec(dtype_bytes: int = 2,
             base: AcceleratorSpec = TPUV5E_LIKE) -> AcceleratorSpec:
    """The v5e spec in the terms the Pallas kernels realize.

    VMEM: the base budget is in bytes.  The Pallas pipeline holds two
    buffers of every block and goma_gemm adds an f32 accumulator for its
    output block, so one word of the solver's tile footprint costs at most
    ``2 * dtype_bytes + 4`` bytes; in those words every feasible tiling's
    ``TpuTilePlan.vmem_bytes`` fits the budget.

    Alignment: Mosaic tiles the last two dims of a block in (8, 128)
    units, so every VMEM tile is an MXU multiple or the whole padded
    extent (K below one MXU is left unpadded)."""
    return dataclasses.replace(
        base,
        name=f"{base.name}-{dtype_bytes}B",
        sram_words=base.sram_words // (2 * dtype_bytes + 4),
        rf_words=base.rf_words,
        l1_align=(MXU, MXU, MXU),
    )


def tpu_problem(M: int, N: int, K: int, *, dtype_bytes: int = 2
                ) -> tuple[Gemm, AcceleratorSpec, tuple[int, int, int]]:
    """The (padded Gemm, spec, padded dims) GOMA instance of a TPU GEMM —
    the identity under which plans are stored and looked up."""
    pm, pn = _pad_to(M, MXU), _pad_to(N, MXU)
    pk = _pad_to(K, MXU) if K >= MXU else K
    hw = tpu_spec(dtype_bytes)
    return Gemm(pm, pn, pk, f"tpu_{M}x{N}x{K}"), hw, (pm, pn, pk)


def plan_from_mapping(M: int, N: int, K: int,
                      padded: tuple[int, int, int], m: Mapping, *,
                      objective: float = float("nan"),
                      solve_time_s: float = 0.0) -> TpuTilePlan:
    """Materialize a TpuTilePlan from an (already solved) mapping — the
    path by which cached/manifest plans skip the solver entirely."""
    bm, bn, bk = m.L1
    # pallas grid order: GOMA's walking axis is the innermost grid dim
    axis_of = {"x": "m", "y": "n", "z": "k"}
    inner = axis_of[m.alpha01]
    order = [g for g in ("m", "n", "k") if g != inner] + [inner]
    # degenerate dims drop out of the grid ordering naturally (size-1 dims
    # stay; pallas handles trip-1 grid entries)
    return TpuTilePlan(M=M, N=N, K=K, padded=padded,
                       block=(bm, bn, bk), grid_order=tuple(order),
                       walk=m.alpha01, objective=objective,
                       solve_time_s=solve_time_s)


@dataclasses.dataclass(frozen=True)
class FusedTilePlan:
    """A GOMA-chain-solved Pallas tiling for the fused gated-MLP op:
    ``out[M,N2] = act(A@Wg, A@Wu) @ Wd`` with A ``(M,K)``, Wg/Wu
    ``(K,FF)``, Wd ``(FF,N2)`` and the intermediate ``(bm, FF)`` strip
    held in VMEM scratch.

    ``fused=False`` records that no strip height was residency-feasible,
    that the chain solver kept the unfused pair, or that the fused
    kernel's blocks (Wd's whole block among them) exceed the VMEM budget:
    callers run per-GEMM ``gemm`` calls instead.
    """

    M: int
    FF: int
    K: int
    N2: int
    padded: tuple[int, int, int, int]     # (pm, pff, pk, pn2)
    fused: bool
    bm: int                               # shared m-strip height
    bk: int                               # producer reduction tile
    objective: float                      # chain objective, absolute pJ
    unfused_objective: float
    solve_time_s: float

    @property
    def grid(self) -> tuple[int, int]:
        pm, pff, pk, pn2 = self.padded
        return (pm // self.bm, pk // self.bk)

    def vmem_bytes(self, dtype_bytes: int) -> int:
        """VMEM goma_fused allocates for this plan: two pipeline buffers of
        each block — Wd's whole (pff, pn2) block among them — plus the two
        f32 (bm, pff) strips."""
        pm, pff, pk, pn2 = self.padded
        bm, bk = self.bm, self.bk
        blocks = bm * bk + 2 * bk * pff + pff * pn2 + bm * pn2
        return 2 * dtype_bytes * blocks + 2 * 4 * bm * pff

    def producer_plan(self) -> TpuTilePlan:
        """The equivalent single-GEMM tiling of one producer link — the
        unfused composition the fused kernel must bit-match (full-width
        N block, same bm/bk, k-walk)."""
        pm, pff, pk, pn2 = self.padded
        return TpuTilePlan(M=self.M, N=self.FF, K=self.K,
                           padded=(pm, pff, pk),
                           block=(self.bm, pff, self.bk),
                           grid_order=("m", "n", "k"), walk="z",
                           objective=float("nan"), solve_time_s=0.0)

    def consumer_plan(self) -> TpuTilePlan:
        """The consumer link's tiling: the compatibility pin makes the
        K tile full (nk == 1), so the composition's second matmul is the
        single-k fast path — one fp32 dot per block, exactly what the
        fused kernel computes in-register."""
        pm, pff, pk, pn2 = self.padded
        return TpuTilePlan(M=self.M, N=self.N2, K=self.FF,
                           padded=(pm, pn2, pff),
                           block=(self.bm, pn2, pff),
                           grid_order=("m", "n", "k"), walk="z",
                           objective=float("nan"), solve_time_s=0.0)


def fused_mlp_problem(M: int, FF: int, K: int, N2: int | None = None, *,
                      dtype_bytes: int = 2):
    """The (padded GemmChain, spec, padded dims) chain instance of a TPU
    fused MLP — the identity under which fused plans are stored.

    FF is both the producer's N and the consumer's K, so it is always
    padded to the MXU (the intermediate is a matmul output)."""
    if N2 is None:
        N2 = K
    pm, pff, pn2 = _pad_to(M, MXU), _pad_to(FF, MXU), _pad_to(N2, MXU)
    pk = _pad_to(K, MXU) if K >= MXU else K
    hw = tpu_spec(dtype_bytes)
    chain = GemmChain(
        producer=Gemm(pm, pff, pk, f"tpu_fused_{M}x{FF}x{K}_gate_up"),
        consumer=Gemm(pm, pn2, pff, f"tpu_fused_{M}x{FF}x{K}_down"),
        producer_count=2, elementwise="silu_mul",
        name=f"tpu_fused_mlp_{M}x{FF}x{K}x{N2}")
    return chain, hw, (pm, pff, pk, pn2)


def plan_fused_mlp(M: int, FF: int, K: int, N2: int | None = None, *,
                   dtype_bytes: int = 2) -> FusedTilePlan:
    """GOMA-chain-optimal fused-MLP tiling (bm, bk) for the Pallas fused
    kernel, read-through cached in the plan store's fused section when
    one is installed.

    The *fused* producer links are solved under
    ``allowed_walk01=("z",)`` — the fused kernel accumulates the strip
    in VMEM scratch across k steps, so a non-z outer walk (partial
    strips round-tripping HBM) is not expressible.  The unfused
    baseline stays unrestricted (see ``solve_chain``), so a fused plan
    is only recorded when it beats every unfused realization."""
    # N2 defaults to K; normalize before the cache so the 3- and 4-arg
    # calling conventions share one entry (one chain solve, not two)
    return _plan_fused_mlp(M, FF, K, K if N2 is None else N2,
                           dtype_bytes=dtype_bytes)


@functools.lru_cache(maxsize=512)
def _plan_fused_mlp(M: int, FF: int, K: int, N2: int, *,
                    dtype_bytes: int = 2) -> FusedTilePlan:
    chain, hw, padded = fused_mlp_problem(M, FF, K, N2,
                                          dtype_bytes=dtype_bytes)
    store = get_plan_store()
    if store is not None:
        from ..planner.batch import cached_solve_chain
        res = cached_solve_chain(chain, hw, objective="energy",
                                 allowed_walk01=("z",), store=store)
    else:
        from .fusion import solve_chain
        res = solve_chain(chain, hw, objective="energy",
                          allowed_walk01=("z",))
    cert = res.certificate
    plan = FusedTilePlan(M=M, FF=FF, K=K, N2=N2, padded=padded,
                         fused=False, bm=0, bk=0,
                         objective=cert.unfused_objective,
                         unfused_objective=cert.unfused_objective,
                         solve_time_s=cert.solve_time_s)
    if cert.fused and res.producer_mapping is not None:
        fused = dataclasses.replace(
            plan, fused=True, bm=int(res.producer_mapping.L1[0]),
            bk=int(res.producer_mapping.L1[2]), objective=cert.objective)
        # the chain solve prices the strips but not Wd, which the kernel
        # keeps whole in VMEM: a chain whose Wd does not fit stays unfused
        if fused.vmem_bytes(dtype_bytes) <= VMEM_BUDGET_BYTES:
            return fused
    return plan


@functools.lru_cache(maxsize=512)
def plan_gemm_tiling(M: int, N: int, K: int,
                     *, dtype_bytes: int = 2) -> TpuTilePlan:
    """GOMA-optimal (bm, bn, bk) + grid order for a (possibly padded) GEMM.

    Dims are padded so M, N are MXU multiples and every padded dim is a
    power-of-two-rich size (the divisor lattice of the padded dims is the
    Pallas-legal tile set).  With a plan store installed the solve is
    read-through cached across processes (see set_plan_store)."""
    gemm, hw, padded = tpu_problem(M, N, K, dtype_bytes=dtype_bytes)
    pk = padded[2]
    res = _tpu_solve(gemm, hw, None)
    m = res.mapping
    if m is None:
        raise ValueError(f"no feasible TPU mapping for {gemm}")
    if m.alpha01 != "z" and m.L1[2] < pk:
        # partial-sum HBM traffic not expressible in one pallas_call
        res = _tpu_solve(gemm, hw, ("z",))
        m = res.mapping
    return plan_from_mapping(M, N, K, padded, m,
                             objective=res.certificate.objective,
                             solve_time_s=res.certificate.solve_time_s)
