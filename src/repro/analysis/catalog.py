"""The analysis plane's subject registry: which kernels and plans exist,
at which pinned shapes they are checked, and what each kernel declares
about its grid.

**Shape lattice.**  The race detector sweeps index maps *concretely*, so
its guarantee is per lattice point, not universal (DESIGN.md §15 spells
out the soundness caveat).  Points are chosen to exercise every
structural regime of each kernel: single-block and multi-block grids,
padding (shape not a block multiple).  Grids stay tiny (tens to
hundreds of programs).  The graph kernels' vertex blocks are pinned at
the TPU's 1024-element tile, the smallest block Mosaic compiles.

**Declarations.**  ``KERNEL_DECLARATIONS`` maps a kernel *body* (keyed by
``(module, qualname)`` — two bodies in this repo share the name
``_scan_kernel``) to the grid axes the author intends to be sequential
(Pallas TPU executes grid axes as nested loops on one core, innermost
last; an accumulation axis is race-free *because* it is sequential).
The detector trusts these declarations only structurally: a declared
axis still must satisfy the revisit/injectivity/coverage rules, and any
captured body *without* a declaration is an error — adding a kernel
without registering it here fails CI.

**Plans.**  ``PLAN_CATALOG`` enumerates every
``(family × method × probe × frontier)`` runner configuration the
engines can produce, as ``build(instrument, max_rounds)`` thunks
returning the jitted runner plus abstract arguments, so the purity lint
can lower each one on abstract shapes and diff instrument variants.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable

from .capture import PallasCapture, capture_kernel

# Pinned plan shapes: small enough to trace every variant in seconds,
# large enough that pow2 padding and capacity clamps behave as at scale.
PLAN_N = 64
PLAN_M = 256
PLAN_WORKERS = 4
PLAN_WINDOW = 16
PLAN_UPDATE_W = 8
PLAN_INS_CAP = 64
PLAN_MAX_ROUNDS = 64


@dataclass(frozen=True)
class KernelDecl:
    """What a kernel body declares about its grid.

    sequential_axes: grid axes executed in order on one core that the
        kernel *relies on* (accumulation seeded at step 0, finalized at
        the last step, or an SMEM/VMEM carry).
    carry: the kernel carries scratch state across grid steps (must come
        with a nonempty sequential set; checked by the carry rule).
    """

    sequential_axes: frozenset = frozenset()
    carry: bool = False


def _decl(*axes, carry: bool = False) -> KernelDecl:
    return KernelDecl(sequential_axes=frozenset(axes), carry=carry)


KERNEL_DECLARATIONS: dict[tuple[str, str], KernelDecl] = {
    # (vertex-blocks, update-blocks): accumulates over update blocks
    # (seed at ui == 0, deaths at ui == nu-1)
    ("repro.kernels.counter_scatter", "_counter_kernel"): _decl(1),
    # one-shot per vertex block, no accumulation
    ("repro.kernels.first_live_scan", "_scan_kernel"): _decl(),
    ("repro.kernels.frontier_expand", "_expand_kernel"): _decl(),
    ("repro.kernels.bucket_peel", "_bucket_kernel"): _decl(),
    # sequential exclusive scan: SMEM carry across the (only) grid axis
    ("repro.kernels.frontier_compact", "_scan_kernel"): _decl(0, carry=True),
}


@dataclass
class KernelEntry:
    """One kernel wrapper plus its shape lattice.

    build(point) traces the real wrapper at that lattice point and
    returns every ``pallas_call`` it made (``analysis.capture``).
    """

    name: str
    points: tuple
    build: Callable[[dict], list]


def _sds(shape, dtype):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(shape, getattr(jnp, dtype))


def _build_counter_scatter(p: dict) -> list[PallasCapture]:
    from ..kernels.counter_scatter import counter_scatter_pallas
    n, b = p["n"], p["b"]
    return capture_kernel(
        counter_scatter_pallas,
        _sds((n,), "int32"), _sds((n,), "bool_"),
        _sds((b,), "int32"), _sds((b,), "int32"),
        block_v=p["block_v"], block_u=p["block_u"])


def _build_first_live(p: dict) -> list[PallasCapture]:
    from ..kernels.first_live_scan import first_live_scan
    n, w = p["n"], p["w"]
    return capture_kernel(
        first_live_scan,
        _sds((n, w), "bool_"), _sds((n, w), "bool_"), _sds((n,), "bool_"),
        block_v=p["block_v"])


def _build_frontier_expand(p: dict) -> list[PallasCapture]:
    from ..kernels.frontier_expand import frontier_expand
    n, w = p["n"], p["w"]
    return capture_kernel(
        frontier_expand,
        _sds((n, w), "bool_"), _sds((n, w), "bool_"), _sds((n,), "bool_"),
        block_v=p["block_v"])


def _build_bucket_peel(p: dict) -> list[PallasCapture]:
    from ..kernels.bucket_peel import bucket_peel_pallas
    n = p["n"]
    return capture_kernel(
        bucket_peel_pallas,
        _sds((n,), "int32"), _sds((n,), "bool_"), _sds((), "int32"),
        block_v=p["block_v"])


def _build_prefix_positions(p: dict) -> list[PallasCapture]:
    from ..kernels.frontier_compact import prefix_positions
    return capture_kernel(prefix_positions, _sds((p["n"],), "int32"),
                          block=p["block"])


def _build_frontier_compact(p: dict) -> list[PallasCapture]:
    from ..kernels.frontier_compact import frontier_compact_pallas
    return capture_kernel(frontier_compact_pallas, _sds((p["n"],), "bool_"),
                          capacity=p["cap"], block=p["block"])


def _build_sparse_expand(p: dict) -> list[PallasCapture]:
    from ..kernels.frontier_compact import sparse_expand_pallas
    n, m, c = p["n"], p["m"], p["c"]
    return capture_kernel(
        sparse_expand_pallas,
        _sds((n + 1,), "int32"), _sds((m,), "int32"), _sds((c,), "int32"),
        ecap=p["ecap"], block=p["block"])


KERNEL_CATALOG: tuple[KernelEntry, ...] = (
    # vertex-blocked kernels: blocks are whole 1024-element TPU tiles
    # (kernels.tiling), so multi-block and padded points need n > 1024
    KernelEntry("counter_scatter", (
        {"n": 4096, "b": 32, "block_v": 1024, "block_u": 8},   # 4×4 grid
        {"n": 2500, "b": 12, "block_v": 1024, "block_u": 8},   # padded
        {"n": 1024, "b": 8, "block_v": 1024, "block_u": 8},    # one block
    ), _build_counter_scatter),
    KernelEntry("first_live_scan", (
        {"n": 4096, "w": 16, "block_v": 1024},
        {"n": 2500, "w": 16, "block_v": 1024},             # padded
    ), _build_first_live),
    KernelEntry("frontier_expand", (
        {"n": 4096, "w": 16, "block_v": 1024},
        {"n": 2500, "w": 16, "block_v": 1024},
    ), _build_frontier_expand),
    KernelEntry("bucket_peel", (
        {"n": 4096, "block_v": 1024},
        {"n": 2500, "block_v": 1024},
    ), _build_bucket_peel),
    KernelEntry("prefix_positions", (
        {"n": 4096, "block": 1024},                        # 4 steps
        {"n": 2500, "block": 1024},                        # padded
    ), _build_prefix_positions),
    # frontier_compact / sparse_expand delegate every pallas_call to the
    # prefix_positions scan; capturing through them proves the boundary-
    # marker ownership path builds exactly those sequential scans.
    KernelEntry("frontier_compact", (
        {"n": 4096, "cap": 32, "block": 1024},
    ), _build_frontier_compact),
    KernelEntry("sparse_expand", (
        {"n": 32, "m": 64, "c": 16, "ecap": 4096, "block": 1024},
    ), _build_sparse_expand),
)


# -- plan catalog --------------------------------------------------------------

@dataclass
class PlanEntry:
    """One (family × method × probe × frontier) runner configuration.

    build(instrument, max_rounds) returns ``(jitted_runner,
    abstract_args)`` ready for ``jax.make_jaxpr`` /
    ``launch.lowering.trace_jaxpr``.
    """

    family: str
    variant: str
    build: Callable[[bool, int], tuple]
    tags: dict = field(default_factory=dict)

    @property
    def name(self) -> str:
        return f"{self.family}/{self.variant}"


def _i32(shape):
    return _sds(shape, "int32")


def _b(shape):
    return _sds(shape, "bool_")


def _fplan(mode: str):
    from ..core.common import frontier_plan
    return frontier_plan(mode, PLAN_N, PLAN_M)


def _trim_args(needs_transpose: bool):
    n, m = PLAN_N, PLAN_M
    tarrs = (_i32((n + 1,)), _i32((m,)), _i32((m,))) if needs_transpose \
        else None
    return (_i32((n + 1,)), _i32((m,)), tarrs, _i32((n,)), _b((n,)))


def _build_trim(method: str, probe: str, fmode: str, needs_transpose: bool,
                use_kernel: bool = False):
    def build(instrument: bool, max_rounds: int):
        from ..core.engine import _local_runner
        fn = _local_runner(method, probe, PLAN_WINDOW, use_kernel,
                           True, PLAN_WORKERS, batched=False,
                           fplan=_fplan(fmode), instrument=instrument,
                           max_rounds=max_rounds)
        return fn, _trim_args(needs_transpose)
    return build


def _build_reach(method: str, fmode: str, overflow: bool):
    def build(instrument: bool, max_rounds: int):
        from ..core.reach import _reach_runner
        fn = _reach_runner(method, PLAN_WINDOW, False, batched=False,
                           overflow=overflow, fplan=_fplan(fmode),
                           instrument=instrument, max_rounds=max_rounds)
        n, m = PLAN_N, PLAN_M
        if method == "push":
            garrs = (_i32((n + 1,)), _i32((m,)), _i32((m,)))
            tarrs = None
        else:
            garrs = (_i32((n + 1,)), _i32((m,)), None)
            tarrs = (_i32((n + 1,)), _i32((m,)))
        return fn, (garrs, tarrs, _b((n,)), _b((n,)))
    return build


def _build_peel(k_stop, fmode: str):
    def build(instrument: bool, max_rounds: int):
        from ..core.peel import _peel_runner
        fn = _peel_runner("bucket", k_stop, False, batched=False,
                          fplan=_fplan(fmode), instrument=instrument,
                          max_rounds=max_rounds)
        n, m = PLAN_N, PLAN_M
        garrs = (_i32((n + 1,)), _i32((m,)))
        tarrs = (_i32((n + 1,)), _i32((m,)), _i32((m,)))
        return fn, (garrs, tarrs, _b((n,)))
    return build


def _build_stream(full: bool, revivable: bool, fmode: str):
    def build(instrument: bool, max_rounds: int):
        from ..core.stream import _stream_runner
        fn = _stream_runner("ac4", False, full=full, revivable=revivable,
                            fplan=_fplan(fmode), instrument=instrument,
                            max_rounds=max_rounds)
        n, m, cap, w = PLAN_N, PLAN_M, PLAN_INS_CAP, PLAN_UPDATE_W
        tarrs = (_i32((n + 1,)), _i32((m,)), _i32((m,)), _i32((m,)))
        overlay = (_b((m,)), _i32((cap,)), _i32((cap,)), _b((cap,)))
        state = (_b((n,)), _i32((n,)))
        updates = tuple(_i32((w,)) for _ in range(7))
        return fn, (tarrs, overlay, state, updates)
    return build


def _plan_catalog() -> tuple[PlanEntry, ...]:
    entries: list[PlanEntry] = []
    # trim: ac3 (no transpose, windowed, dense-only frontier),
    # ac4/ac4* (transpose, dense probe), ac6 (windowed + sparse frontier)
    trim_axes = [
        ("ac3", "dense", "dense", False),
        ("ac3", "windowed", "dense", False),
        ("ac4", "dense", "dense", True),
        ("ac4", "dense", "sparse", True),
        ("ac4*", "dense", "dense", True),
        ("ac4*", "dense", "sparse", True),
        ("ac6", "dense", "dense", False),
        ("ac6", "dense", "sparse", False),
        ("ac6", "windowed", "dense", False),
    ]
    for method, probe, fmode, needs_t in trim_axes:
        entries.append(PlanEntry(
            "trim", f"{method}[probe={probe},frontier={fmode}]",
            _build_trim(method, probe, fmode, needs_t),
            tags={"method": method}))
    for fmode in ("dense", "sparse"):
        entries.append(PlanEntry(
            "reach", f"push[frontier={fmode}]",
            _build_reach("push", fmode, overflow=False)))
    for overflow in (False, True):
        entries.append(PlanEntry(
            "reach", f"pull[overflow={overflow}]",
            _build_reach("pull", "dense", overflow=overflow)))
    for k_stop in (None, 1):
        for fmode in ("dense", "sparse"):
            entries.append(PlanEntry(
                "peel", f"bucket[k_stop={k_stop},frontier={fmode}]",
                _build_peel(k_stop, fmode)))
    for full, revivable in ((True, False), (False, False), (False, True)):
        for fmode in ("dense", "sparse"):
            entries.append(PlanEntry(
                "stream",
                f"ac4[full={full},revivable={revivable},frontier={fmode}]",
                _build_stream(full, revivable, fmode)))
    return tuple(entries)


PLAN_CATALOG: tuple[PlanEntry, ...] = _plan_catalog()
